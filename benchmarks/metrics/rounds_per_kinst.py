"""Engine rounds (round_ctr) per thousand simulated instructions retired
in the traced window: the program's own counters."""


def read(ctx):
    if not ctx.get("icount"):
        return None
    return ctx["rounds"] / (ctx["icount"] / 1e3)
