"""Share of the traced window in which no operation ran on the device
(profiler trace: 1 - union of device-op intervals / window), in %."""


def read(ctx):
    if not ctx.get("busy_s") or not ctx.get("window_s"):
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
