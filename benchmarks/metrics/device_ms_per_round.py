"""Device busy time in the traced window per engine round retired in it
(the program's round_ctr), in ms."""


def read(ctx):
    if not ctx.get("busy_s") or not ctx.get("rounds"):
        return None
    return 1e3 * ctx["busy_s"] / ctx["rounds"]
