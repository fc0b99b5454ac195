"""Mean host time of a Simulator construction inside the window (trace
upload and state allocation; the harness's bench.sim_init span), in ms.
Only restart-mode cells build simulators inside the window."""


def read(ctx):
    s = ctx.get("sim_init_s") or []
    if not s:
        return None
    return 1e3 * sum(s) / len(s)
