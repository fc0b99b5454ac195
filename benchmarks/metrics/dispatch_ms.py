"""Mean host time of the program's ``sim.dispatch`` span (the enqueue of
one polling window's device program, in ``Simulator.run``) over the spans
that end in the traced window, in ms."""


def read(ctx):
    d = (ctx.get("span_s") or {}).get("sim.dispatch") or []
    if not d:
        return None
    return 1e3 * sum(d) / len(d)
