"""Device time of the contended mesh's flight loop (scope
``engine.noc_flight``, nested in memory resolve) per engine round
(``round_ctr``) retired in the traced window, in ms: each op's own time,
by the innermost scope of its op_name (``phases.reduce``).  Silent where
the program traces no such scope: a hop-counter mesh, or a program that
predates the scope."""

PHASE = "engine.noc_flight"


def read(ctx):
    split = ctx.get("phase_s") or {}
    if PHASE not in split or not ctx.get("rounds"):
        return None
    return 1e3 * split[PHASE] / ctx["rounds"]
