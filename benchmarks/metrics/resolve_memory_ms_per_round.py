"""Device time of memory resolve (scope ``engine.resolve_memory``) per engine round (``round_ctr``)
retired in the traced window, in ms: each op's own time, by the innermost
scope of its op_name (``phases.reduce``)."""

PHASES = ("engine.resolve_memory",)


def read(ctx):
    split = ctx.get("phase_s")
    if not split or not ctx.get("rounds"):
        return None
    return 1e3 * sum(split[p] for p in PHASES) / ctx["rounds"]
