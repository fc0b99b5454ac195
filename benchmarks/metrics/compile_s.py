"""Seconds jax spent tracing, lowering and compiling (or loading from the
compile cache) during set-up: its /jax/core/compile/* monitoring events."""


def read(ctx):
    return ctx.get("compile_s") or None
