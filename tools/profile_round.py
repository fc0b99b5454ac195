"""Fused per-round device-cost breakdown on the attached backend.

Times block retirement, the complex slot, resolve, and the whole quantum
step separately (each iterated inside one jitted fori_loop on a mid-run
state) — the numbers that matter for the engine's rounds/sec ceiling.

Usage: python tools/profile_round.py [tiles] [iters] [--set sec/key=val ...]

``--set`` forwards config overrides, so before/after comparisons of the
engine's perf knobs are one command each, e.g.:

    python tools/profile_round.py 1024 20 --set tpu/window_cache=false
    python tools/profile_round.py 1024 20 --set tpu/block_events=4
"""

import sys
import time

import jax

import graphite_tpu  # noqa: F401,E402  (x64 + the compile-cache policy)

from graphite_tpu.config import (apply_set_overrides, load_config, split_set_overrides)
from graphite_tpu.engine import resolve as rs
from graphite_tpu.engine.core import _block_retire, _complex_slot
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams


def _timed(fn, state, ta, iters):
    # ta rides as a jit ARGUMENT: closure-capturing the trace arrays
    # embeds them as HLO literals, which at 1024 tiles bloats the
    # program and every cache key.
    @jax.jit
    def loop(s, t):
        return jax.lax.fori_loop(0, iters, lambda i, x: fn(x, t), s)

    jax.block_until_ready(loop(state, ta))
    t0 = time.perf_counter()
    jax.block_until_ready(loop(state, ta))
    return time.perf_counter() - t0


def fused(fn, state, ta, iters):
    """Marginal per-iteration cost: time the fused loop at ``iters`` and
    ``2*iters`` and difference — cancels the per-call dispatch constant,
    which otherwise dominates at small tile counts."""
    t1 = _timed(fn, state, ta, iters)
    t2 = _timed(fn, state, ta, 2 * iters)
    return max(t2 - t1, 0.0) / iters * 1e6


def main():
    args, overrides = split_set_overrides(sys.argv[1:])
    T = int(args[0]) if len(args) > 0 else 64
    iters = int(args[1]) if len(args) > 1 else 50
    cfg = load_config()
    cfg.set("general/total_cores", T)
    apply_set_overrides(cfg, overrides)
    params = SimParams.from_config(cfg)
    trace = synth.gen_radix(num_tiles=T, keys_per_tile=256, seed=1)
    sim = Simulator(params, trace)
    sim.run(max_steps=4)   # mid-run state: warm caches, parked requests
    state, ta = sim.state, sim.trace
    if overrides:
        print(f"overrides: {' '.join(overrides)}", flush=True)

    from graphite_tpu.engine import quantum
    from graphite_tpu.engine.vparams import variant_params
    vp = variant_params(params)
    phases = [
        ("complex", lambda s, t: _complex_slot(params, vp, s, t)),
        ("resolve_memory", lambda s, t: rs.resolve_memory(params, vp, s)),
        ("resolve_all", lambda s, t: rs.resolve(params, s)),
        # The full quantum step (local rounds + resolve + boundary +
        # sampling): iterated cost ~= the engine's whole-round floor.
        ("quantum_step", lambda s, t: quantum.quantum_step(params, s, t)),
    ]
    if params.block_events > 0:
        phases.insert(0, ("block",
                          lambda s, t: _block_retire(params, vp, s, t)))
    if params.fast_forward > 0:
        # Round-12 legs, e.g.:
        #   python tools/profile_round.py 64 20 --set tpu/fast_forward=4
        # block_wide is the wide fast-forward window round the cadence
        # actually runs; fast_forward is the analytic run-ahead probe.
        from graphite_tpu.engine.core import (_fast_forward_guarded, _ff_width)
        W = _ff_width(params)
        if W > params.block_events:
            phases.insert(0, ("block_wide",
                              lambda s, t: _block_retire(
                                  params, vp, s, t, width=W)))
        phases.insert(0, ("fast_forward",
                          lambda s, t: _fast_forward_guarded(
                              params, vp, s, t)))
    for name, fn in phases:
        us = fused(fn, state, ta, iters)
        print(f"T={T} {name}: {us:.0f} us/round", flush=True)


if __name__ == "__main__":
    main()
