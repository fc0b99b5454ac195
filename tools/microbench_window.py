"""Before/after microbench of the block-window walk (round 10 kernels).

Times ONE _block_retire round (the [T, K] window walk — the round-cost
hot spot PROFILE.md's phase table attributes ~10 ms of a ~16 ms round to
at T = 1024) under each available execution path:

  * ``lax``        — the reference path (tpu/pallas_kernels = off)
  * ``interpret``  — the fused kernel under the Pallas interpreter.
                     On CPU this is an EMULATION: its wall-clock is a
                     correctness vehicle, not a speed claim (expect it
                     to be slower than lax on CPU — that is normal and
                     reported as such).
  * ``tpu``        — real Mosaic lowering; timed only when the default
                     backend is a TPU.  This is the number the kernels
                     exist for: the K-deep walk's dozens of ~150 us
                     dispatches collapse into one custom-call.

Also prints the structural evidence for the current config: jaxpr op
counts (eqns / gathers / scatters / pallas_call sites) of one window
round with kernels off vs on — the dispatch-chain the kernel absorbs.

Usage: python tools/microbench_window.py [tiles] [iters] [--set sec/key=val ...]

``--set`` forwards config overrides exactly like profile_round.py:

    python tools/microbench_window.py 1024 20 --set tpu/block_events=4
    python tools/microbench_window.py 64 50 --set tpu/miss_chain=12
"""

import dataclasses
import sys
import time

import jax

import graphite_tpu  # noqa: F401,E402  (x64 + the compile-cache policy)

from graphite_tpu.config import (apply_set_overrides, load_config,
                                 split_set_overrides)
from graphite_tpu.engine.core import _block_retire
from graphite_tpu.engine.kernels import dispatch as kdispatch
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.engine.vparams import variant_params
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams


def _timed(fn, state, ta, iters):
    @jax.jit
    def loop(s, t):
        return jax.lax.fori_loop(0, iters, lambda i, x: fn(x, t), s)

    jax.block_until_ready(loop(state, ta))
    t0 = time.perf_counter()
    jax.block_until_ready(loop(state, ta))
    return time.perf_counter() - t0


def fused(fn, state, ta, iters):
    """Marginal per-iteration cost (differences out dispatch constants —
    see profile_round.py)."""
    t1 = _timed(fn, state, ta, iters)
    t2 = _timed(fn, state, ta, 2 * iters)
    return max(t2 - t1, 0.0) / iters * 1e6


def main():
    args, overrides = split_set_overrides(sys.argv[1:])
    T = int(args[0]) if len(args) > 0 else 64
    iters = int(args[1]) if len(args) > 1 else 20
    cfg = load_config()
    cfg.set("general/total_cores", T)
    apply_set_overrides(cfg, overrides)
    params = SimParams.from_config(cfg)
    trace = synth.gen_radix(num_tiles=T, keys_per_tile=256, seed=1)
    sim = Simulator(params, trace)
    sim.run(max_steps=4)   # mid-run state: warm caches, live windows
    state, ta = sim.state, sim.trace
    if overrides:
        print(f"overrides: {' '.join(overrides)}", flush=True)

    modes = ["off", "interpret"]
    if jax.default_backend() == "tpu":
        modes.append("on")
    for mode in modes:
        p = dataclasses.replace(params, pallas_kernels=mode)
        if mode != "off" and kdispatch.window_mode(p) == "off":
            print(f"T={T} window[{mode}]: unsupported config "
                  f"(dispatch gates to lax)", flush=True)
            continue
        vp = variant_params(p)
        us = fused(lambda s, t, p=p, vp=vp: _block_retire(p, vp, s, t),
                   state, ta, iters)
        note = "  (interpreter emulation, not a speed claim)" \
            if mode == "interpret" and jax.default_backend() != "tpu" \
            else ""
        print(f"T={T} window[{'lax' if mode == 'off' else mode}]: "
              f"{us:.0f} us/round{note}", flush=True)

    # Structural evidence: the op chain the kernel absorbs.
    p_off = dataclasses.replace(params, pallas_kernels="off")
    p_on = dataclasses.replace(params, pallas_kernels="interpret")
    for lbl, p in (("off", p_off), ("on", p_on)):
        vp = variant_params(p)
        c = kdispatch.jaxpr_op_counts(
            lambda s, p=p, vp=vp: _block_retire(p, vp, s, ta), state)
        print(f"T={T} window jaxpr[kernels {lbl}]: {c['eqns']} eqns, "
              f"{c['gather']} gathers, {c['scatter']} scatters, "
              f"{c['pallas_call']} pallas_call", flush=True)


if __name__ == "__main__":
    main()
