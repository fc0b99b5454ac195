"""Pallas round-cost kernels == the lax reference, bit for bit.

The kernels (engine/kernels/: the fused block-window walk + the chain
replay's classify kernel, behind ``tpu/pallas_kernels``) execute the
SAME pure walk/classify functions the lax path calls inline, on
block-sliced operands inside ``pl.pallas_call``.  All arithmetic is
integer and per-tile independent, so kernels-on must be BIT-IDENTICAL
to kernels-off — every round counter, per-tile clock, and stat counter.
These are hard (non-xfail) gates, run in interpret mode so they hold on
any backend; on a TPU the same contract covers the Mosaic path (the
PROFILE.md round-10 repro commands re-run this module there).
"""

import numpy as np
import pytest

from graphite_tpu.config import load_config
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams

pytestmark = pytest.mark.quick

ROUND_CTRS = ("ctr_quantum", "ctr_window", "ctr_complex", "ctr_conflict",
              "ctr_resolve", "round_ctr")


def _run(trace, num_tiles, mode, **over):
    import jax
    cfg = load_config()
    cfg.set("general/total_cores", num_tiles)
    cfg.set("tpu/pallas_kernels", mode)
    for k, v in over.items():
        cfg.set(k, v)
    params = SimParams.from_config(cfg)
    sim = Simulator(params, trace)
    summary = sim.run(max_steps=256)
    ctrs = {f: int(jax.device_get(getattr(sim.state, f)))
            for f in ROUND_CTRS}
    return summary, ctrs


def _assert_identical(a, ca, b, cb, label):
    assert a.done.all() and b.done.all(), label
    assert ca == cb, f"{label}: round ctrs {ca} != {cb}"
    np.testing.assert_array_equal(a.clock, b.clock, label)
    for k in a.counters:
        np.testing.assert_array_equal(a.counters[k], b.counters[k],
                                      f"{label}.{k}")


@pytest.mark.parametrize("num_tiles", [
    8,
    pytest.param(64, marks=pytest.mark.slow),   # T=64 pays 2 big compiles
])
def test_interpret_bit_identity_radix(num_tiles):
    """Kernels-on (interpret) == kernels-off on the radix quick shape,
    through the whole engine: window walk + chain replay + fan-out leg
    (miss_chain=12 exercises the chain classify kernel every pass)."""
    trace = synth.gen_radix(num_tiles=num_tiles,
                            keys_per_tile=16 if num_tiles >= 64 else 48,
                            radix=16, seed=5)
    over = {"tpu/miss_chain": 12}
    a, ca = _run(trace, num_tiles, "off", **over)
    b, cb = _run(trace, num_tiles, "interpret", **over)
    _assert_identical(a, ca, b, cb, f"radix{num_tiles}")


def test_interpret_bit_identity_fft8():
    trace = synth.gen_fft(num_tiles=8, points_per_tile=64)
    for over in ({}, {"tpu/miss_chain": 12}):
        a, ca = _run(trace, 8, "off", **over)
        b, cb = _run(trace, 8, "interpret", **over)
        _assert_identical(a, ca, b, cb, f"fft8 {over}")


@pytest.mark.parametrize("chain", [0, 12])
def test_interpret_bit_identity_shared_l2(chain):
    """The shared-L2 protocols compile the walk without a private L2
    operand (None-field plumbing), and at miss_chain > 0 the chain
    classify kernel takes its shared-L2 branches (slice->controller
    DRAM legs, owner-side L1D lookup, slice hit counters) — cover both
    shapes."""
    trace = synth.gen_radix(num_tiles=8, keys_per_tile=32, radix=16,
                            seed=11)
    over = {"caching_protocol/type": "pr_l1_sh_l2_mesi",
            "tpu/miss_chain": chain}
    a, ca = _run(trace, 8, "off", **over)
    b, cb = _run(trace, 8, "interpret", **over)
    _assert_identical(a, ca, b, cb, f"sh_l2_mesi chain={chain}")


def test_dispatch_defaults_lax():
    """The default is the lax path on every backend (Mosaic refuses the
    kernels' int64 operands, ROADMAP A.1), 'auto' is gone, 'on' means
    real lowering, and the gates route iocoom windows to lax at any
    setting."""
    from graphite_tpu.config import ConfigError
    from graphite_tpu.engine.kernels import dispatch

    cfg = load_config()
    cfg.set("general/total_cores", 2)
    params = SimParams.from_config(cfg)
    assert params.pallas_kernels == "off"
    assert dispatch.kernels_mode(params) == "off"
    assert dispatch.window_mode(params) == "off"
    cfg.set("tpu/pallas_kernels", "auto")
    with pytest.raises(ConfigError):
        SimParams.from_config(cfg)
    cfg.set("tpu/pallas_kernels", "on")
    assert dispatch.kernels_mode(SimParams.from_config(cfg)) == "tpu"
    cfg.set("tpu/pallas_kernels", "interpret")
    cfg.set("tile/model_list", "<default,iocoom,T1,T1,T1>")
    p2 = SimParams.from_config(cfg)
    assert dispatch.window_mode(p2) == "off"      # iocoom gate
    assert dispatch.chain_mode(p2) == "interpret"


def test_tile_block_divides():
    from graphite_tpu.engine.kernels import dispatch
    for t in (1, 2, 8, 64, 128, 256, 1024):
        tb = dispatch.tile_block(t)
        assert t % tb == 0 and tb <= 128
    assert dispatch.tile_block(96) in (32, 96 // 3, 96) or 96 % \
        dispatch.tile_block(96) == 0


def test_sweep_zoo_accepts_pallas_kernels_flag():
    """The flag is a string, so the sweep space classifies it structural
    by nature — the zoo walk must stay green and a sweep attempt over it
    must be refused as structural."""
    import dataclasses

    from graphite_tpu.sweep import space

    cfg = load_config()
    cfg.set("general/total_cores", 2)
    params = SimParams.from_config(cfg)
    for path, value in space.iter_leaves(params):
        space.classify(path, value)       # raises on an unclassified leaf
    assert space.classify("pallas_kernels", params.pallas_kernels) \
        == "structural"
    a = params
    b = dataclasses.replace(params, pallas_kernels="interpret")
    assert space.structural_signature(a) != space.structural_signature(b)


def test_multi_block_grid_bit_identity():
    """T=256 > the 128-tile block cap, so the window kernel runs a
    REAL multi-block grid (grid=(2,)) — the shape every bench-scale
    config uses.  One _block_retire phase on a fresh state must match
    the lax path leaf-for-leaf.  (Regression test: the kernel jaxpr was
    once traced at full-T shapes and replayed on 128-wide blocks,
    crashing every T > 128 run at trace time.)"""
    import dataclasses

    import jax

    from graphite_tpu.engine import core, state as statemod
    from graphite_tpu.engine.kernels import dispatch
    from graphite_tpu.engine.vparams import variant_params

    T = 256
    cfg = load_config()
    cfg.set("general/total_cores", T)
    cfg.set("tpu/miss_chain", 8)
    p_off = SimParams.from_config(cfg)
    assert dispatch.tile_block(T) < T      # genuinely multi-block
    p_on = dataclasses.replace(p_off, pallas_kernels="interpret")
    trace = synth.gen_radix(num_tiles=T, keys_per_tile=4, radix=8, seed=2)
    ta = statemod.TraceArrays.from_trace(trace)
    st = statemod.make_state(p_off, has_capi=False)
    a = core._block_retire(p_off, variant_params(p_off), st, ta)
    b = core._block_retire(p_on, variant_params(p_on), st, ta)
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_resume_with_kernels_on(tmp_path):
    """Kernels add NO state (schema unchanged): a mid-chain checkpoint
    written by a kernels-on run restores and finishes bit-identically to
    the unbroken kernels-on run — and matches the kernels-off run."""
    import jax

    cfg = load_config()
    cfg.set("general/total_cores", 8)
    cfg.set("tpu/miss_chain", 12)
    cfg.set("tpu/pallas_kernels", "interpret")
    params = SimParams.from_config(cfg)
    trace = synth.gen_radix(num_tiles=8, keys_per_tile=48, radix=16, seed=7)

    full = Simulator(params, trace)
    s_full = full.run(max_steps=96)
    assert s_full.done.all()

    half = Simulator(params, trace)
    half.run(max_steps=2)
    ck = str(tmp_path / "ck_kernels.npz")
    half.save_checkpoint(ck)

    resumed = Simulator(params, trace)
    resumed.restore_checkpoint(ck)
    s_res = resumed.run(max_steps=96)
    assert s_res.done.all()

    assert s_full.completion_time_ps == s_res.completion_time_ps
    np.testing.assert_array_equal(s_full.clock, s_res.clock)
    for f in ROUND_CTRS:
        a = int(jax.device_get(getattr(full.state, f)))
        b = int(jax.device_get(getattr(resumed.state, f)))
        assert a == b, f"{f}: unbroken {a} != resumed {b}"
    for f, a in s_full.counters.items():
        assert np.array_equal(a, s_res.counters[f]), f


def test_structural_collapse_window_phase():
    """The structural-evidence contract bench.py records: with kernels
    on, the window walk appears as exactly ONE pallas_call equation in
    the lowered round (one TPU custom-call by construction), and the
    gather/scatter population of the phase drops accordingly."""
    import dataclasses

    import jax.numpy as jnp

    from graphite_tpu.engine import core
    from graphite_tpu.engine.kernels import dispatch
    from graphite_tpu.engine.sim import Simulator as Sim
    from graphite_tpu.engine.vparams import variant_params

    cfg = load_config()
    cfg.set("general/total_cores", 8)
    params_off = SimParams.from_config(cfg)
    params_on = dataclasses.replace(params_off,
                                    pallas_kernels="interpret")
    trace = synth.gen_radix(num_tiles=8, keys_per_tile=16, radix=8)
    sim = Sim(params_off, trace)

    def block_round(p):
        vp = variant_params(p)
        return lambda s: core._block_retire(p, vp, s, sim.trace)

    off = dispatch.jaxpr_op_counts(block_round(params_off), sim.state)
    on = dispatch.jaxpr_op_counts(block_round(params_on), sim.state)
    assert off["pallas_call"] == 0
    assert on["pallas_call"] == 1, on
    # The walk's op population moves INSIDE the one call: the phase's
    # residual eqn count (gather + everything else) collapses.
    assert on["eqns"] < off["eqns"] // 2, (on, off)
    assert on["gather"] < off["gather"], (on, off)
