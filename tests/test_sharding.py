"""Tile-axis sharding: the engine must produce bit-identical results when
state is sharded over the 8-device CPU mesh (the multi-chip execution path
the driver dry-runs; replaces the reference's multi-process regression
pattern of running every app at PROCS=1 and PROCS=2,
tests/apps/Makefile:4-25)."""

import jax
import jax.extend.core as jex_core
import numpy as np

from graphite_tpu.config import load_config
from graphite_tpu.engine.quantum import megastep
from graphite_tpu.engine.state import TraceArrays, make_state
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams
from graphite_tpu.parallel.mesh import make_mesh, shard_pytree


def test_sharded_matches_single_device():
    tiles = 16
    cfg = load_config()
    cfg.set("general/total_cores", tiles)
    cfg.set("tpu/max_events_per_quantum", 16)
    cfg.set("tpu/quanta_per_step", 2)
    params = SimParams.from_config(cfg)
    trace = synth.gen_migratory(tiles, lines=4, rounds=2)
    tarrays = TraceArrays.from_trace(trace)

    ref = megastep(params, make_state(params), tarrays)

    mesh = make_mesh(jax.devices()[:8])
    st = shard_pytree(make_state(params), mesh, tiles)
    ta = shard_pytree(tarrays, mesh, tiles)
    out = megastep(params, st, ta)

    for name in ("clock", "cursor", "pend_kind", "dram_ring_end"):
        assert np.array_equal(np.asarray(getattr(ref, name)),
                              np.asarray(getattr(out, name))), name
    for f in ref.counters._fields:
        assert np.array_equal(np.asarray(getattr(ref.counters, f)),
                              np.asarray(getattr(out.counters, f))), f


def test_dryrun_multichip_entry():
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)


# ---------------------------------------------------------------- round 11
# Explicit shard_map path (tpu/tile_shards): the engine itself is wrapped
# over the mesh, the window walk runs per-shard with zero cross-device
# traffic, and the contract is BIT-identity against tile_shards=1 — every
# state leaf, every counter, every phase counter.

import dataclasses

import pytest

from graphite_tpu.engine.sim import Simulator


def _params(tiles: int, shards: int, **sets):
    cfg = load_config()
    cfg.set("general/total_cores", tiles)
    cfg.set("tpu/tile_shards", str(shards))
    for k, v in sets.items():
        cfg.set(k.replace("__", "/"), v)
    return SimParams.from_config(cfg)


def _assert_states_equal(a, b):
    """Every leaf of two SimStates, by name (nested counters included)."""
    for name in a._fields:
        x, y = getattr(a, name), getattr(b, name)
        if hasattr(x, "_fields"):
            for f in x._fields:
                u, v = getattr(x, f), getattr(y, f)
                if u is None:
                    assert v is None, f"{name}.{f}"
                    continue
                assert np.array_equal(np.asarray(u), np.asarray(v)), \
                    f"{name}.{f}"
            continue
        if x is None:
            assert y is None, name
            continue
        assert np.array_equal(np.asarray(x), np.asarray(y)), name


def _run_pair(trace, tiles: int, **sets):
    """Run the SAME trace with tile_shards=8 and =1; return both sims."""
    sharded = Simulator(_params(tiles, 8, **sets), trace)
    sharded.run()
    solo = Simulator(_params(tiles, 1, **sets), trace)
    solo.run()
    return sharded, solo


def test_tile_shards_bit_identity_radix():
    trace = synth.gen_radix(num_tiles=64, keys_per_tile=8, radix=16,
                            seed=3)
    sharded, solo = _run_pair(trace, 64)
    _assert_states_equal(sharded.state, solo.state)


def test_tile_shards_bit_identity_fft():
    # T=8 with 8 shards: one tile per shard, the degenerate slice width.
    trace = synth.gen_fft(num_tiles=8, points_per_tile=32)
    sharded, solo = _run_pair(trace, 8)
    _assert_states_equal(sharded.state, solo.state)


@pytest.mark.slow   # two T=256 compiles
def test_tile_shards_bit_identity_large():
    trace = synth.gen_radix(num_tiles=256, keys_per_tile=8, radix=32,
                            seed=5)
    sharded, solo = _run_pair(trace, 256)
    _assert_states_equal(sharded.state, solo.state)


def test_tile_shards_checkpoint_reshard(tmp_path):
    """A checkpoint written by a SHARDED run restores into an UNSHARDED
    simulator (and finishes bit-identically to the never-sharded run):
    checkpoint shapes are tile_shards-independent, so resharding on
    restore is just loading with different params."""
    trace = synth.gen_radix(num_tiles=64, keys_per_tile=8, radix=16,
                            seed=6)
    p8, p1 = _params(64, 8), _params(64, 1)

    full = Simulator(p1, trace)
    s_full = full.run()

    half = Simulator(p8, trace)
    half.run(max_steps=2)
    ck = str(tmp_path / "ck_shard8.npz")
    half.save_checkpoint(ck)

    resumed = Simulator(p1, trace)       # reshard: 8 -> 1
    resumed.restore_checkpoint(ck)
    s_res = resumed.run()

    assert s_full.completion_time_ps == s_res.completion_time_ps
    _assert_states_equal(full.state, resumed.state)

    back = Simulator(p8, trace)          # and back: the 1-shard ckpt
    full.save_checkpoint(str(tmp_path / "ck_shard1.npz"))
    back.restore_checkpoint(str(tmp_path / "ck_shard1.npz"))
    _assert_states_equal(full.state, back.state)


def test_tile_shards_structural_no_cross_shard_traffic():
    """The CPU-checkable form of the scale-out claim (PROFILE.md round
    11): the per-shard window phase contains ZERO collective primitives
    and no full-T aval (every tile axis is T/S), while the whole sharded
    megastep carries only the small bounded set of explicit collectives
    (the WindowOut all_gathers + the quantum pmin barrier).  T=48 so the
    tile count collides with no structural dim (bp table entries = 64).
    """
    from graphite_tpu.engine import core, quantum
    from graphite_tpu.engine.kernels import dispatch
    from graphite_tpu.engine.kernels import window as kwindow
    from graphite_tpu.engine.vparams import variant_params

    T, S = 48, 8
    TL = T // S
    p8 = _params(T, S)
    p1 = dataclasses.replace(p8, tile_shards=1)
    trace = synth.gen_radix(num_tiles=T, keys_per_tile=8, radix=16,
                            seed=7)
    sim = Simulator(p1, trace)
    vp = variant_params(p1)

    # Capture the real WindowIn shapes by spying on the dispatch point.
    captured = {}
    orig = kwindow.run_window

    def spy(params, vp2, wi, s_ids, mode):
        captured["wi"] = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), wi)
        captured["s_ids"] = s_ids
        return orig(params, vp2, wi, s_ids, mode)

    kwindow.run_window = spy
    try:
        jax.eval_shape(lambda s: core._block_retire(p1, vp, s, sim.trace),
                       sim.state)
    finally:
        kwindow.run_window = orig
    wi_spec, s_ids = captured["wi"], captured["s_ids"]

    # (a) slice + walk at shard-local shapes: zero collectives.
    def walk_local(wi):
        wi_l = kwindow.shard_local_window_in(wi, 0, TL)
        return kwindow.window_walk(p8, vp, wi_l, s_ids)

    counts = dispatch.jaxpr_op_counts(walk_local, wi_spec)
    assert counts["collective"] == 0, counts

    # (b) no aval inside the walk carries a T-sized dim.
    wi_l_spec = jax.eval_shape(
        lambda wi: kwindow.shard_local_window_in(wi, 0, TL), wi_spec)
    closed = jax.make_jaxpr(
        lambda wi: kwindow.window_walk(p8, vp, wi, s_ids))(wi_l_spec)
    bad = []

    def scan_avals(jaxpr):
        for eqn in jaxpr.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                shape = getattr(getattr(v, "aval", None), "shape", ())
                if T in shape:
                    bad.append((eqn.primitive.name, shape))
            for p in eqn.params.values():
                subs = ([p.jaxpr] if isinstance(p, jex_core.ClosedJaxpr)
                        else [p] if isinstance(p, jex_core.Jaxpr) else [])
                for sub in subs:
                    scan_avals(sub)

    scan_avals(closed.jaxpr)
    assert not bad, bad[:10]

    # (c) whole-step collective budget: sharded carries a small bounded
    # count (output all_gathers + pmin), unsharded exactly zero.
    c8 = dispatch.jaxpr_op_counts(
        lambda s, t: quantum.megastep(p8, s, t), sim.state, sim.trace)
    c1 = dispatch.jaxpr_op_counts(
        lambda s, t: quantum.megastep(p1, s, t), sim.state, sim.trace)
    assert c1["collective"] == 0, c1
    assert 0 < c8["collective"] <= 64, c8


def test_tile_shards_sweep_identity():
    """vmap x shard_map composition: a sharded sweep's lanes equal the
    unsharded sweep's lanes, leaf for leaf."""
    from graphite_tpu.sweep.batch import SweepSimulator

    trace = synth.gen_radix(num_tiles=16, keys_per_tile=8, radix=16,
                            seed=8)

    def variants(shards):
        return [_params(16, shards, l2__data_access_time=str(lat))
                for lat in (8, 10, 12)]

    sw8 = SweepSimulator(variants(8), trace)
    sw8.run()
    sw1 = SweepSimulator(variants(1), trace)
    sw1.run()
    _assert_states_equal(sw8.bstate, sw1.bstate)


# ---------------------------------------------------------------- round 15
# Resident tile-sharded state (tpu/shard_state=resident): every T-leading
# leaf stays sharded along T for the whole run, resolve is home-routed
# over fixed-capacity all_to_alls, and the contract is shard-count
# INVARIANCE — resident S=8 equals resident S=1 bit-for-bit, including
# through both host spill paths (capacity overflow replay and the
# stuck-head gather through the replicated resolver).

from graphite_tpu.engine import resident
from graphite_tpu.params import ConfigError

# One params object per cell: resident._CACHE keys compiled programs on
# id(params), so sharing these across tests skips ~20s recompiles each.
_RP_CACHE = {}


def _rparams(tiles: int, shards: int, **sets):
    key = (tiles, shards, tuple(sorted(sets.items())))
    if key not in _RP_CACHE:
        base = dict(tpu__shard_state="resident",
                    tpu__block_events="4",
                    tpu__quanta_per_step="1",
                    tpu__miss_chain="8",
                    tpu__window_cache="false",
                    dram__queue_model__enabled="false")
        base.update(sets)
        _RP_CACHE[key] = _params(tiles, shards, **base)
    return _RP_CACHE[key]


def _resident_pair(trace, tiles: int, **sets):
    """Run the SAME trace resident at S=8 and S=1; return both sims."""
    r8 = Simulator(_rparams(tiles, 8, **sets), trace)
    r8.run()
    r1 = Simulator(_rparams(tiles, 1, **sets), trace)
    r1.run()
    return r8, r1


@pytest.mark.slow   # two resident compile sets (~1 min each on 1 core)
def test_resident_bit_identity_migratory():
    """Line-migration traffic (E->owner-change chains, the worst case
    for home routing) at S=8 equals S=1 on every leaf."""
    trace = synth.gen_migratory(16, lines=4, rounds=2)
    r8, r1 = _resident_pair(trace, 16)
    assert bool(np.asarray(r8.state.all_done()))
    _assert_states_equal(r8.state, r1.state)


@pytest.mark.slow   # two more compile sets (route_capacity is structural)
def test_resident_overflow_spill_identity():
    """route_capacity=1 forces the capacity-overflow host spill to fire
    (capped result discarded, sub-round replayed uncapped on one
    device) — and the final state is STILL shard-count invariant, so
    correctness never depends on the capacity heuristic."""
    trace = synth.gen_migratory(16, lines=4, rounds=2)
    before = resident._DEBUG_STATS["overflow_spills"]
    r8, r1 = _resident_pair(trace, 16, tpu__route_capacity="1")
    assert resident._DEBUG_STATS["overflow_spills"] > before, \
        "spill path never fired — the test is not exercising overflow"
    _assert_states_equal(r8.state, r1.state)


def test_resident_lowered_census():
    """The tentpole's collective budget, counted on the lowered step:
    ZERO full-T all_gathers (the replicated path has 13), at most two
    all_to_alls (request + response legs), exactly one int64 pmin (the
    quantum barrier, lowered as two int32 pmin words: the TPU refuses an
    s64 min all-reduce)."""
    params = _rparams(16, 8)
    trace = synth.gen_migratory(16, lines=4, rounds=2)
    tarrays = TraceArrays.from_trace(trace)
    counts = resident.lowered_quantum_collectives(
        params, make_state(params), tarrays)
    assert counts["all_gather"] == 0, counts
    assert counts["all_to_all"] <= 2, counts
    assert counts["pmin"] == 2, counts


def test_resident_quantum_guard():
    """The replicated quantum program refuses resident params loudly
    instead of silently running with replicated semantics."""
    params = _rparams(16, 8)
    trace = synth.gen_migratory(16, lines=4, rounds=2)
    tarrays = TraceArrays.from_trace(trace)
    with pytest.raises(ValueError, match="resident"):
        megastep(params, make_state(params), tarrays)


def test_resident_validated_subset_rejects():
    """Configs outside the resident subset fail at validation time
    (ConfigError naming the mode), not as a silent per-round spill:
    window cache on, and traces with sync ops (radix uses barriers)."""
    bad = _params(16, 8, tpu__shard_state="resident",
                  tpu__miss_chain="8",
                  dram__queue_model__enabled="false")  # window_cache on
    trace = synth.gen_migratory(16, lines=4, rounds=2)
    with pytest.raises(ConfigError, match="resident"):
        resident.megarun(bad, make_state(bad),
                         TraceArrays.from_trace(trace), 1)
    barriers = synth.gen_radix(num_tiles=16, keys_per_tile=8, radix=16)
    good = _rparams(16, 8)
    with pytest.raises(ConfigError, match="resident"):
        resident.megarun(good, make_state(good),
                         TraceArrays.from_trace(barriers), 1)


@pytest.mark.slow   # directory pressure run: ~97 stuck spills per side
def test_resident_stuck_spill_identity():
    """A 1-set-per-home-tile directory forces live-sharer victims the
    routed pass cannot price; the stuck-head gather through the
    replicated resolver fires and the result stays S-invariant."""
    trace = synth.gen_migratory(16, lines=64, rounds=2)
    before = resident._DEBUG_STATS["stuck_spills"]
    r8, r1 = _resident_pair(trace, 16,
                            dram_directory__total_entries="2",
                            dram_directory__associativity="2")
    assert resident._DEBUG_STATS["stuck_spills"] > before, \
        "stuck-spill path never fired — not enough directory pressure"
    _assert_states_equal(r8.state, r1.state)


@pytest.mark.slow   # batched resident programs: extra compile set
def test_resident_sweep_identity():
    """vmap x shard_map composition for resident lanes: a sharded
    resident sweep's lanes equal the unsharded resident sweep's lanes,
    leaf for leaf (the sweep path routes with a structurally
    overflow-free capacity, so no spill nondeterminism)."""
    from graphite_tpu.sweep.batch import SweepSimulator

    trace = synth.gen_migratory(16, lines=4, rounds=2)

    def variants(shards):
        return [_rparams(16, shards, l2__data_access_time=str(lat))
                for lat in (8, 10, 12)]

    sw8 = SweepSimulator(variants(8), trace)
    sw8.run()
    sw1 = SweepSimulator(variants(1), trace)
    sw1.run()
    _assert_states_equal(sw8.bstate, sw1.bstate)
