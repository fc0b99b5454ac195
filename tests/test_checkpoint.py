"""Checkpoint/resume: stop mid-simulation, restore, and finish with
bit-identical results vs an uninterrupted run.  Includes the corrupt-
file contract (CheckpointCorruptError, ISSUE 15) and the v25 batched
[V]-leading sweep checkpoints the service preempts through."""

import numpy as np
import pytest

from graphite_tpu.config import load_config
from graphite_tpu.engine.checkpoint import CheckpointCorruptError
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.events import synth
from graphite_tpu.params import SimParams


def test_resume_bit_identical(tmp_path):
    cfg = load_config()
    cfg.set("general/total_cores", 8)
    params = SimParams.from_config(cfg)
    trace = synth.gen_private_mem(8, accesses=30, working_set_kb=4)

    full = Simulator(params, trace)
    s_full = full.run()

    half = Simulator(params, trace)
    half.run(max_steps=2)
    ck = str(tmp_path / "ck.npz")
    half.save_checkpoint(ck)

    resumed = Simulator(params, trace)
    resumed.restore_checkpoint(ck)
    assert resumed.steps == 2
    s_res = resumed.run()

    assert s_full.completion_time_ps == s_res.completion_time_ps
    for f, a in s_full.counters.items():
        assert np.array_equal(a, s_res.counters[f]), f


def test_resume_chained_run_identical(tmp_path):
    """Checkpoint/resume THROUGH the miss-chain machinery (schema v22):
    a chained radix run split mid-flight — banked mq_* elements, chain
    base/rel clocks and all — must retire the same engine rounds and
    final clocks as the unbroken run.  (The chain arrays are live state
    between the bank and the serve; a resume that dropped or reordered
    them would re-price or lose banked requests.)"""
    import jax

    cfg = load_config()
    cfg.set("general/total_cores", 8)
    cfg.set("tpu/miss_chain", 12)
    params = SimParams.from_config(cfg)
    trace = synth.gen_radix(num_tiles=8, keys_per_tile=48, radix=16, seed=7)

    full = Simulator(params, trace)
    s_full = full.run(max_steps=96)
    assert s_full.done.all()

    half = Simulator(params, trace)
    half.run(max_steps=2)
    ck = str(tmp_path / "ck_chain.npz")
    half.save_checkpoint(ck)

    resumed = Simulator(params, trace)
    resumed.restore_checkpoint(ck)
    s_res = resumed.run(max_steps=96)
    assert s_res.done.all()

    assert s_full.completion_time_ps == s_res.completion_time_ps
    np.testing.assert_array_equal(s_full.clock, s_res.clock)
    for f in ("ctr_quantum", "ctr_window", "ctr_complex", "ctr_conflict",
              "ctr_resolve", "round_ctr"):
        a = int(jax.device_get(getattr(full.state, f)))
        b = int(jax.device_get(getattr(resumed.state, f)))
        assert a == b, f"{f}: unbroken {a} != resumed {b}"
    for f, a in s_full.counters.items():
        assert np.array_equal(a, s_res.counters[f]), f


def test_checkpoint_shape_guard(tmp_path):
    cfg = load_config()
    cfg.set("general/total_cores", 8)
    params = SimParams.from_config(cfg)
    trace = synth.gen_private_mem(8, accesses=5, working_set_kb=4)
    sim = Simulator(params, trace)
    ck = str(tmp_path / "ck.npz")
    sim.save_checkpoint(ck)

    cfg2 = load_config()
    cfg2.set("general/total_cores", 16)
    params2 = SimParams.from_config(cfg2)
    trace2 = synth.gen_private_mem(16, accesses=5, working_set_kb=4)
    sim2 = Simulator(params2, trace2)
    import pytest
    with pytest.raises(ValueError):
        sim2.restore_checkpoint(ck)


def test_resume_mid_window_fanout_identical(tmp_path):
    """Checkpoint/resume THROUGH the round-9 carried-window machinery
    (schema v23): with boundary-spanning windows + the fan-out replay,
    the win_* cache arrays ([.., 4K]), partial window occupancy past
    the quantum cut, banked chains, and the spanned boundary itself are
    all live state between steps.  A sharing-heavy run split mid-flight
    must retire the same engine rounds, phase counts, and final clocks
    as the unbroken run — a resume that flushed the carried window (or
    re-gathered it at the wrong offset) shows up as a different
    window-round count."""
    import jax

    cfg = load_config()
    cfg.set("general/total_cores", 8)
    cfg.set("tpu/miss_chain", 12)
    assert SimParams.from_config(cfg).fanout_replay  # default-on switch
    params = SimParams.from_config(cfg)
    trace = synth.gen_migratory(8, lines=16, rounds=6)

    full = Simulator(params, trace)
    s_full = full.run(max_steps=96)
    assert s_full.done.all()

    half = Simulator(params, trace)
    half.run(max_steps=2)
    # The split must land mid-window/mid-chain for the test to bite:
    # some tile still has banked elements or resident window occupancy.
    mq = int(jax.device_get(half.state.mq_count).sum())
    win_live = int(jax.device_get(
        (half.state.win_base >= 0).sum())) if half.state.win_base.size \
        else 0
    assert mq > 0 or win_live > 0, "split landed outside the machinery"
    ck = str(tmp_path / "ck_win.npz")
    half.save_checkpoint(ck)

    resumed = Simulator(params, trace)
    resumed.restore_checkpoint(ck)
    s_res = resumed.run(max_steps=96)
    assert s_res.done.all()

    assert s_full.completion_time_ps == s_res.completion_time_ps
    np.testing.assert_array_equal(s_full.clock, s_res.clock)
    for f in ("ctr_quantum", "ctr_window", "ctr_complex", "ctr_conflict",
              "ctr_resolve", "round_ctr"):
        a = int(jax.device_get(getattr(full.state, f)))
        b = int(jax.device_get(getattr(resumed.state, f)))
        assert a == b, f"{f}: unbroken {a} != resumed {b}"
    for f, a in s_full.counters.items():
        assert np.array_equal(a, s_res.counters[f]), f


# ------------------------------------------- corrupt files (ISSUE 15)

def _solo_ckpt(tmp_path, name="ck.npz"):
    cfg = load_config()
    cfg.set("general/total_cores", 8)
    params = SimParams.from_config(cfg)
    trace = synth.gen_private_mem(8, accesses=5, working_set_kb=4)
    sim = Simulator(params, trace)
    ck = str(tmp_path / name)
    sim.save_checkpoint(ck)
    return params, trace, ck


def test_truncated_checkpoint_raises_corrupt_error(tmp_path):
    """A file torn under the writer (modeled as post-rename truncation)
    must surface CheckpointCorruptError NAMING the path — not a generic
    zipfile traceback — so the service's discard-and-rerun fallback can
    key on it."""
    params, trace, ck = _solo_ckpt(tmp_path)
    size = int(__import__("os").path.getsize(ck))
    with open(ck, "r+b") as f:
        f.truncate(max(size // 3, 1))
    sim = Simulator(params, trace)
    with pytest.raises(CheckpointCorruptError, match="ck.npz"):
        sim.restore_checkpoint(ck)


def test_garbage_checkpoint_raises_corrupt_error(tmp_path):
    params, trace, _ = _solo_ckpt(tmp_path)
    bad = str(tmp_path / "bad.npz")
    with open(bad, "wb") as f:
        f.write(b"not a checkpoint at all")
    with pytest.raises(CheckpointCorruptError, match="bad.npz"):
        Simulator(params, trace).restore_checkpoint(bad)


def test_missing_checkpoint_stays_file_not_found(tmp_path):
    """An absent file is an operator error, not corruption: the service
    treats the two differently (corrupt → rerun; missing → the journal
    replay already dropped the resume record)."""
    params, trace, _ = _solo_ckpt(tmp_path)
    with pytest.raises(FileNotFoundError):
        Simulator(params, trace).restore_checkpoint(
            str(tmp_path / "nope.npz"))


def test_checkpoint_save_is_atomic_no_tmp_left(tmp_path):
    """The save path writes tmp + fsync + rename: after a successful
    save the directory holds exactly the checkpoint, no orphan temp."""
    import os
    _, _, ck = _solo_ckpt(tmp_path, name="atomic.npz")
    names = os.listdir(tmp_path)
    assert "atomic.npz" in names
    assert not [n for n in names if ".tmp" in n]


# ------------------------------- v25: batched [V]-leading sweep states

def test_sweep_checkpoint_mid_bucket_resume_identical(tmp_path):
    """ACCEPTANCE (schema v25): a V=2 bucket checkpointed mid-flight
    and restored into a FRESH SweepSimulator finishes with per-lane
    clocks, quanta, and counters bit-identical to the unbroken batched
    run — which is itself lane-identical to the solo runs.  The 100ns
    barrier quantum stretches the tiny trace over several windows so
    max_steps=2 genuinely splits mid-bucket."""
    from graphite_tpu.sweep import SweepSimulator, build_variants

    cfg = load_config()
    cfg.set("general/total_cores", 4)
    cfg.set("clock_skew_management/lax_barrier/quantum", 100)
    trace = synth.gen_radix(num_tiles=4, keys_per_tile=16, radix=8, seed=1)
    variants = [p for _, _, p in
                build_variants(cfg, ["dram/latency=80,120"])]

    full = SweepSimulator(variants, trace)
    s_full = full.run()

    half = SweepSimulator(variants, trace)
    half.run(max_steps=2)
    assert not all(s.done.all() for s in half.summaries()), \
        "split landed after completion — the resume test has no bite"
    ck = str(tmp_path / "bucket.ckpt.npz")
    half.save_checkpoint(ck)

    resumed = SweepSimulator(variants, trace)
    resumed.restore_checkpoint(ck)
    assert resumed.steps == half.steps
    s_res = resumed.run()

    for lane_full, lane_res, p in zip(s_full, s_res, variants):
        np.testing.assert_array_equal(np.asarray(lane_full.clock),
                                      np.asarray(lane_res.clock))
        assert lane_full.quanta == lane_res.quanta
        for k in lane_full.counters:
            np.testing.assert_array_equal(lane_full.counters[k],
                                          lane_res.counters[k], k)
        solo = Simulator(p, trace).run()
        np.testing.assert_array_equal(np.asarray(lane_res.clock),
                                      np.asarray(solo.clock))


def test_sweep_checkpoint_guards(tmp_path):
    """Wrong-V loads and solo/sweep cross-loads fail loudly instead of
    slicing garbage into lanes."""
    from graphite_tpu.engine.checkpoint import (load_checkpoint,
                                                load_sweep_checkpoint)
    from graphite_tpu.sweep import SweepSimulator, build_variants

    cfg = load_config()
    cfg.set("general/total_cores", 4)
    trace = synth.gen_radix(num_tiles=4, keys_per_tile=16, radix=8, seed=1)
    variants = [p for _, _, p in
                build_variants(cfg, ["dram/latency=80,120"])]
    sim = SweepSimulator(variants, trace)
    ck = str(tmp_path / "v2.ckpt.npz")
    sim.save_checkpoint(ck)

    with pytest.raises(ValueError, match="variants"):
        load_sweep_checkpoint(ck, variants[:1],
                              num_streams=trace.num_tiles)
    with pytest.raises(ValueError, match="sweep"):
        load_checkpoint(ck, variants[0])


# ---------------------------------------------------------------- round 15
# Resident tile-sharded runs (tpu/shard_state=resident): checkpoints stay
# whole-array .npz (the flatten seam gathers sharded leaves — the only
# full-T materialization point of a resident run) and restore re-places
# them onto the mesh, so stop/resume is bit-identical at any shard count.

_RESIDENT_PARAMS = None


def _resident_params():
    """One shared params object: resident program caches key on
    id(params), so every Simulator in this section reuses compiles."""
    global _RESIDENT_PARAMS
    if _RESIDENT_PARAMS is None:
        cfg = load_config()
        cfg.set("general/total_cores", 16)
        cfg.set("tpu/tile_shards", "8")
        cfg.set("tpu/shard_state", "resident")
        cfg.set("tpu/block_events", "4")
        cfg.set("tpu/quanta_per_step", "1")
        cfg.set("tpu/miss_chain", "8")
        cfg.set("tpu/window_cache", "false")
        cfg.set("dram/queue_model/enabled", "false")
        _RESIDENT_PARAMS = SimParams.from_config(cfg)
    return _RESIDENT_PARAMS


@pytest.mark.slow   # three resident megaruns share one compile set
def test_resident_resume_bit_identical(tmp_path):
    """Stop a resident run mid-flight, checkpoint, restore (which
    re-places the whole-array leaves tile-sharded), finish — every
    state leaf equals the uninterrupted run's."""
    from graphite_tpu.engine.checkpoint import _flatten_with_paths

    params = _resident_params()
    trace = synth.gen_migratory(16, lines=4, rounds=2)

    full = Simulator(params, trace)
    full.run()

    half = Simulator(params, trace)
    half.run(max_steps=2)
    ck = str(tmp_path / "resident.npz")
    half.save_checkpoint(ck)

    resumed = Simulator(params, trace)
    resumed.restore_checkpoint(ck)
    assert resumed.steps == 2
    resumed.run()

    a, _ = _flatten_with_paths(full.state)
    b, _ = _flatten_with_paths(resumed.state)
    for key in a:
        assert np.array_equal(a[key], b[key]), key


def test_resident_old_schema_rejected(tmp_path):
    """Pre-resident checkpoints (schema < 26) are rejected with the
    schema ValueError, not silently reinterpreted: the routed-resolve
    phase counters changed semantics under the v26 bump."""
    params = _resident_params()
    trace = synth.gen_migratory(16, lines=4, rounds=2)
    # Save the INITIAL state — schema enforcement needs no simulation,
    # and skipping the run keeps this in the quick tier (no compiles).
    sim = Simulator(params, trace)
    ck = str(tmp_path / "new.npz")
    sim.save_checkpoint(ck)

    with np.load(ck) as z:
        doctored = {k: z[k] for k in z.files}
    doctored["__meta_schema"] = np.int64(25)
    old = str(tmp_path / "old.npz")
    with open(old, "wb") as f:
        np.savez(f, **doctored)

    with pytest.raises(ValueError, match="schema 25"):
        Simulator(params, trace).restore_checkpoint(old)


def _dir_word_2d(flat, assoc):
    """The flat uint32 directory store -> the [A, F] int64 dir_word that
    checkpoints of schemas 26-29 carry (entry (f, way) at [way, f])."""
    halves = flat.reshape(-1, assoc, 2).astype(np.uint64)
    words = halves[..., 0] | (halves[..., 1] << np.uint64(32))   # [F, A]
    return np.ascontiguousarray(words.T).view(np.int64)


def test_v27_sharer_planes_restore_identical(tmp_path):
    """Schema 28 stores the sharer bitmap as flat uint32 words; a v27
    checkpoint's [W*A, F] uint64 planes (plane (w, way) at row w*A + way)
    convert on load, bit for bit, and the resumed run equals the
    unbroken one."""
    cfg = load_config()
    cfg.set("general/total_cores", 8)
    params = SimParams.from_config(cfg)
    trace = synth.gen_radix(num_tiles=8, keys_per_tile=16, radix=16, seed=3)

    full = Simulator(params, trace)
    s_full = full.run()

    half = Simulator(params, trace)
    half.run(max_steps=2)
    ck = str(tmp_path / "v28.npz")
    half.save_checkpoint(ck)
    with np.load(ck) as z:
        doctored = {k: z[k] for k in z.files}
    flat = doctored["dir_sharers"]
    assert flat.dtype == np.uint32 and flat.any()
    A = params.directory.associativity
    doctored["dir_word"] = _dir_word_2d(doctored["dir_word"], A)
    F = doctored["dir_word"].shape[1]
    words = flat.reshape(F, A, -1, 2).astype(np.uint64)
    rows = words[..., 0] | (words[..., 1] << np.uint64(32))     # [F, A, W]
    doctored["dir_sharers"] = np.ascontiguousarray(
        rows.transpose(2, 1, 0).reshape(-1, F))
    doctored["__meta_schema"] = np.int64(27)
    old = str(tmp_path / "v27.npz")
    with open(old, "wb") as f:
        np.savez(f, **doctored)

    resumed = Simulator(params, trace)
    resumed.restore_checkpoint(old)
    np.testing.assert_array_equal(np.asarray(resumed.state.dir_sharers),
                                  flat)
    s_res = resumed.run()
    assert s_full.completion_time_ps == s_res.completion_time_ps
    for f, a in s_full.counters.items():
        assert np.array_equal(a, s_res.counters[f]), f


def test_v28_restores_with_empty_link_rings(tmp_path):
    """Schema 29 carries the contended mesh's per-link queue rings and
    restores them as saved; a v28 checkpoint (carried link horizons, no
    rings) restores with the rings empty."""
    cfg = load_config()
    cfg.set("general/total_cores", 8)
    cfg.set("network/memory", "emesh_hop_by_hop")
    params = SimParams.from_config(cfg)
    trace = synth.gen_migratory(8, lines=4, rounds=2)
    ck = str(tmp_path / "v29.npz")
    Simulator(params, trace).save_checkpoint(ck)
    with np.load(ck) as z:
        saved = {k: z[k] for k in z.files}
    keys = [k for k in saved if k.startswith(("link_mem/", "link_user/"))]
    assert len(keys) == 6
    assert params.net_memory.queue_slots == 100
    assert saved["link_mem/start"].shape == (100, 4 * 8)
    assert saved["link_user/start"].size == 0
    filled = {k: (np.arange(saved[k].size) + 1).astype(saved[k].dtype)
              .reshape(saved[k].shape) for k in keys}

    def restored(arrays, name):
        path = str(tmp_path / name)
        with open(path, "wb") as f:
            np.savez(f, **arrays)
        sim = Simulator(params, trace)
        sim.restore_checkpoint(path)
        return {k: np.asarray(getattr(getattr(sim.state, k.split("/")[0]),
                                      k.split("/")[1])) for k in keys}

    got = restored(dict(saved, **filled), "rings.npz")
    for k in keys:
        np.testing.assert_array_equal(got[k], filled[k])
    v28 = {k: v for k, v in saved.items() if k not in keys}
    v28["link_free_mem"] = np.full((4, 8), 123, np.int64)
    v28["link_free_user"] = np.zeros((4, 0), np.int64)
    v28["__meta_schema"] = np.int64(28)
    got = restored(v28, "v28.npz")
    for k in keys:
        assert got[k].shape == saved[k].shape and not got[k].any(), k


def test_v29_dir_word_restores_identical(tmp_path):
    """Schema 30 stores the packed directory words as flat uint32 halves;
    a v29 checkpoint's [A, F] int64 dir_word converts on load, bit for
    bit, and the resumed run equals the unbroken one."""
    cfg = load_config()
    cfg.set("general/total_cores", 8)
    params = SimParams.from_config(cfg)
    trace = synth.gen_radix(num_tiles=8, keys_per_tile=16, radix=16, seed=5)

    full = Simulator(params, trace)
    s_full = full.run()

    half = Simulator(params, trace)
    half.run(max_steps=2)
    ck = str(tmp_path / "v30.npz")
    half.save_checkpoint(ck)
    with np.load(ck) as z:
        doctored = {k: z[k] for k in z.files}
    flat = doctored["dir_word"]
    A = params.directory.associativity
    assert flat.dtype == np.uint32 and flat.ndim == 1
    words = _dir_word_2d(flat, A)
    assert words.shape == (A, 8 * params.directory.num_sets)
    assert (words & 7).any()                         # live entries to carry
    doctored["dir_word"] = words
    doctored["__meta_schema"] = np.int64(29)
    old = str(tmp_path / "v29.npz")
    with open(old, "wb") as f:
        np.savez(f, **doctored)

    resumed = Simulator(params, trace)
    resumed.restore_checkpoint(old)
    np.testing.assert_array_equal(np.asarray(resumed.state.dir_word), flat)
    s_res = resumed.run()
    assert s_full.completion_time_ps == s_res.completion_time_ps
    for f, a in s_full.counters.items():
        assert np.array_equal(a, s_res.counters[f]), f
