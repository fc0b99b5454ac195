"""Simulation checkpoint/resume.

The reference has none — a crashed process kills the whole distributed run
(SURVEY.md section 5.3/5.4; the closest mechanisms are the model
enable/disable region controls, reference simulator.cc:287-301).  Because
graphite_tpu's entire mutable state is one pytree of arrays
(engine/state.py), checkpointing is a flatten + save: any simulation can be
stopped, stored, moved across hosts/device counts, and resumed
bit-identically (resume is deterministic — the engine has no RNG and no
host-order dependence).

Format: a single .npz whose keys are the flattened pytree paths, plus
engine metadata (steps, schema version; batched sweep checkpoints add the
variant count).  Writes are ATOMIC — tmp file in the target directory,
fsync, rename — so a crash mid-save leaves the previous checkpoint intact
instead of a torn one; the sweep service's preempt/resume leans on this.
Truncated/corrupt files surface as ``CheckpointCorruptError`` naming the
path, never a raw zipfile traceback.  Orbax-style async/sharded
checkpointing can layer on the same pytree for multi-host runs.
"""

from __future__ import annotations

import os
import tempfile
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from graphite_tpu.engine.state import SimState, make_state, \
    sharers_from_planes
from graphite_tpu.params import SimParams

_SCHEMA_VERSION = 29  # v29: per-link queue rings (link_mem/*,
#   link_user/*: noc_flight.LinkRings) replace the carried link horizons
#   (link_free_mem / link_free_user); a v26-v28 file restores with empty
#   link rings (its horizons are dropped);
#   v28: the full-map sharer bitmap is stored as a
#   flat, entry-major uint32 store (SimState.dir_sharers); a v26/v27
#   [W*A, F] uint64 plane array converts on load (sharers_from_planes);
#   v27: streaming segmented ingest (round 16) —
#   streamed runs checkpoint at segment seams and record the ingest
#   frame (__ingest_base / __ingest_segment_events / __ingest_n_total)
#   beside the state leaves; state semantics are unchanged, so v26
#   files (whole-trace) still restore (see _check_schema);
#   v26: resident tile-sharded runs (tpu/shard_state)
#   — checkpoints stay whole-array (the flatten seam gathers sharded
#   leaves via np.asarray, the ONLY full-T materialization point of a
#   resident run), and restore re-places tile-sharded in
#   sim.restore_checkpoint; the bump rejects pre-resident files whose
#   phase-counter semantics predate the routed-resolve counters;
#   v25: fault-tolerant sweep service — batched
#   [V]-leading SweepSimulator checkpoints (save/load_sweep_checkpoint,
#   __meta_variants) and atomic tmp+fsync+rename writes;
#   v24: round-12 adaptive-fidelity fast-forward —
#   the analytic-span attribution scalars (ctr_ff/ctr_ffq/ff_events)
#   join the phase-counter block so a mid-fast-forward checkpoint
#   resumes with exact round/quantum accounting;
#   v23: round-9 fan-out chain replay — carried
#   window occupancy widens the win_* cache arrays to [.., 4K] (partial
#   windows survive quantum cuts instead of forcing a refresh) and the
#   chain_fanout_served / chain_fallback counters land in Counters;
#   v22: blocking-semantics miss chains — banked
#   elements no longer install at bank time, so the mq_victim array is
#   gone (resolve fills at serve time and derives victims then);
#   v21: quantum-scoped block-window cache arrays
#   (win_meta/win_addr/win_base/win_seat; zero-width when
#   tpu/window_cache is off or the window phase is disabled);
#   v20: [telemetry] round-metric sample arrays
#   (tel_gauges/tel_cursor/tel_pend; zero-size when telemetry is off);
#   v19: VMManager accounting scalars (vm_*);
#   v18: iocoom register scoreboard (reg_ready);
#   v17: ThreadScheduler seats + stream store (strm_*,
#       seat_*; stream-indexed spawned_at/done_at);
#   v16: dram_qacc moment accumulators (m_g_1 queue model);
#   v15: DRAM busy-interval ring (history_list role);
#   v14: banked miss-chain arrays (mq_*, chain_*);
#   v13: packed int64 dir_word (tag|stamp|owner|state);
#   v4: packed int32 cache/dir metadata layout;
#   v12: syscall counters;
#   v11: [W*A, F] flat sharer planes;
#   v10: packed int64 cache words (timestamp LRU), dir_stamp, round_ctr,
#        optional (zero-size) CAPI channel arrays;
#   v9: ROI flag + statistics/progress sample ring;
#   v5: iocoom load/store queue state (lq/sq rings);
#   v6: dir_forwards counter (MOSI cache-to-cache transfers);
#   v7: link_free_mem horizons + net_link_wait_ps (NoC contention);
#   v8: cond vars + thread lifecycle (spawned_at/done_at, cond tokens)


class CheckpointCorruptError(RuntimeError):
    """The checkpoint file is unreadable — truncated mid-write by a
    crash, or damaged on storage.  Saves are atomic (tmp+fsync+rename),
    so a corrupt file is never the only copy a healthy writer left;
    delete it and fall back to re-running from the last good state."""


def _flatten_with_paths(state: SimState):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    out = {}
    for path, leaf in leaves:
        key = "/".join(
            p.name if hasattr(p, "name") else str(getattr(p, "idx", p))
            for p in path)
        out[key] = np.asarray(leaf)
    return out, treedef


def _atomic_savez(path: str, arrays: dict) -> None:
    """Write the .npz atomically: tmp file beside the target, fsync,
    rename — a crash at any point leaves either the old file or the new
    one, never a torn write (same pattern as events/trace_cache.py)."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp.npz")
    pending = tmp
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        pending = None
    finally:
        if pending is not None:
            try:
                os.unlink(pending)
            except OSError:
                pass
    from graphite_tpu.testing import faults
    faults.maybe_truncate(path)


def _open_checkpoint(path: str):
    """np.load with corrupt-file classification: anything the zip/npz
    layer throws (BadZipFile on a truncated archive, EOFError, pickle
    noise) becomes a CheckpointCorruptError naming the path."""
    try:
        z = np.load(path)
    except FileNotFoundError:
        raise
    except Exception as e:
        raise CheckpointCorruptError(
            f"checkpoint {path!r} is unreadable "
            f"({type(e).__name__}: {e}) — truncated or corrupt; delete "
            f"it and re-run from the last good state") from e
    if "__meta_schema" not in z.files:
        z.close()
        raise CheckpointCorruptError(
            f"checkpoint {path!r} has no __meta_schema field — not a "
            f"graphite_tpu checkpoint, or torn mid-write")
    return z


# v27 added ingest metadata WITHOUT touching state-leaf semantics, so
# v26 (whole-trace) checkpoints restore unchanged, v28 only changed
# the sharer store's layout, which converts on load, and v29's link rings
# start empty; anything older predates the routed-resolve counter
# semantics and is rejected.
_COMPATIBLE_SCHEMAS = (26, 27, 28, 29)


def _check_schema(path: str, z) -> None:
    if int(z["__meta_schema"]) not in _COMPATIBLE_SCHEMAS:
        raise ValueError(
            f"checkpoint schema {int(z['__meta_schema'])} not in "
            f"{_COMPATIBLE_SCHEMAS}")


def _load_leaves(path: str, z, template: SimState) -> SimState:
    """Fill ``template``'s leaves from the archive, shape-verified."""
    arrays, treedef = _flatten_with_paths(template)
    schema = int(z["__meta_schema"])
    planes = schema < 28
    leaves = []
    for key, tmpl in arrays.items():
        if key.startswith("__meta"):
            continue
        if schema < 29 and key.startswith(("link_mem/", "link_user/")):
            leaves.append(jnp.zeros(tmpl.shape, tmpl.dtype))
            continue
        if key not in z:
            raise ValueError(f"checkpoint missing field {key!r}")
        try:
            a = z[key]
        except ValueError:
            raise
        except Exception as e:
            raise CheckpointCorruptError(
                f"checkpoint {path!r} field {key!r} is unreadable "
                f"({type(e).__name__}: {e}) — truncated or corrupt") \
                from e
        if planes and key == "dir_sharers":
            a = sharers_from_planes(a, z["dir_word"].shape[-2])
        if a.shape != tmpl.shape:
            raise ValueError(
                f"checkpoint field {key!r} shape {a.shape} != expected "
                f"{tmpl.shape} (params mismatch?)")
        # Commit each leaf to a device array NOW, from an OWNED host
        # copy: under GRAPHITE_DONATE_STATE=1 megarun/megastep
        # donate their state argument, and donating a leaf that is
        # still a host numpy view of the (mmap'd) npz is an aliasing
        # hazard on the CPU backend (observed as nondeterministic
        # wrong results / bitcast garbage in resumed runs — the same
        # buffer-lifetime bug class that made donation opt-in,
        # engine/quantum.py state_donation_enabled).
        # jnp.array(copy=True) — not asarray, which zero-copies
        # aligned host buffers.
        leaves.append(jnp.array(a, dtype=tmpl.dtype, copy=True))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def save_checkpoint(path: str, state: SimState, steps: int = 0,
                    ingest: dict = None) -> None:
    """``ingest`` (streamed runs, engine/ingest.py — saved at segment
    seams) records the ingest frame beside the state: per-row segment
    bases, the segment capacity, and the full stream length.  Restore
    could derive valid bases from cursors alone (base placement never
    affects values, only which columns are resident), but the exact
    frame makes a resumed run's swap schedule — and thus its stall
    profile — match the original."""
    arrays, _ = _flatten_with_paths(state)
    arrays["__meta_steps"] = np.int64(steps)
    arrays["__meta_schema"] = np.int64(_SCHEMA_VERSION)
    if ingest is not None:
        arrays["__ingest_base"] = np.asarray(ingest["base"],
                                             dtype=np.int32)
        arrays["__ingest_segment_events"] = np.int64(
            ingest["segment_events"])
        arrays["__ingest_n_total"] = np.int64(ingest["n_total"])
    _atomic_savez(path, arrays)


def load_ingest(path: str) -> dict:
    """The ingest frame a v27 streamed checkpoint carries, or None for a
    whole-trace checkpoint (state loading ignores these keys either way
    — _load_leaves iterates the TEMPLATE's paths)."""
    with _open_checkpoint(path) as z:
        if "__ingest_base" not in z.files:
            return None
        return {
            "base": np.asarray(z["__ingest_base"], dtype=np.int32),
            "segment_events": int(z["__ingest_segment_events"]),
            "n_total": int(z["__ingest_n_total"]),
        }


def load_checkpoint(path: str, params: SimParams) -> Tuple[SimState, int]:
    """Rebuild a SimState (shaped by ``params``) from a checkpoint.

    The params must describe the same simulation (tile count, cache
    geometry, ...) that produced the checkpoint; shapes are verified.
    Raises CheckpointCorruptError on an unreadable file, ValueError on a
    schema or shape mismatch.
    """
    with _open_checkpoint(path) as z:
        if "__meta_variants" in z.files:
            raise ValueError(
                f"{path!r} is a batched sweep checkpoint "
                f"(V={int(z['__meta_variants'])}); load it with "
                f"load_sweep_checkpoint")
        _check_schema(path, z)
        saved_capi = z["ch_sent"].size > 0
        saved_streams = int(z["strm_cursor"].shape[0]) \
            if "strm_cursor" in z else 0
        template = make_state(params, has_capi=saved_capi,
                              num_streams=saved_streams
                              or params.num_tiles)
        steps = int(z["__meta_steps"])
        state = _load_leaves(path, z, template)
    return state, steps


# ------------------------------------------------- batched sweep state
# (v25: the sweep service preempts a long-running V-wide bucket at a
# window boundary and resumes it bit-identically — per-lane resume
# identity is the solo guarantee carried through the stacked axis)

def save_sweep_checkpoint(path: str, bstate: SimState,
                          steps: int = 0) -> None:
    """Save [V]-leading batched SweepSimulator state.  The leading axis
    is recorded (__meta_variants) so a resume against the wrong bucket
    width fails loudly instead of unflattening garbage."""
    arrays, _ = _flatten_with_paths(bstate)
    arrays["__meta_steps"] = np.int64(steps)
    arrays["__meta_schema"] = np.int64(_SCHEMA_VERSION)
    arrays["__meta_variants"] = np.int64(bstate.clock.shape[0])
    _atomic_savez(path, arrays)


def load_sweep_checkpoint(path: str, variants: List[SimParams],
                          num_streams: int = 0
                          ) -> Tuple[SimState, int]:
    """Rebuild batched [V]-leading state for ``variants`` (the PADDED
    bucket, in lane order).  The template is the same per-variant
    make_state stack SweepSimulator builds, so shapes verify per leaf
    with the [V] axis in front."""
    with _open_checkpoint(path) as z:
        if "__meta_variants" not in z.files:
            raise ValueError(
                f"{path!r} is a solo checkpoint; load it with "
                f"load_checkpoint")
        v = int(z["__meta_variants"])
        if v != len(variants):
            raise ValueError(
                f"sweep checkpoint holds {v} lanes, bucket has "
                f"{len(variants)} variants — resume must use the same "
                f"padded bucket")
        _check_schema(path, z)
        saved_capi = z["ch_sent"].size > 0
        saved_streams = int(z["strm_cursor"].shape[1]) \
            if "strm_cursor" in z else 0
        streams = num_streams or saved_streams or variants[0].num_tiles
        states = [make_state(p, has_capi=saved_capi, num_streams=streams)
                  for p in variants]
        template = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *states)
        steps = int(z["__meta_steps"])
        bstate = _load_leaves(path, z, template)
    return bstate, steps
