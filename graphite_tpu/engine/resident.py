"""Resident tile-sharded execution (``tpu/shard_state = resident``).

Round 11's ``tpu/tile_shards`` is "replicated state, sharded hot phase":
every SimState leaf lives whole on every device and each quantum step pays
13 output ``all_gather``s plus the ``pmin`` barrier, so resident HBM and
per-step collective bytes both scale with full T.  This module is the
other end of the design space — Graphite's own process partitioning
(reference: common/misc/config.h computeProcessToTileMapping + the socket
transport) rebuilt as collectives: every T-leading SimState leaf stays
SHARDED along the tile axis for the whole run, the window walk and local
advance run shard-local with zero cross-device traffic, and the
resolve/chain phase becomes home-binned routing —

  * each shard buckets its chain heads (and deferred L2 victims) by
    ``dense.home_fold`` home shard and ``all_to_all``-routes them to the
    home device (ONE fixed-capacity collective),
  * the home shard prices them against its resident directory slice with
    the chain-classify machinery (FCFS election, fan-out/owner budgets,
    MSI transition, NoC leg pricing) and counts the home-side events,
  * responses and coherence deliveries (owner downgrades, invalidation
    fan-out) route back in ONE more ``all_to_all``,

so one resolve sub-round is exactly two fixed-shape ``all_to_all``s per
chain iteration instead of thirteen full-T ``all_gather``s per step, and
the quantum barrier stays the existing ``pmin``.  Per-device resident
footprint drops from O(T) to O(T/S).

Correctness never depends on the routing-capacity heuristic: when a
source shard has more candidate records for one home shard than the
per-pair capacity, the pass raises an overflow flag, the host DISCARDS
the capped result and replays the same sub-round uncapped on a gathered
single-device copy (``tpu/route_capacity = 0`` — the default — sizes the
buffers so overflow is impossible and the spill never fires).  A second
host-side spill handles chains the routed pass cannot serve (e.g. a
directory victim with live sharers, which the replicated engine resolves
with the conflict-round eviction machinery): when a sub-round makes no
global progress while heads remain, the state is gathered through the
replicated ``resolve_memory`` once and re-placed.  Both spill decisions
are computed from ``psum``-reduced globals, so the host control sequence
is identical at every shard count.

Bit-identity contract: resident is its own program family — the exact
(hash-free) home-side elections, per-home fan/owner budgets and the
complex-slot subset below deliberately differ from the replicated
engine's hashed global elections — and the invariant the tests pin is
SHARD-COUNT INVARIANCE: ``shard_state=resident, tile_shards=S`` is
bit-identical to the same program at ``tile_shards=1`` for every S (the
single code path always runs under shard_map, on a 1-device mesh at
S=1).  Every loop/branch predicate that steers control flow goes through
``psum``/``pmin`` so no shard can diverge.

Validated subset (``_validate``): the resident program supports the
blocking-chain memory engine with private L2s and uniform DVFS — trace
ops are restricted to the compute/memory/branch/stall/done core (no
CAPI sync, no thread spawn/scheduler multiplexing), which is the
configuration the multichip scale-out studies run.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P_spec

from graphite_tpu.engine import cache as cachemod
from graphite_tpu.engine import core as coremod
from graphite_tpu.engine import dense
from graphite_tpu.engine import directory as dirmod
from graphite_tpu.engine import noc
from graphite_tpu.engine import resolve as resolvemod
from graphite_tpu.engine.kernels.chain import CTRL_BYTES, J_OWN, _lat
from graphite_tpu.engine.state import (
    PEND_BARRIER, PEND_CBC, PEND_COND, PEND_CSIG, PEND_EX_REQ, PEND_IFETCH,
    PEND_JOIN, PEND_MUTEX, PEND_NONE, PEND_RECV, PEND_SEND, PEND_SH_REQ,
    PEND_START, SimState, TraceArrays, dir_gather, dir_set, dword_owner,
    dword_pack, dword_stamp, dword_state, dword_tag, dword_with_meta,
    sharer_words, sharers_add,
    sharers_gather)
from graphite_tpu.engine.vparams import VariantParams, variant_params
from graphite_tpu.isa import DVFSModule, EventOp
from graphite_tpu.params import ConfigError, SimParams
from graphite_tpu.parallel import mesh as meshmod
from graphite_tpu.parallel.mesh import TILE_AXIS
from graphite_tpu.time_base import TIME_MAX

STAMP_STRIDE = coremod.STAMP_STRIDE
_spanned_bound = coremod._spanned_bound

# Cache/directory line states (shared vocabulary with cache.py/directory.py).
_I, _S, _O, _E, _M = 0, 1, 2, 3, 4

# Record planes of the request-routing all_to_all ([.., 6] int64 rows).
_PLANES = 6
_REC_EMPTY, _REC_REQ, _REC_VIC = 0, 1, 2

# A stamp value no in-use directory entry carries (vkey sentinel).
_NEVER = np.int32(2**31 - 1)

_FLAG_KEYS = ("progress", "more_heads", "overflow", "done", "routed")

# Trace ops the resident program family supports (see _validate).
_RESIDENT_OPS = (int(EventOp.NOP), int(EventOp.COMPUTE),
                 int(EventOp.MEM_READ), int(EventOp.MEM_WRITE),
                 int(EventOp.BRANCH), int(EventOp.STALL),
                 int(EventOp.DONE))

_SYNC_PENDS = (PEND_RECV, PEND_BARRIER, PEND_MUTEX, PEND_SEND, PEND_COND,
               PEND_JOIN, PEND_START, PEND_CSIG, PEND_CBC)


# ===================================================== config validation

def _validate(params: SimParams, state: SimState, trace: TraceArrays) -> None:
    """Reject configurations outside the resident program family — loud
    errors at driver entry, never silent wrong answers."""

    def bad(msg: str) -> None:
        raise ConfigError(f"tpu/shard_state=resident: {msg}")

    if params.shard_state != "resident":
        bad("driver entered with shard_state != resident")
    if params.num_tiles % params.tile_shards != 0:
        bad(f"tile_shards={params.tile_shards} must divide "
            f"num_tiles={params.num_tiles}")
    if params.miss_chain <= 0:
        bad("requires the blocking-chain memory engine (tpu/miss_chain > 0)")
    if not params.fanout_replay:
        bad("requires tpu/fanout_replay = true (the chain replay cadence)")
    if params.core.model != "simple":
        bad(f"requires the simple core model, got {params.core.model!r}")
    if params.shared_l2:
        bad("shared-L2 protocols are not routed; use a private-L2 protocol")
    if params.directory.directory_type != "full_map":
        bad(f"requires a full_map directory, got "
            f"{params.directory.directory_type!r}")
    if params.dram.queue_model_enabled:
        bad("DRAM queue contention state is not home-routed; disable "
            "dram/queue_model/enabled")
    if params.net_memory.model == "emesh_hop_by_hop" \
            or params.net_memory.queue_model_enabled:
        bad("contended memory-network models carry per-link state; use "
            "magic/emesh_hop_counter/atac with the queue model off")
    if params.fast_forward != 0:
        bad("tpu/fast_forward must be 0 (run-ahead spans are replicated-only)")
    if params.window_cache:
        bad("tpu/window_cache must be off (the cached span is full-T)")
    if params.block_events <= 0:
        bad("requires tpu/block_events > 0")
    if params.stats_enabled or params.progress_enabled \
            or params.telemetry_enabled:
        bad("periodic stats/progress/telemetry sampling is replicated-only")
    if params.enable_power_modeling:
        bad("power modeling is replicated-only")
    if params.track_miss_types:
        bad("cache/track_miss_types is replicated-only")
    if not params.models_enabled_at_start:
        bad("requires models enabled at start (no ROI gating)")
    if state.sched_enabled:
        bad("the thread scheduler (streams > tiles) is replicated-only")
    # Uniform DVFS periods: the home-side NoC/cache pricing folds the
    # per-tile period takes into scalars, which is exact only when every
    # tile's domain clocks agree.
    periods = np.asarray(jax.device_get(state.period_ps))
    if periods.size and not (periods == periods[0:1, :]).all():
        bad("requires uniform DVFS periods across tiles")
    # Trace-op subset (host scan; DONE padding included).
    ops = np.asarray(jax.device_get(trace.meta[0]))
    if not np.isin(ops, np.asarray(_RESIDENT_OPS)).all():
        extra = sorted(set(np.unique(ops).tolist())
                       - set(_RESIDENT_OPS))
        bad(f"trace contains unsupported ops {extra} (sync/spawn/CAPI "
            "events are replicated-only)")


def route_capacity(params: SimParams) -> int:
    """Per-(source shard, home shard) record capacity of the routing
    all_to_all.  0 (auto) sizes it at 2*T/S — one REQ plus one deferred
    victim per local tile is the structural maximum, so overflow is
    impossible and the spill path never fires."""
    tl = params.num_tiles // params.tile_shards
    return params.route_capacity if params.route_capacity > 0 else 2 * tl


# ===================================================== shard-local helpers

def _psum(x):
    return jax.lax.psum(x, TILE_AXIS)


def _local_ids(params: SimParams, num_local: int) -> jnp.ndarray:
    """[TL] int32 GLOBAL tile ids of this shard's slice."""
    base = jax.lax.axis_index(TILE_AXIS).astype(jnp.int32) * num_local
    return base + jnp.arange(num_local, dtype=jnp.int32)


def _fcfs_keys_tile(active, issue, gtile, num_tiles: int) -> jnp.ndarray:
    """FCFS key ordered by (issue, global tile), unique per active record
    (at most one REQ per requester tile per chain iteration).

    Rebased to the earliest active record ON THIS SHARD: every election
    group (directory slot, home-tile fan budget, (home, owner) budget)
    lives entirely on one home shard, so a per-shard rebase shifts all
    compared keys by one constant and the order — hence the winner — is
    shard-count invariant."""
    issue0 = jnp.min(jnp.where(active, issue, dense.BIG))
    return jnp.clip(issue - issue0, 0, jnp.int64(2**40)) \
        * num_tiles + gtile.astype(jnp.int64)


def _scalar_period(st: SimState, module: DVFSModule) -> jnp.ndarray:
    """Uniform-DVFS collapse: the per-tile period take becomes one scalar
    (validated host-side in ``_validate``)."""
    return st.period_ps[0, int(module)]


# ===================================================== home-side victim notify

def _vic_apply(params: SimParams, st: SimState, valid, g_tile, vline, vdirty,
               fidx_l, home_l, num_local: int) -> SimState:
    """Apply routed L2-victim notifications against the home-resident
    directory slice — the shard-local port of resolve._dir_evict_notify
    (same probe, same meta rewrites, same merged sharer-subtract scatter,
    with tile-BIT geometry in GLOBAL ids and set indices local)."""
    A = params.directory.associativity
    W = sharer_words(params.num_tiles)

    drow = dir_gather(st.dir_word, fidx_l, A)             # [R, A]
    dstate = dword_state(drow)
    match = (dword_tag(drow) == vline[:, None].astype(jnp.int64)) \
        & (dstate != _I) & valid[:, None]
    found = match.any(axis=1)
    way = jnp.argmax(match, axis=1).astype(jnp.int32)
    word_way = jnp.take_along_axis(drow, way[:, None], axis=1)[:, 0]
    est = dword_state(word_way)
    eowner = dword_owner(word_way)
    # Sharer row of the matched way: [R, W].
    esh = sharers_gather(st.dir_sharers, fidx_l, A, W, way)
    word_i = (g_tile // 64).astype(jnp.int32)
    bit = jnp.uint64(1) << (g_tile % 64).astype(jnp.uint64)
    woh = word_i[:, None] == jnp.arange(W, dtype=jnp.int32)[None, :]
    cur = jnp.sum(jnp.where(woh, esh, jnp.uint64(0)), axis=1)
    has_bit = (cur & bit) != 0
    drop_m = found & (est == _M) & (eowner == g_tile)
    drop_o = found & (est == _O) & (eowner == g_tile)
    drop_s = found & has_bit & ((est == _S) | ((est == _O)
                                               & (eowner != g_tile)))
    left = esh & ~jnp.where(woh, bit[:, None], jnp.uint64(0))
    empty = (left == 0).all(axis=1)
    mask_i = drop_m | ((drop_s | drop_o) & empty)          # entry dies
    mask_s = drop_o & ~empty                               # O -> S, ownerless
    new_word = jnp.where(mask_i, dword_with_meta(word_way, _I, -1),
                         dword_with_meta(word_way, _S, -1))
    dir_word = dir_set(st.dir_word, A, mask_i | mask_s, fidx_l, way,
                       new_word)
    # Merged sharer subtract: dead entries drop their whole row, live
    # S/O entries drop this tile's bit (modular add).
    dir_sharers = sharers_add(
        st.dir_sharers, A, W,
        rows=(drop_m, fidx_l, way, esh, jnp.zeros_like(esh)),
        bits=((drop_s | drop_o) & has_bit, fidx_l, way, g_tile),
        clear_bits=True)
    # Dirty victims write back at the home controller (home == dram site
    # for the private-L2 fold).
    wb = jnp.zeros((num_local,), jnp.int64).at[
        jnp.where(valid & vdirty, home_l, num_local)].add(1, mode="drop")
    c = st.counters._replace(dram_writes=st.counters.dram_writes + wb)
    return st._replace(dir_word=dir_word, dir_sharers=dir_sharers,
                       counters=c)


# ===================================================== the routed chain pass

def _routed_pass(params: SimParams, vp: VariantParams, st: SimState,
                 shards: int, cap: int):
    """One full chain replay, home-routed: fori over miss_chain + 1
    iterations (the extra iteration flushes the last deferred victim),
    each iteration exactly two tiled all_to_alls.

    Returns (state, overflow_flag(bool, local), routed_count(int64,
    local))."""
    T = params.num_tiles
    S = shards
    TL = T // S
    P = params.miss_chain
    A = params.directory.associativity
    W = sharer_words(T)
    ndsets = st.dir_word.shape[0] // (2 * A * TL)
    FL = TL * ndsets
    C = min(cap, 2 * TL)          # per-(source, dest) record capacity
    R = S * C                     # records per home shard per iteration
    NP = max(3, 2 + W)            # response-leg planes
    KF = min(params.max_inv_fanout_per_round, T)

    lids = _local_ids(params, TL)
    shard_lo = jax.lax.axis_index(TILE_AXIS).astype(jnp.int32) * TL

    p_core = _scalar_period(st, DVFSModule.CORE)
    p_l1i = _scalar_period(st, DVFSModule.L1_ICACHE)
    p_l1d = _scalar_period(st, DVFSModule.L1_DCACHE)
    p_l2 = _scalar_period(st, DVFSModule.L2_CACHE)
    p_dir = _scalar_period(st, DVFSModule.DIRECTORY)
    p_net = _scalar_period(st, DVFSModule.NETWORK_MEMORY)

    rstamp = st.round_ctr * STAMP_STRIDE + (STAMP_STRIDE - 1)
    flits_req = noc.num_flits(CTRL_BYTES, vp.net_memory.flit_width_bits)
    flits_data = noc.num_flits(params.line_size + CTRL_BYTES,
                               vp.net_memory.flit_width_bits)
    ack_ps = _lat(vp.inv_ack_cycles, p_core)

    stop_hi = st.mq_count
    head0 = st.mq_head
    base0 = jnp.where(head0 == 0, jnp.int64(0), st.chain_base)

    def _a2a(x):
        lead = x.shape[0] * x.shape[1]
        flat = x.reshape((lead,) + x.shape[2:])
        out = jax.lax.all_to_all(flat, TILE_AXIS, split_axis=0,
                                 concat_axis=0, tiled=True)
        return out.reshape(x.shape)

    def body(p, carry):
        (st, stopped, head, base, vic_line, vic_dirty, vic_valid,
         ovf, nroute) = carry

        # ---- source side: this shard's chain heads + deferred victims
        hsel = jnp.clip(head, 0, max(P - 1, 0))[None, :]
        req = jnp.take_along_axis(st.mq_req, hsel, axis=0)[0]
        delta = jnp.take_along_axis(st.mq_delta, hsel, axis=0)[0]
        extra = jnp.take_along_axis(st.mq_extra, hsel, axis=0)[0]
        r_act = (p < P) & (~stopped) & (head < stop_hi)
        kind = (req & 7).astype(jnp.int32)
        line = jnp.where(r_act, req >> 8, 0)
        is_ex_l = r_act & (kind == PEND_EX_REQ)
        is_if_l = r_act & (kind == PEND_IFETCH)
        issue = base + delta

        c_valid = jnp.concatenate([r_act, vic_valid])
        c_type = jnp.concatenate([
            jnp.where(r_act, _REC_REQ, _REC_EMPTY),
            jnp.where(vic_valid, _REC_VIC, _REC_EMPTY)]).astype(jnp.int64)
        c_tile = jnp.concatenate([lids, lids]).astype(jnp.int64)
        c_line = jnp.concatenate([line, jnp.where(vic_valid, vic_line, 0)])
        c_a = jnp.concatenate([kind.astype(jnp.int64),
                               jnp.zeros((TL,), jnp.int64)])
        c_b = jnp.concatenate([issue, vic_dirty.astype(jnp.int64)])
        c_extra = jnp.concatenate([extra, jnp.zeros((TL,), jnp.int64)])
        planes = jnp.stack([c_type, c_tile, c_line, c_a, c_b, c_extra],
                           axis=1)                         # [2TL, 6]
        c_home = resolvemod.home_of_line(params, c_line).astype(jnp.int32)
        c_dest = c_home // TL
        # Per-destination slot election: FCFS by candidate row (REQ rows
        # before VIC rows — the order is a per-shard constant, so any S
        # sees the same survivor set whenever nothing overflows).
        rank = dense.grouped_rank(c_dest, jnp.arange(2 * TL, dtype=jnp.int64),
                                  c_valid)
        routed = c_valid & (rank < C)
        slot = jnp.clip(rank, 0, C - 1)
        send = jnp.zeros((S, C, _PLANES), jnp.int64).at[
            jnp.where(routed, c_dest, S), slot].set(planes, mode="drop")
        ovf = ovf | (c_valid & (rank >= C)).any()
        nroute = nroute + jnp.sum((routed[:TL]).astype(jnp.int64))

        rec = _a2a(send).reshape(R, _PLANES)

        # ---- home side
        rtype = rec[:, 0]
        h_req = rtype == _REC_REQ
        h_vic = rtype == _REC_VIC
        g_tile = rec[:, 1].astype(jnp.int32)
        rline = jnp.where(rtype > 0, rec[:, 2], 0)
        home = resolvemod.home_of_line(params, rline).astype(jnp.int32)
        dset = resolvemod.dir_set_of_line(params, rline).astype(jnp.int32)
        home_l = jnp.clip(home - shard_lo, 0, TL - 1)
        fidx_l = home_l * ndsets + dset

        # Deferred victims first: iteration k's home sequence is
        # [notify_{k-1}, classify_k, apply_k] — the same point in the
        # global order as the replicated pass's
        # [classify_k, apply_k, notify_k].
        st = _vic_apply(params, st, h_vic, g_tile, rline,
                        rec[:, 4] != 0, fidx_l, home_l, TL)

        active = h_req
        is_ex = active & (rec[:, 3] == PEND_EX_REQ)
        is_if = active & (rec[:, 3] == PEND_IFETCH)
        h_issue = rec[:, 4]
        h_extra = rec[:, 5]

        drow = dir_gather(st.dir_word, fidx_l, A)          # [R, A]
        dstate = dword_state(drow)
        dstamp = dword_stamp(drow)
        match = (dword_tag(drow) == rline[:, None]) & (dstate != _I)
        hit = active & match.any(axis=1)
        hway = jnp.argmax(match, axis=1).astype(jnp.int32)
        invalid = dstate == _I
        # Exact hit-way exclusion table (no hash): a way some hit holds
        # this iteration must not be chosen as a miss victim.
        used_tbl = jnp.zeros((FL + 1, A), jnp.bool_).at[
            jnp.where(hit, fidx_l, FL), hway].set(True, mode="drop")
        hway_used = used_tbl[fidx_l]                       # [R, A]
        vkey = jnp.where(hway_used, _NEVER,
                         jnp.where(invalid, -1, dstamp)).astype(jnp.int32)
        miss_way = jnp.argmin(vkey, axis=1).astype(jnp.int32)
        can_alloc = active & ~hit & (
            jnp.take_along_axis(vkey, miss_way[:, None], axis=1)[:, 0]
            != _NEVER)
        way = jnp.where(hit, hway, miss_way)
        packed = _fcfs_keys_tile(active, h_issue, g_tile, T)
        wslot = dense.elect(active, packed, fidx_l * A + way, FL * A)

        way_word = jnp.take_along_axis(drow, way[:, None], axis=1)[:, 0]
        way_state = dword_state(way_word)
        way_owner = dword_owner(way_word)
        entry_row = sharers_gather(st.dir_sharers, fidx_l, A, W,
                                   way)                    # [R, W]
        entry_state = jnp.where(hit, way_state, _I)
        entry_owner = jnp.where(hit, way_owner, -1)
        entry_sharers = jnp.where(hit[:, None], entry_row, jnp.uint64(0))
        act = dirmod.transition(params.protocol_kind, is_ex, g_tile,
                                entry_state, entry_owner, entry_sharers, W,
                                is_ifetch=is_if)
        has_inv = (act.inv_targets != 0).any(axis=1)
        vic_dead = (way_state == _I) | (
            ((way_state == _S) | (way_state == _O))
            & (entry_row == 0).all(axis=1))
        cand0 = active & wslot & (hit | (can_alloc & vic_dead))
        # Fan-out budget, exact and PER HOME TILE (the replicated pass
        # ranks globally; a global rank is not shard-count invariant).
        need_fan = cand0 & has_inv
        fan_rank = dense.grouped_rank(home_l.astype(jnp.int64), packed,
                                      need_fan)
        cand = cand0 & (~has_inv | (fan_rank < KF))
        owner = act.owner_tile
        posr = dense.grouped_rank(
            home_l.astype(jnp.int64) * T + owner.astype(jnp.int64),
            packed, cand & act.owner_leg)
        serve = cand & ~(act.owner_leg & (posr >= J_OWN))
        owner_leg = act.owner_leg & serve
        fan_go = serve & has_inv
        evicting = serve & ~hit & (way_state != _I)
        hard_stop = active & ~serve & (
            (can_alloc & ~vic_dead) | (~hit & ~can_alloc)
            | (act.owner_leg & (posr >= J_OWN)))

        # Directory apply.
        dir_word = dir_set(st.dir_word, A, serve, fidx_l, way,
                           dword_pack(rline, st.round_ctr, act.new_state,
                                      act.new_owner))
        dir_sharers = sharers_add(
            st.dir_sharers, A, W,
            rows=(serve, fidx_l, way, entry_row, act.new_sharers))
        st = st._replace(dir_word=dir_word, dir_sharers=dir_sharers)

        # Timing (chain.py's queue-off private-L2 legs; uniform periods
        # collapse every per-tile take to a scalar).
        net_req = noc.unicast_ps(params.net_memory, g_tile, home, CTRL_BYTES,
                                 p_net, params.mesh_width,
                                 vnet=vp.net_memory)
        t_dir = h_issue + net_req + _lat(vp.dir_access_cycles, p_dir)
        leg_ps = noc.unicast_ps(params.net_memory, home, owner, CTRL_BYTES,
                                p_net, params.mesh_width,
                                vnet=vp.net_memory) \
            + _lat(vp.l2_access_cycles, p_l2) \
            + noc.unicast_ps(params.net_memory, owner, home,
                             params.line_size + CTRL_BYTES, p_net,
                             params.mesh_width, vnet=vp.net_memory)
        owner_ps = jnp.where(owner_leg, leg_ps, 0)
        inv_bool = dirmod.bitmap_to_bool(act.inv_targets, T)   # [R, T]
        inv_ps = jnp.where(
            fan_go,
            2 * noc.max_hop_to_mask_ps(params.net_memory, home, inv_bool,
                                       CTRL_BYTES, p_net, params.mesh_width,
                                       vnet=vp.net_memory) + ack_ps, 0)
        inv_count = jnp.where(fan_go,
                              dirmod.popcount(act.inv_targets), 0) \
            .astype(jnp.int64)
        need_read = serve & act.dram_read
        dram_ready = jnp.where(need_read, t_dir + owner_ps, 0) \
            + vp.dram_latency_ps + vp.dram_processing_ps
        t_data = jnp.maximum(t_dir + owner_ps,
                             jnp.where(need_read, dram_ready, 0))
        t_data = jnp.maximum(t_data, t_dir + inv_ps)
        reply_ps = noc.unicast_ps(params.net_memory, home, g_tile,
                                  params.line_size + CTRL_BYTES, p_net,
                                  params.mesh_width, vnet=vp.net_memory)
        l1_fill_ps = jnp.where(is_if, _lat(vp.l1i_access_cycles, p_l1i),
                               _lat(vp.l1d_access_cycles, p_l1d))
        completion = t_data + reply_ps + _lat(vp.l2_access_cycles, p_l2) \
            + l1_fill_ps + h_extra
        dram_wb = act.dram_write & serve

        # Home-side counters at the home tile.
        b = lambda m: m.astype(jnp.int64)          # noqa: E731
        hstack = jnp.stack([
            b(serve & ~is_ex), b(serve & is_ex), b(evicting), b(owner_leg),
            b(owner_leg & ~act.dram_write),
            b(serve) + inv_count,
            jnp.where(serve, flits_data, 0) + inv_count * flits_req,
            inv_count, b(need_read), b(dram_wb)], axis=1)   # [R, 10]
        hb = jnp.zeros((TL, 10), jnp.int64).at[home_l].add(hstack)
        c = st.counters
        st = st._replace(counters=c._replace(
            dir_sh_req=c.dir_sh_req + hb[:, 0],
            dir_ex_req=c.dir_ex_req + hb[:, 1],
            dir_evictions=c.dir_evictions + hb[:, 2],
            dir_writebacks=c.dir_writebacks + hb[:, 3],
            dir_forwards=c.dir_forwards + hb[:, 4],
            net_mem_pkts=c.net_mem_pkts + hb[:, 5],
            net_mem_flits=c.net_mem_flits + hb[:, 6],
            dir_invalidations=c.dir_invalidations + hb[:, 7],
            dram_reads=c.dram_reads + hb[:, 8],
            dram_writes=c.dram_writes + hb[:, 9]))

        # ---- response leg: one all_to_all carrying requester replies
        # (slots [0, TL)) and coherence deliveries (slots [TL, TL+2R)).
        resp0 = (b(serve) | (b(hard_stop) << 1) | (b(fan_go) << 2))
        vals = jnp.zeros((R, NP), jnp.int64) \
            .at[:, 0].set(resp0) \
            .at[:, 1].set(jnp.where(serve, completion, 0))
        dest_r = jnp.clip(g_tile // TL, 0, S - 1)
        slot_r = jnp.clip(g_tile - dest_r * TL, 0, TL - 1)
        resp_block = jnp.zeros((S, TL, NP), jnp.int64).at[
            jnp.where(active, dest_r, S), slot_r].set(vals, mode="drop")
        own_words = jax.lax.bitcast_convert_type(
            dirmod.make_tile_bit(jnp.clip(owner, 0, T - 1), W), jnp.int64)
        fan_words = jax.lax.bitcast_convert_type(act.inv_targets, jnp.int64)
        pad = NP - 2 - W
        def _down_rec(go, down_code, words):
            cols = [jnp.where(go, down_code + 1, 0)[:, None].astype(jnp.int64),
                    jnp.where(go, rline, 0)[:, None],
                    jnp.where(go[:, None], words, 0)]
            if pad:
                cols.append(jnp.zeros((R, pad), jnp.int64))
            return jnp.concatenate(cols, axis=1)
        own_recs = _down_rec(owner_leg,
                             act.owner_downgrade_to.astype(jnp.int64),
                             own_words)
        fan_recs = _down_rec(fan_go, jnp.int64(_I), fan_words)
        d_all = jnp.stack([own_recs, fan_recs], axis=1).reshape(2 * R, NP)
        own_pres = (jnp.arange(S)[None, :]
                    == jnp.clip(owner // TL, 0, S - 1)[:, None]) \
            & owner_leg[:, None]
        fan_pres = inv_bool.reshape(R, S, TL).any(axis=2) & fan_go[:, None]
        presence = jnp.stack([own_pres, fan_pres], axis=1).reshape(2 * R, S)
        down_block = jnp.where(presence.T[:, :, None], d_all[None, :, :], 0)
        out = jnp.concatenate([resp_block, down_block], axis=1)

        rin = _a2a(out)                                    # [S, TL+2R, NP]

        # ---- destination side: coherence deliveries BEFORE fills.
        downs = rin[:, TL:, :].reshape(S * 2 * R, NP)
        dvalid = downs[:, 0] > 0
        ddown = (downs[:, 0] - 1).astype(jnp.int32)
        dline = downs[:, 1]
        dw_u = jax.lax.bitcast_convert_type(downs[:, 2:2 + W], jnp.uint64)
        w_idx = (lids // 64).astype(jnp.int32)
        sh = (lids % 64).astype(jnp.uint64)
        bit_g = ((dw_u[:, w_idx] >> sh[None, :]) & 1) != 0  # [D, TL]
        tgt = (dvalid[:, None] & bit_g).T                   # [TL, D]
        D = downs.shape[0]
        dlinesT = jnp.broadcast_to(dline[None, :], (TL, D))
        ddownT = jnp.broadcast_to(ddown[None, :], (TL, D))
        st = st._replace(
            l2=cachemod.invalidate_by_value(st.l2, dlinesT, tgt, ddownT),
            l1d=cachemod.invalidate_by_value(st.l1d, dlinesT, tgt, ddownT))

        # ---- requester side: reply apply + private fills.
        resp = jnp.max(rin[:, :TL, :], axis=0)              # [TL, NP]
        rbits = resp[:, 0]
        served = r_act & ((rbits & 1) != 0)
        hard_stop_r = r_act & (((rbits >> 1) & 1) != 0)
        fan_go_r = r_act & (((rbits >> 2) & 1) != 0)
        completion_r = resp[:, 1]
        f2 = cachemod.fill(st.l2, line,
                           jnp.where(is_ex_l, _M, _S).astype(jnp.int32),
                           served, params.l2.num_sets, params.l2.replacement,
                           rstamp)
        vt1, vs1 = f2.victim_tag, f2.victim_state
        l1d = cachemod.invalidate_by_value(
            st.l1d, vt1[:, None], (served & (vs1 != _I))[:, None],
            jnp.full((TL, 1), _I, jnp.int32))
        fd = cachemod.fill(l1d, line,
                           jnp.where(is_ex_l, _M, _S).astype(jnp.int32),
                           served & ~is_if_l, params.l1d.num_sets,
                           params.l1d.replacement, rstamp)
        fi = cachemod.fill(st.l1i, line,
                           jnp.full((TL,), _S, jnp.int32),
                           served & is_if_l, params.l1i.num_sets,
                           params.l1i.replacement, rstamp)
        st = st._replace(l2=f2.cache, l1d=fd.cache, l1i=fi.cache)
        victim_dirty = served & ((vs1 == _M) | (vs1 == _O))
        victim_live = served & (vs1 != _I)

        c = st.counters
        st = st._replace(counters=c._replace(
            mem_stall_ps=c.mem_stall_ps
            + jnp.where(served, completion_r - issue, 0),
            net_mem_pkts=c.net_mem_pkts + b(served) + b(victim_dirty),
            net_mem_flits=c.net_mem_flits + b(served) * flits_req
            + b(victim_dirty) * flits_data,
            chain_fanout_served=c.chain_fanout_served + b(fan_go_r),
            chain_fallback=c.chain_fallback + b(hard_stop_r)))

        base = jnp.where(served, completion_r, base)
        head = head + served.astype(head.dtype)
        stopped = stopped | hard_stop_r
        return (st, stopped, head, base, vt1, victim_dirty, victim_live,
                ovf, nroute)

    carry0 = (st,
              jnp.zeros((TL,), jnp.bool_),        # stopped
              head0, base0,
              jnp.zeros((TL,), jnp.int64),        # vic_line
              jnp.zeros((TL,), jnp.bool_),        # vic_dirty
              jnp.zeros((TL,), jnp.bool_),        # vic_valid
              jnp.bool_(False), jnp.int64(0))
    (st, _stopped, head, base, _vl, _vd, _vv, ovf, nroute) = \
        jax.lax.fori_loop(0, P + 1, body, carry0)

    drained = (st.mq_count > 0) & (head >= st.mq_count)
    st = st._replace(
        mq_head=jnp.where(drained, 0, head),
        mq_count=jnp.where(drained, 0, st.mq_count),
        chain_base=jnp.where(drained, jnp.int64(0), base),
        clock=jnp.where(drained, base + st.chain_rel, st.clock),
        chain_rel=jnp.where(drained, jnp.int64(0), st.chain_rel),
        round_ctr=st.round_ctr + 1)
    return st, ovf, nroute


def _resolve_subround(params: SimParams, vp: VariantParams, st: SimState,
                      shards: int, cap: int):
    """One resolve sub-round (the resident replacement for resolve()):
    run the routed chain pass iff any shard holds parked requests, then
    emit psum-reduced control flags for the host driver."""
    any_mem = _psum(jnp.sum((st.mq_count > 0).astype(jnp.int32))) > 0

    def go(s):
        s = s._replace(ctr_resolve=s.ctr_resolve + 1)
        s, ovf, nroute = _routed_pass(params, vp, s, shards, cap)
        sat = (s.mq_head < s.mq_count).astype(jnp.int64)
        c = s.counters
        s = s._replace(counters=c._replace(
            dir_deferrals=c.dir_deferrals + sat))
        return s, ovf, nroute

    def skip(s):
        return s, jnp.bool_(False), jnp.int64(0)

    st, ovf, nroute = jax.lax.cond(any_mem, go, skip, st)
    flags = {
        "progress": _psum(jnp.sum(st.cursor.astype(jnp.int64)))
        + _psum(jnp.sum(st.clock))
        + _psum(jnp.sum(st.counters.mem_stall_ps)),
        "more_heads": _psum(jnp.sum(
            (st.mq_head < st.mq_count).astype(jnp.int32))),
        "overflow": _psum(ovf.astype(jnp.int32)),
        "done": _psum(jnp.sum(st.done.astype(jnp.int32))),
        "routed": _psum(nroute),
    }
    return st, flags


# ===================================================== local advance

def _advance(params: SimParams, vp: VariantParams, st: SimState,
             trace: TraceArrays, shards: int) -> SimState:
    """Shard-local window advance + the resident complex slot.

    The window loop is core.local_advance's chain cadence with every
    control predicate psum-reduced (a shard-local predicate would desync
    round_ctr across shards).  The complex slot shrinks to the resident
    op subset — DONE retires the stream, NOP (trace padding) retires for
    free — under the same eligibility gates."""
    T = params.num_tiles
    TL = T // shards
    P = params.miss_chain
    K = params.block_events
    N = trace.meta.shape[-1]
    cap_w = max(1, -(-P * 3 // (2 * K)))
    qps = vp.quantum_ps
    lids = _local_ids(params, TL)

    def can_retire(s):
        mid_ = s.mq_count > 0
        wb_ = _spanned_bound(params, vp, s.boundary)
        return (~s.done) & (s.pend_kind == PEND_NONE) & (s.cursor < N) \
            & jnp.where(mid_, (s.chain_rel < qps) & (s.mq_count < P),
                        s.clock < wb_)

    def wprog(s):
        return _psum(jnp.sum(s.cursor.astype(jnp.int64)))

    def wmore(s):
        return _psum(jnp.sum(can_retire(s).astype(jnp.int32))) > 0

    def wcond(c):
        j, pv, cv, more, _s = c
        return (j < cap_w) & ((j == 0) | ((cv > pv) & more))

    def wbody(c):
        j, _pv, cv, _more, s = c
        s = coremod._block_retire(params, vp, s, trace, tile_ids=lids)
        return (j + 1, cv, wprog(s), wmore(s), s)

    def wloop(s):
        init = (jnp.int32(0), jnp.int64(-1), wprog(s), wmore(s), s)
        return jax.lax.while_loop(wcond, wbody, init)[4]

    st = jax.lax.cond(wmore(st), wloop, lambda s: s, st)

    def _eligible(s):
        cur = jnp.minimum(s.cursor, N - 1)
        op = jnp.take_along_axis(trace.meta[0], cur[:, None], axis=1)[:, 0]
        gb = _spanned_bound(params, vp, s.boundary)
        el = (~s.done) & (s.pend_kind == PEND_NONE) & (s.clock < gb) \
            & (s.cursor < N) & (s.mq_count == 0) \
            & ((op == int(EventOp.DONE)) | (op == int(EventOp.NOP)))
        return el, op

    def mini(s):
        el, op = _eligible(s)
        is_done = el & (op == int(EventOp.DONE))
        return s._replace(
            cursor=s.cursor + el.astype(s.cursor.dtype),
            done=s.done | is_done,
            done_at=jnp.where(is_done, s.clock, s.done_at),
            round_ctr=s.round_ctr + 1,
            ctr_complex=s.ctr_complex + 1)

    el0, _op0 = _eligible(st)
    pred = _psum(jnp.sum(el0.astype(jnp.int32))) > 0
    return jax.lax.cond(pred, mini, lambda s: s, st)


# ===================================================== quantum boundary

def _begin_quantum(params: SimParams, vp: VariantParams,
                   st: SimState) -> SimState:
    """quantum.next_boundary, resident form: the min-reduction is the
    shard-local min followed by the ONE int64 pmin — the quantum barrier
    (two int32 pmin words, meshmod.pmin_i64)."""
    blocked = jnp.zeros_like(st.done)
    for k in _SYNC_PENDS:
        blocked = blocked | (st.pend_kind == k)
    runnable = (~st.done) & (~blocked)
    clk = st.clock
    if params.miss_chain > 0 and params.fanout_replay:
        clk = jnp.where(st.mq_head > 0, jnp.maximum(clk, st.chain_base), clk)
    masked = jnp.where(runnable, clk, TIME_MAX)
    mn = meshmod.pmin_i64(jnp.min(masked))
    q = vp.quantum_ps
    nb = (mn // q + 1) * q
    any_run = _psum(jnp.sum(runnable.astype(jnp.int32))) > 0
    boundary = jnp.where(any_run, nb, st.boundary + q).astype(jnp.int64)
    return st._replace(boundary=boundary, ctr_quantum=st.ctr_quantum + 1)


# ===================================================== program cache

class _Programs(NamedTuple):
    mesh: Any
    mesh1: Any
    shards: int
    cap: int
    begin: Any
    advance: Any
    resolve: Any
    spill: Any        # 1-device uncapped sub-round (overflow replay)
    stuck: Any        # replicated resolve_memory on gathered state


_CACHE: Dict[Tuple[int, int, int], _Programs] = {}
_CACHE_KEEPALIVE: Dict[int, SimParams] = {}


def _programs(params: SimParams, state: SimState,
              trace: TraceArrays) -> _Programs:
    shards = params.tile_shards
    cap = route_capacity(params)
    key = (id(params), shards, cap)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    devices = jax.devices()
    if len(devices) < shards:
        raise ConfigError(
            f"tpu/tile_shards={shards} needs at least that many devices; "
            f"jax sees {len(devices)} (force virtual CPU devices with "
            f"--xla_force_host_platform_device_count)")
    T = params.num_tiles
    vp = variant_params(params)
    mesh = meshmod.make_mesh(devices[:shards])
    mesh1 = meshmod.make_mesh(devices[:1])
    st_specs = meshmod.resident_specs(state, T)
    tr_specs = meshmod.resident_specs(trace, T)
    flag_specs = {k: P_spec() for k in _FLAG_KEYS}

    begin = jax.jit(jax.shard_map(
        lambda s: _begin_quantum(params, vp, s), mesh=mesh,
        in_specs=(st_specs,), out_specs=st_specs, check_vma=False))
    advance = jax.jit(jax.shard_map(
        lambda s, tr: _advance(params, vp, s, tr, shards), mesh=mesh,
        in_specs=(st_specs, tr_specs), out_specs=st_specs, check_vma=False))
    resolve = jax.jit(jax.shard_map(
        lambda s: _resolve_subround(params, vp, s, shards, cap), mesh=mesh,
        in_specs=(st_specs,), out_specs=(st_specs, flag_specs),
        check_vma=False))
    spill = jax.jit(jax.shard_map(
        lambda s: _resolve_subround(params, vp, s, 1, 2 * T), mesh=mesh1,
        in_specs=(st_specs,), out_specs=(st_specs, flag_specs),
        check_vma=False))
    stuck = jax.jit(lambda s: resolvemod.resolve_memory(params, vp, s))

    pg = _Programs(mesh=mesh, mesh1=mesh1, shards=shards, cap=cap,
                   begin=begin, advance=advance, resolve=resolve,
                   spill=spill, stuck=stuck)
    _CACHE[key] = pg
    _CACHE_KEEPALIVE[id(params)] = params
    return pg


# ===================================================== host driver

def _host_progress(state: SimState) -> int:
    c, k, m = jax.device_get((state.cursor, state.clock,
                              state.counters.mem_stall_ps))
    return int(np.sum(np.asarray(c, np.int64)) + np.sum(k) + np.sum(m))


def _host_all_done(state: SimState) -> bool:
    return bool(np.asarray(jax.device_get(state.done)).all())


# Host-side spill tally (test introspection; obs counters are the
# user-facing surface).
_DEBUG_STATS = {"overflow_spills": 0, "stuck_spills": 0}


def _obs_counters():
    from graphite_tpu.obs.registry import get_registry
    reg = get_registry()
    routed = reg.counter(
        "routed_chain_heads",
        "Chain-head records all_to_all-routed to home shards by the "
        "resident resolve pass")
    overflows = reg.counter(
        "routing_overflows_total",
        "Resident routing-capacity overflows (each one replays the "
        "sub-round uncapped on the host spill path)")
    return routed, overflows


def megarun(params: SimParams, state: SimState, trace: TraceArrays,
            max_quanta) -> SimState:
    """Run up to ``max_quanta`` resident quantum steps; the host drives
    the sub-round cadence from psum-reduced flags (identical control
    sequence at every shard count) and owns both spill paths."""
    _validate(params, state, trace)
    pg = _programs(params, state, trace)
    T = params.num_tiles
    state = meshmod.resident_place(state, pg.mesh, T)
    trace_p = meshmod.resident_place(trace, pg.mesh, T)
    cap_rounds = max(params.rounds_per_quantum,
                     params.max_events_per_quantum)
    routed_ctr, ovf_ctr = _obs_counters()

    for _q in range(int(max_quanta)):
        if _host_all_done(state):
            break
        state = pg.begin(state)
        prev = -1
        cur = _host_progress(state)
        i = 0
        while i < cap_rounds and (i == 0 or cur > prev):
            prev = cur
            st1 = pg.advance(state, trace_p)
            st2, flags = pg.resolve(st1)
            f = {k: int(v) for k, v in jax.device_get(flags).items()}
            if f["overflow"]:
                # Capacity miss: the capped result may have dropped
                # records — discard it and replay this sub-round
                # uncapped on one device.  Correctness never depends on
                # the capacity heuristic.
                ovf_ctr.inc(1)
                _DEBUG_STATS["overflow_spills"] += 1
                full = jax.device_get(st1)
                st2f, flags_f = pg.spill(
                    meshmod.resident_place(full, pg.mesh1, T))
                f = {k: int(v) for k, v in jax.device_get(flags_f).items()}
                state = meshmod.resident_place(jax.device_get(st2f),
                                               pg.mesh, T)
            else:
                state = st2
            if f["routed"]:
                routed_ctr.inc(f["routed"])
            cur = f["progress"]
            if cur <= prev and f["more_heads"] > 0:
                # Heads the routed pass cannot serve (live-sharer
                # directory victims need the conflict-round eviction
                # machinery): gather once through the replicated
                # resolve, re-place, continue.
                _DEBUG_STATS["stuck_spills"] += 1
                full = jax.device_get(state)
                full = jax.device_get(pg.stuck(full))
                state = meshmod.resident_place(full, pg.mesh, T)
                cur = _host_progress(state)
            i += 1
    return state


# ===================================================== batched (sweep) form

def _lane_select(run, new_tree, old_tree):
    """Per-lane freeze: keep ``old`` wherever ``run`` is False — the host
    mirror of vmapped-while masking (megarun_loop's masked semantics)."""
    def sel(n, o):
        m = run.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree_util.tree_map(sel, new_tree, old_tree)


def batched_specs(tree: Any, num_tiles: int) -> Any:
    """Resident PartitionSpecs for a LANE-LEADING batched pytree (the
    sweep engine's vmap axis): each leaf's tile axis shifts right by
    one."""
    def spec(path, leaf):
        name = meshmod._path_name(path)
        base = meshmod.resident_spec_for_shape(name, np.shape(leaf)[1:],
                                               num_tiles)
        return P_spec(None, *base)
    return jax.tree_util.tree_map_with_path(spec, tree)


def _batched_place(tree: Any, mesh, num_tiles: int) -> Any:
    """device_put a lane-leading batched pytree with resident placement."""
    def place(path, leaf):
        name = meshmod._path_name(path)
        base = meshmod.resident_spec_for_shape(name, np.shape(leaf)[1:],
                                               num_tiles)
        return jax.device_put(leaf, jax.sharding.NamedSharding(
            mesh, P_spec(None, *base)))
    return jax.tree_util.tree_map_with_path(place, tree)


class _SweepPrograms(NamedTuple):
    mesh: Any
    shards: int
    cap: int
    begin: Any
    advance: Any
    resolve: Any
    stuck: Any


_SWEEP_CACHE: Dict[Tuple[int, int], _SweepPrograms] = {}


def _sweep_programs(params: SimParams, bstate: SimState, trace: TraceArrays,
                    bvp: VariantParams) -> _SweepPrograms:
    shards = params.tile_shards
    key = (id(params), shards)
    hit = _SWEEP_CACHE.get(key)
    if hit is not None:
        return hit
    devices = jax.devices()
    if len(devices) < shards:
        raise ConfigError(
            f"tpu/tile_shards={shards} needs at least that many devices; "
            f"jax sees {len(devices)}")
    T = params.num_tiles
    # The sweep path always routes at the structural capacity (2*T/S per
    # pair): overflow is impossible, so the batched driver has no
    # overflow replay.
    cap = 2 * (T // shards)
    mesh = meshmod.make_mesh(devices[:shards])
    bst_specs = batched_specs(bstate, T)
    tr_specs = meshmod.resident_specs(trace, T)
    bvp_specs = jax.tree_util.tree_map(lambda _: P_spec(), bvp)
    flag_specs = {k: P_spec() for k in _FLAG_KEYS}
    run_spec = P_spec()

    def beg(bs, bv, run):
        new = jax.vmap(lambda s, v: _begin_quantum(params, v, s))(bs, bv)
        return _lane_select(run, new, bs)

    def adv(bs, tr, bv, run):
        new = jax.vmap(lambda s, v: _advance(params, v, s, tr, shards),
                       in_axes=(0, 0))(bs, bv)
        return _lane_select(run, new, bs)

    def res(bs, bv, run):
        new, flags = jax.vmap(
            lambda s, v: _resolve_subround(params, v, s, shards, cap))(bs, bv)
        return _lane_select(run, new, bs), flags

    begin = jax.jit(jax.shard_map(beg, mesh=mesh,
                              in_specs=(bst_specs, bvp_specs, run_spec),
                              out_specs=bst_specs, check_vma=False))
    advance = jax.jit(jax.shard_map(
        adv, mesh=mesh,
        in_specs=(bst_specs, tr_specs, bvp_specs, run_spec),
        out_specs=bst_specs, check_vma=False))
    resolve = jax.jit(jax.shard_map(
        res, mesh=mesh, in_specs=(bst_specs, bvp_specs, run_spec),
        out_specs=(bst_specs, flag_specs), check_vma=False))
    stuck = jax.jit(jax.vmap(
        lambda s, v: resolvemod.resolve_memory(params, v, s)))

    pg = _SweepPrograms(mesh=mesh, shards=shards, cap=cap, begin=begin,
                        advance=advance, resolve=resolve, stuck=stuck)
    _SWEEP_CACHE[key] = pg
    _CACHE_KEEPALIVE[id(params)] = params
    return pg


def _host_lane_progress(bstate: SimState) -> np.ndarray:
    c, k, m = jax.device_get((bstate.cursor, bstate.clock,
                              bstate.counters.mem_stall_ps))
    v = np.asarray(c, np.int64).sum(axis=1) + np.asarray(k).sum(axis=1) \
        + np.asarray(m).sum(axis=1)
    return v


def sweep_megarun(params: SimParams, bstate: SimState, trace: TraceArrays,
                  bvp: VariantParams, max_quanta) -> SimState:
    """Batched resident megarun: shard_map OUTSIDE vmap, one routed
    program serving every sweep lane, per-lane freezing mirroring the
    replicated sweep's masked megarun_loop."""
    _validate(params, jax.tree_util.tree_map(lambda x: x[0], bstate), trace)
    pg = _sweep_programs(params, bstate, trace, bvp)
    T = params.num_tiles
    V = int(np.shape(bstate.clock)[0])
    bstate = _batched_place(bstate, pg.mesh, T)
    trace_p = meshmod.resident_place(trace, pg.mesh, T)
    bvp_p = jax.device_put(bvp, jax.sharding.NamedSharding(pg.mesh, P_spec()))
    cap_rounds = max(params.rounds_per_quantum,
                     params.max_events_per_quantum)
    routed_ctr, _ovf_ctr = _obs_counters()

    nq = np.zeros((V,), np.int64)
    while True:
        done_l = np.asarray(jax.device_get(bstate.done)).all(axis=1)
        lane_go = (~done_l) & (nq < int(max_quanta))
        if not lane_go.any():
            break
        go_dev = jnp.asarray(lane_go)
        bstate = pg.begin(bstate, bvp_p, go_dev)
        nq += lane_go.astype(np.int64)
        prev_a = np.full((V,), -1, np.int64)
        cur_a = _host_lane_progress(bstate)
        i_a = np.zeros((V,), np.int64)
        while True:
            lane_run = lane_go & (i_a < cap_rounds) \
                & ((i_a == 0) | (cur_a > prev_a))
            if not lane_run.any():
                break
            prev_a = np.where(lane_run, cur_a, prev_a)
            run_dev = jnp.asarray(lane_run)
            bs1 = pg.advance(bstate, trace_p, bvp_p, run_dev)
            bstate, flags = pg.resolve(bs1, bvp_p, run_dev)
            f = jax.device_get(flags)
            if np.asarray(f["overflow"])[lane_run].any():
                raise AssertionError(
                    "resident sweep routed at structural capacity; "
                    "overflow is impossible")
            routed = int(np.asarray(f["routed"])[lane_run].sum())
            if routed:
                routed_ctr.inc(routed)
            cur_a = np.where(lane_run, np.asarray(f["progress"], np.int64),
                             cur_a)
            stuck = lane_run & (cur_a <= prev_a) \
                & (np.asarray(f["more_heads"]) > 0)
            if stuck.any():
                _DEBUG_STATS["stuck_spills"] += 1
                full = jax.device_get(bstate)
                vp_full = jax.device_get(bvp_p)
                resolved = jax.device_get(pg.stuck(full, vp_full))
                stuck_dev = stuck
                merged = jax.tree_util.tree_map(
                    lambda n, o: np.where(
                        stuck_dev.reshape((-1,) + (1,) * (np.ndim(n) - 1)),
                        np.asarray(n), np.asarray(o)), resolved, full)
                bstate = _batched_place(merged, pg.mesh, T)
                cur_a = np.where(stuck, _host_lane_progress(bstate), cur_a)
            i_a = np.where(lane_run, i_a + 1, i_a)
    return bstate


# ===================================================== collective census

def quantum_program(params: SimParams, state: SimState,
                    trace: TraceArrays, mesh):
    """ONE resident quantum step (begin -> advance -> one resolve
    sub-round) shard_mapped over ``mesh``: the census input below and
    the ahead-of-time compile target of tests/test_tpu_compile.py (a
    mesh of described, unattached devices)."""
    _validate(params, state, trace)
    T = params.num_tiles
    shards = mesh.devices.size
    cap = route_capacity(params)
    vp = variant_params(params)
    st_specs = meshmod.resident_specs(state, T)
    tr_specs = meshmod.resident_specs(trace, T)
    flag_specs = {k: P_spec() for k in _FLAG_KEYS}

    def one(s, tr):
        s = _begin_quantum(params, vp, s)
        s = _advance(params, vp, s, tr, shards)
        return _resolve_subround(params, vp, s, shards, cap)

    return jax.shard_map(one, mesh=mesh, in_specs=(st_specs, tr_specs),
                         out_specs=(st_specs, flag_specs), check_vma=False)


def lowered_quantum_collectives(params: SimParams, state: SimState,
                                trace: TraceArrays) -> Dict[str, int]:
    """Op census of ONE resident quantum step — the run_tests.sh gate
    input: zero all_gathers, at most two all_to_alls (both inside the
    chain fori body), exactly one pmin."""
    from graphite_tpu.engine.kernels import dispatch as kdispatch
    pg = _programs(params, state, trace)
    T = params.num_tiles
    fn = quantum_program(params, state, trace, pg.mesh)
    state_p = meshmod.resident_place(state, pg.mesh, T)
    trace_p = meshmod.resident_place(trace, pg.mesh, T)
    return kdispatch.jaxpr_op_counts(fn, state_p, trace_p)


def modeled_step_bytes(params: SimParams, state: SimState) -> Dict[str, int]:
    """Modeled cross-device bytes moved by ONE quantum step's collectives
    under each shard strategy (the weak_scaling.py column).

    replicated: every T-leading leaf is all_gathered back after the
    sharded window walk — (S-1)/S of each gathered leaf's bytes cross
    links.  resident: the two fixed-capacity all_to_alls per chain
    iteration — request records [S, C, 6] and the response/delivery leg
    [S, TL + 2R, NP] — of which (S-1)/S crosses links, times the
    miss_chain+1 iterations of one sub-round."""
    T = params.num_tiles
    S = max(1, params.tile_shards)
    TL = T // S
    C = route_capacity(params)
    R = S * C
    W = sharer_words(T)
    NP = max(3, 2 + W)
    cross = (S - 1) / S if S > 1 else 0.0

    gathered = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        name = meshmod._path_name(path)
        if meshmod.resident_spec_for(name, leaf, T) != P_spec():
            gathered += np.asarray(leaf).nbytes
    replicated = int(gathered * cross)

    per_iter = (S * C * _PLANES + S * (TL + 2 * R) * NP) * 8
    resident = int(per_iter * (params.miss_chain + 1) * cross * S)
    return {"replicated": replicated, "resident": resident}
