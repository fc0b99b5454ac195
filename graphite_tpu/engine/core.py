"""Local (intra-tile) event processing — the per-quantum core kernel.

This replaces the reference's app-thread hot path — the injected analysis
calls that queue each instruction into the core model and synchronously
probe the private cache hierarchy (reference: pin/instruction_modeling.cc:13-21
-> CoreModel::queueInstruction/iterate core_model.cc:282-299 ->
SimpleCoreModel::handleInstruction simple_core_model.cc:37-97 ->
Core::initiateMemoryAccess core.cc:139-266 -> L1/L2 controllers).

Execution shape (the round-3 perf design; VERDICT r2 item 1):

  * ``_block_retire`` — the fast path: every round gathers the next
    ``block_events`` events of every tile as one [T, K] window and retires
    the leading run of *simple* events per tile in one shot: COMPUTE /
    BRANCH / MEM hits (and single L2-hit fills), STALL, SYNC.  Per-event
    sequential semantics are preserved exactly — clocks advance through a
    max-plus prefix (each event is the transform t -> max(t, floor) + dt,
    which composes associatively), the branch predictor resolves
    within-window RAW on its table entries, and cache LRU uses monotone
    stamps so a window of touches commutes into scatter-max.  A window
    stops a tile at its first non-simple event, L2-fill hazard (an earlier
    in-window fill into the same set), quantum boundary, or stream end.
  * ``_complex_slot`` — the general path: one event per tile, handling
    every event kind (misses park the tile with a pending request for
    engine/resolve.py, sync/network/lifecycle ops do their bookkeeping).
    The block phase is a pure accelerator: any event it declines is
    handled here with identical semantics, so ``block_events = 0``
    degenerates to the round-2 one-event-per-slot engine (tested
    equivalent in tests/test_block_equivalence.py).

Timing semantics mirror SimpleCoreModel: every instruction pays its static
cost plus an L1I fetch access; memory operands add the memory-system
latency; branches pay 1 cycle when predicted, the mispredict penalty
otherwise (one-bit predictor, one_bit_branch_predictor.cc).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from graphite_tpu.engine import cache as cachemod
from graphite_tpu.engine import dense
from graphite_tpu.engine import noc
from graphite_tpu.engine import noc_flight
from graphite_tpu.engine.kernels import dispatch as kdispatch
from graphite_tpu.engine.kernels import window as kwindow
from graphite_tpu.engine.state import (
    PEND_BARRIER, PEND_CBC, PEND_COND, PEND_CSIG, PEND_EX_REQ, PEND_IFETCH,
    PEND_JOIN, PEND_MUTEX, PEND_NONE, PEND_RECV, PEND_SEND, PEND_SH_REQ,
    PEND_START, SimState, TraceArrays)
from graphite_tpu.engine.vparams import VariantParams, variant_params
from graphite_tpu.events.schema import ICACHE_BYTES_PER_INSTRUCTION
from graphite_tpu.isa import DVFSModule, EventOp, SyscallClass
from graphite_tpu.obs import scopes
from graphite_tpu import params as params_mod
from graphite_tpu.params import SimParams

I, S, E, M = cachemod.I, cachemod.S, cachemod.E, cachemod.M

# Stamp stride per engine round (value lives in params so the config
# validator needn't import the engine): each local round may issue stamps
# [rc*STRIDE, rc*STRIDE + STRIDE) — block events use offsets 0..K-1
# (params validates K <= STRIDE-2), the complex slot STRIDE-2, resolve
# fills STRIDE-1.  29 stamp bits / 64 = 8.4M rounds before the masked
# wrap (a wrap only perturbs LRU victim choice, never correctness —
# pack_word/with_stamp mask the field).
STAMP_STRIDE = params_mod.STAMP_STRIDE


# Shared with the window kernel (ONE definition — the kernels-on/off
# bit-identity contract forbids the walk and the complex-slot/cadence
# gates drifting apart): cycles->ps conversion, set-row way select, and
# the round-9 boundary-spanning rule all live in kernels/window.py.
_lat = kwindow._lat
_row_word = kwindow._row_word
_spanned_bound = kwindow._spanned_bound
_ff_bound = kwindow._ff_bound


def _period(state: SimState, module: DVFSModule):
    """[T] int32 ps-per-cycle of a DVFS module's current clock."""
    return state.period_ps[:, int(module)]


def mcp_tile(params: SimParams) -> int:
    """Sync/control server tile — the highest tile, as the reference places
    the MCP (common/misc/config.h:88)."""
    return params.num_tiles - 1


def _stamp_base(st: SimState):
    return st.round_ctr * STAMP_STRIDE


# ===================================================== block retirement

def _window_slice_gather(st: SimState, trace: TraceArrays, width: int):
    """Gather ``width`` events per tile starting at the cursor (seated
    stream's row under the ThreadScheduler).  Indices clamp at the trace
    end exactly like the original per-round gather, so cached values are
    bit-identical to a direct gather at any cursor."""
    N = trace.num_events
    pos = st.cursor[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    idx = jnp.minimum(pos, N - 1)
    # Streamed segments (engine/ingest.py): indices stay GLOBAL up to
    # here — the clamp above is against the full stream length — and
    # rebase into resident columns only at the gather (identity for a
    # whole-trace TraceArrays).
    if st.sched_enabled:
        srow = st.seat_stream
        cidx = trace.local_cols(idx, rows=srow)
        meta = trace.meta[:, srow[:, None], cidx]         # [3, T, width]
        addr = trace.addr[srow[:, None], cidx]            # [T, width]
    else:
        cidx = trace.local_cols(idx)
        meta = jnp.take_along_axis(trace.meta, cidx[None], axis=2)
        addr = jnp.take_along_axis(trace.addr, cidx, axis=1)
    return meta, addr


def _window_refresh(params: SimParams, st: SimState, trace: TraceArrays,
                    tile_active: jnp.ndarray,
                    width: int = None) -> SimState:
    """Quantum-scoped window cache (tpu/window_cache): re-gather the
    [T, WC] resident slice only when some ACTIVE tile's next-K events
    fall outside its cached span (cursor advanced past win_base + WC - K,
    restored state, or a seat rotation).  The guard is a scalar
    ``lax.cond`` whose operands are just the window arrays — cache-hit
    rounds pay an elementwise validity check instead of a full-trace
    gather.  ``width`` overrides the required resident span (round-12
    wide fast-forward rounds need ``_ff_width`` events)."""
    K = params.block_events if width is None else width
    WC = st.win_meta.shape[2]
    d = st.cursor - st.win_base
    ok = (d >= 0) & (d + K <= WC)
    if st.sched_enabled:
        ok = ok & (st.win_seat == st.seat_stream)
    need = jnp.any(tile_active & ~ok)

    def refresh(_):
        meta, addr = _window_slice_gather(st, trace, WC)
        seat = st.seat_stream if st.sched_enabled \
            else jnp.full_like(st.win_seat, -1)
        return meta, addr, st.cursor, seat

    def keep(_):
        return st.win_meta, st.win_addr, st.win_base, st.win_seat

    wm, wa, wb, ws = jax.lax.cond(need, refresh, keep, None)
    return st._replace(win_meta=wm, win_addr=wa, win_base=wb, win_seat=ws)


def _ff_width(params: SimParams) -> int:
    """Fast-forward span width in EVENTS (0 = leg compiled out).

    ``tpu/fast_forward`` counts block_events-sized windows; the width is
    clipped so one round's per-event stamps fit its exclusive
    STAMP_STRIDE allocation — it sizes BOTH fast-forward surfaces: the
    wide window rounds of the local cadence and the analytic run-ahead
    span.  A width of one window can never beat the narrow round it
    replaces, so the multiplier floors at 2 — and when K > STRIDE/2 no
    legal width can beat a narrow round, which disables the leg
    statically."""
    K = params.block_events
    if params.fast_forward <= 0 or K <= 0:
        return 0
    cap = STAMP_STRIDE // K
    if cap < 2:
        return 0
    return K * min(max(params.fast_forward, 2), cap)


def _fast_forward_retire(params: SimParams, vp: VariantParams,
                         st: SimState, trace: TraceArrays,
                         cand: jnp.ndarray) -> SimState:
    """One analytic fast-forward round (round 12): gather each candidate
    tile's next ``_ff_width`` events, price the longest hit/compute-only
    prefix in closed form (kernels/window.fast_forward_walk — shared
    with the Pallas and sharded paths exactly like the window walk),
    and land clock/cursor/cache/predictor/counter effects in one apply.

    The gather reads the TRACE directly (``_window_slice_gather``)
    rather than the resident window cache: an engaged span sweeps up to
    the cache's whole width, so the residual resident slice past the
    cursor almost never covers it — while the detection itself (probes
    vs resident cache state) is exactly the window's.  ``round_ctr``
    advances only when some tile ENGAGES (a span crossing the window
    bound into the ``fast_forward_span`` run-ahead budget): a declined
    probe uses no stamps, so reusing its round_ctr value is exact."""
    F = _ff_width(params)
    N = trace.num_events
    meta, addr = _window_slice_gather(st, trace, F)
    pos = st.cursor[:, None] + jnp.arange(F, dtype=jnp.int32)[None, :]
    valid_ev = (pos < N) & cand[:, None]
    fi = kwindow.FFIn(
        meta=meta, addr=addr, valid_ev=valid_ev, tile_active=cand,
        clock=st.clock, period_ps=st.period_ps, bp_table=st.bp_table,
        l1i_word=st.l1i.word, l1d_word=st.l1d.word,
        boundary=st.boundary, models_enabled=st.models_enabled,
        stamp_base=_stamp_base(st))
    mode = kdispatch.window_mode(params)
    if params.tile_shards > 1:
        out = kwindow.run_fast_forward_sharded(params, vp, fi, mode)
    else:
        out = kwindow.run_fast_forward(params, vp, fi, mode)

    any_engage = (out.n_ret > 0).any()
    c = st.counters
    c = c._replace(**{
        name: getattr(c, name) + out.ctr_inc[i]
        for i, name in enumerate(kwindow.WINDOW_CTRS)})
    return st._replace(
        clock=out.clock,
        cursor=st.cursor + out.n_ret,
        l1i=st.l1i._replace(word=out.l1i_word),
        l1d=st.l1d._replace(word=out.l1d_word),
        bp_table=out.bp_table,
        counters=c,
        round_ctr=st.round_ctr + any_engage.astype(jnp.int32),
        ctr_ff=st.ctr_ff + any_engage.astype(jnp.int64),
        ff_events=st.ff_events + jnp.sum(out.n_ret).astype(jnp.int64),
    )


@jax.named_scope(scopes.FAST_FORWARD)
def _fast_forward_guarded(params: SimParams, vp: VariantParams,
                          state: SimState,
                          trace: TraceArrays) -> SimState:
    """Adaptive cadence gate for the fast-forward leg: statically
    compiled out at ``fast_forward`` 0 (bit-identity with the
    pre-round-12 engine), under iocoom (RAW floors disqualify the
    closed form), with the ThreadScheduler seated (rotation boundaries
    are thread-switch events the span must not cross), or when no span
    could beat a window round (``_ff_width`` == 0).  Otherwise: price
    run-ahead spans (commits past the window bound, admitted by the
    ``fast_forward_span`` budget) until no tile engages, then fall back
    to the detailed machinery — whose window rounds at fast_forward > 0
    are the WIDE in-bound surface of the same leg."""
    if _ff_width(params) == 0 or params.core.model == "iocoom" \
            or state.sched_enabled:
        return state
    N = trace.num_events
    P = params.miss_chain

    def cand_of(s):
        c = (~s.done) & (s.pend_kind == PEND_NONE) & (s.cursor < N) \
            & (s.clock < _ff_bound(params, vp, s.boundary))
        if P > 0:
            c = c & (s.mq_count == 0)       # pending chain heads decline
        return c

    def prog(s):
        return jnp.sum(s.cursor.astype(jnp.int64))

    cap = max(1, params.max_events_per_quantum)

    def fcond(carry):
        i, pv, cv, _s = carry
        return (i < cap) & ((i == 0) | (cv > pv))

    def fbody(carry):
        i, _pv, cv, s = carry
        s = _fast_forward_retire(params, vp, s, trace, cand_of(s))
        return i + 1, cv, prog(s), s

    def floop(s):
        _, _, _, out = jax.lax.while_loop(
            fcond, fbody, (jnp.int32(0), jnp.int64(-1), prog(s), s))
        return out

    # At span 0 the walk's engage rule (commits past the window bound)
    # provably never fires — skip the probe outright.  ``span_ps`` is a
    # VARIANT operand, so the gate is a runtime scalar and sweep lanes
    # with mixed spans stay one program.
    return jax.lax.cond(cand_of(state).any() & state.models_enabled
                        & (vp.fast_forward_span_ps > 0),
                        floop, lambda s: s, state)


@jax.named_scope(scopes.WINDOW_WALK)
def _block_retire(params: SimParams, vp: VariantParams, st: SimState,
                  trace: TraceArrays, width: int = None,
                  tile_ids=None) -> SimState:
    """Retire the leading run of simple events in each tile's [K] window.

    This function is the gather/apply shell: it assembles the window
    operands (trace slice via the window cache, cache arrays, chain
    state), dispatches the WALK — probes, hit/stall/hazard
    classification, branch-predictor RAW, the max-plus clock prefix,
    chain banking, LRU touches/fills, counter accumulation — and lands
    the results back into SimState.  The walk itself lives in
    engine/kernels/window.py as ONE pure per-tile function shared by
    both execution paths: inline lax (``tpu/pallas_kernels`` off — the
    pre-round-10 program, op for op) or a single fused Pallas kernel
    gridded over tile blocks (interpret / tpu modes), bit-identical by
    construction.  See kernels/window.py for the walk semantics and the
    round-7/9 blocking-chain commentary.

    ``width`` (round 12, ``tpu/fast_forward`` > 0) widens the window to
    ``_ff_width`` events: the UNCHANGED walk — probes, hazards, chain
    banking, the max-plus prefix — runs over a [T, width] slice, so one
    wide round retires the run + banks the misses that several narrow
    rounds would have, which is where the fast-forward round-count drop
    comes from.  The walk is width-polymorphic by construction
    (kernels/window.py), so wide and narrow rounds cannot drift.
    """
    K = params.block_events if width is None else width
    T = params.num_tiles
    N = trace.num_events
    P = params.miss_chain
    shared_l2 = params.shared_l2
    iocoom = params.core.model == "iocoom"

    nm0 = st.mq_count if P > 0 else jnp.zeros(T, dtype=jnp.int32)
    in_chain = nm0 > 0
    # Boundary-spanning windows (round 9, tpu/fanout_replay & P > 0):
    # the quantum cut used to truncate every window mid-flight, so the
    # empty-chain bound widens by one quantum of overrun.  Mid-chain
    # tiles run on the relative clock: the boundary check moves to the
    # per-event prefix inside the walk.
    wbound = _spanned_bound(params, vp, st.boundary)
    tile_active = (~st.done) & (st.pend_kind == PEND_NONE) \
        & (in_chain | (st.clock < wbound)) & (st.cursor < N)

    # ---- window gather: next K events per tile.  With the
    # ThreadScheduler, each tile reads its SEATED stream's trace row.
    # With the window cache, rounds read the small resident [T, WC] slice
    # at per-tile offsets (refreshed from the trace only when an active
    # tile outruns it) — values are bit-identical to the direct gather.
    pos = st.cursor[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
    valid_ev = (pos < N) & tile_active[:, None]
    if st.win_meta.shape[2] >= K:
        st = _window_refresh(params, st, trace, tile_active, width=K)
        WC = st.win_meta.shape[2]
        # Post-refresh every ACTIVE tile's offset is in bounds; inactive
        # tiles clamp and read junk that valid_ev masks (exactly the junk
        # the trace-end clamp produced before).
        off = jnp.clip(st.cursor - st.win_base, 0, WC - K)
        oidx = off[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
        meta = jnp.take_along_axis(st.win_meta, oidx[None], axis=2)
        addr = jnp.take_along_axis(st.win_addr, oidx, axis=1)
    else:
        meta, addr = _window_slice_gather(st, trace, K)

    S_ids = st.spawned_at.shape[0]
    wi = kwindow.WindowIn(
        meta=meta, addr=addr, valid_ev=valid_ev, tile_active=tile_active,
        tile_ids=(jnp.arange(T, dtype=jnp.int32)
                  if tile_ids is None else tile_ids),
        clock=st.clock, period_ps=st.period_ps, bp_table=st.bp_table,
        l1i_word=st.l1i.word, l1i_rr=st.l1i.rr_ptr,
        l1d_word=st.l1d.word, l1d_rr=st.l1d.rr_ptr,
        l2_word=None if shared_l2 else st.l2.word,
        l2_rr=None if shared_l2 else st.l2.rr_ptr,
        boundary=st.boundary, models_enabled=st.models_enabled,
        stamp_base=_stamp_base(st),
        chain_rel=st.chain_rel if P > 0 else None,
        mq_count=st.mq_count if P > 0 else None,
        mq_head=st.mq_head if P > 0 else None,
        mq_req=st.mq_req if P > 0 else None,
        mq_delta=st.mq_delta if P > 0 else None,
        mq_extra=st.mq_extra if P > 0 else None,
        lq_ready=st.lq_ready if iocoom else None,
        sq_ready=st.sq_ready if iocoom else None,
    )
    # Sharded dispatch (tpu/tile_shards > 1, inside the quantum
    # program's shard_map): each device walks its own T/S tile slice and
    # all_gathers the results — the whole walk is shard-local compute.
    # Resident mode (shard_state = "resident") never takes this branch:
    # its caller already runs shard-local with T = T/S operands and an
    # explicit tile_ids slice, so the plain path below IS its program.
    if params.tile_shards > 1 and params.shard_state == "replicated" \
            and tile_ids is None:
        out = kwindow.run_window_sharded(params, vp, wi, S_ids,
                                         kdispatch.window_mode(params))
    else:
        out = kwindow.run_window(params, vp, wi, S_ids,
                                 kdispatch.window_mode(params))

    # ---- SPAWN: start the child's stream once the request lands on its
    # tile — the walk's one cross-tile effect, applied here as a single
    # scatter-max over the returned (mask, child, landing-time) triples.
    spawned_at = st.spawned_at.at[
        jnp.where(out.spawn_mask, out.spawn_child, S_ids)].max(
        out.spawn_land, mode="drop")

    c = st.counters
    c = c._replace(**{
        name: getattr(c, name) + out.ctr_inc[i]
        for i, name in enumerate(kwindow.WINDOW_CTRS)})

    st = st._replace(
        clock=out.clock,
        cursor=st.cursor + out.n_ret,
        l1i=st.l1i._replace(word=out.l1i_word, rr_ptr=out.l1i_rr),
        l1d=st.l1d._replace(word=out.l1d_word, rr_ptr=out.l1d_rr),
        l2=st.l2 if shared_l2
        else st.l2._replace(word=out.l2_word, rr_ptr=out.l2_rr),
        bp_table=out.bp_table,
        spawned_at=spawned_at,
        round_ctr=st.round_ctr + 1,
        ctr_window=st.ctr_window + 1,
        counters=c,
    )
    if P > 0:
        st = st._replace(
            mq_req=out.mq_req,
            mq_delta=out.mq_delta,
            mq_extra=out.mq_extra,
            mq_count=out.mq_count,
            chain_rel=out.chain_rel,
        )
    if width is not None and width > params.block_events:
        # Round-12 attribution: a wide fast-forward round counts when it
        # retires MORE than one narrow round's per-tile capacity — the
        # events a detailed round could not have priced.  ctr_ffq
        # derives from ctr_ff growth at the quantum layer.
        gain = jnp.maximum(out.n_ret - params.block_events, 0)
        st = st._replace(
            ctr_ff=st.ctr_ff + (gain > 0).any().astype(jnp.int64),
            ff_events=st.ff_events + jnp.sum(gain).astype(jnp.int64))
    return st


# ======================================================== complex slot

@jax.named_scope(scopes.COMPLEX_SLOT)
def _complex_slot(params: SimParams, vp: VariantParams, state: SimState,
                  trace: TraceArrays) -> SimState:
    """One event per tile, every event kind — the general path."""
    T = params.num_tiles
    N = trace.num_events
    line_bits = params.line_size.bit_length() - 1
    rows = jnp.arange(T)
    num_locks = state.lock_holder.shape[0]
    num_bars = state.bar_count.shape[0]
    mcp = mcp_tile(params)
    st = state
    c = st.counters

    # Round-9: the complex slot spans like the window — a tile whose
    # window ran past the cut and parked on a sync/atomic/lifecycle
    # event retires it now instead of idling a whole quantum (sync
    # costs are timestamp-based, so the early retire is skew-safe).
    cbound = _spanned_bound(params, vp, st.boundary)
    active = (~st.done) & (st.pend_kind == PEND_NONE) \
        & (st.clock < cbound) & (st.cursor < N)
    if params.miss_chain > 0:
        # Complex events need an absolute clock — a tile with banked
        # chain elements waits for the resolve pass to drain them.
        active = active & (st.mq_count == 0)
    cur = jnp.minimum(st.cursor, N - 1)
    srow = st.seat_stream if st.sched_enabled else rows
    ccur = trace.local_cols(cur, rows=srow)   # segment rebase (identity
    #   for a whole-trace TraceArrays — engine/ingest.py)
    ev = trace.meta[:, srow, ccur]         # [3, T] one fused gather
    addr = trace.addr[srow, ccur]
    op = jnp.where(active, ev[0], EventOp.NOP)
    arg = ev[1]
    arg2 = ev[2]

    # Region of interest: outside it, compute/branch/memory events
    # fast-forward — zero cost, no cache effects, no counters (the
    # reference's disabled-models mode runs functionally without
    # instrumentation, simulator.cc:287-301).  Sync, network, and
    # lifecycle events stay functional either way.
    en = st.models_enabled
    if params.enable_core_modeling:
        models_enabled = (st.models_enabled
                          | (op == EventOp.ENABLE_MODELS).any()) \
            & ~(op == EventOp.DISABLE_MODELS).any()
    else:
        # Core modeling disabled in config: ROI markers in the trace
        # cannot re-enable it.
        models_enabled = st.models_enabled

    # iocoom drain points: atomics, sync/thread ops, DONE (and branches
    # unless speculative loads are on) wait for every outstanding
    # load/store completion (reference: iocoom_core_model.cc LQ/SQ
    # synchronization; [core/iocoom] carbon_sim.cfg:180-186).
    if params.core.model == "iocoom":
        drain_t = jnp.maximum(jnp.max(st.lq_ready, axis=0),
                              jnp.max(st.sq_ready, axis=0))
        drain_op = ((op == EventOp.ATOMIC)
                    | (op == EventOp.BARRIER_WAIT)
                    | (op == EventOp.MUTEX_LOCK)
                    | (op == EventOp.MUTEX_UNLOCK)
                    | (op == EventOp.COND_WAIT)
                    | (op == EventOp.COND_SIGNAL)
                    | (op == EventOp.COND_BROADCAST)
                    | (op == EventOp.JOIN)
                    | (op == EventOp.RECV)
                    | (op == EventOp.SEND)
                    | (op == EventOp.SYNC)
                    | (op == EventOp.SPAWN)
                    | (op == EventOp.DVFS_SET)
                    | (op == EventOp.SYSCALL)
                    | (op == EventOp.DONE))
        if not params.core.speculative_loads:
            drain_op = drain_op | (op == EventOp.BRANCH)
        if params.core.mixed:
            drain_op = drain_op & jnp.asarray(params.core.iocoom_mask)
        clk = jnp.where(drain_op, jnp.maximum(st.clock, drain_t),
                        st.clock)
        # Register scoreboard RAW floor (reference
        # iocoom_core_model.cc:119-143: read-register operands delay the
        # instruction to their ready times): a COMPUTE event naming a
        # source register stalls until that register's ready time.
        sreg = (arg2 >> 20) & 31          # src reg + 1, 0 = none
        has_sreg = (op == EventOp.COMPUTE) & (sreg > 0)
        if params.core.mixed:
            has_sreg = has_sreg & jnp.asarray(params.core.iocoom_mask)
        rr = st.reg_ready[jnp.maximum(sreg - 1, 0), rows]
        clk = jnp.where(has_sreg, jnp.maximum(clk, rr), clk)
    else:
        clk = st.clock

    # Per-tile clock periods (DVFS-aware), ps per cycle.
    p_core = _period(st, DVFSModule.CORE)
    p_l1i = _period(st, DVFSModule.L1_ICACHE)
    p_l1d = _period(st, DVFSModule.L1_DCACHE)
    p_l2 = _period(st, DVFSModule.L2_CACHE)
    p_nu = _period(st, DVFSModule.NETWORK_USER)

    l1i_ps = _lat(vp.l1i_access_cycles, p_l1i)
    l1d_ps = _lat(vp.l1d_access_cycles, p_l1d)
    l2_ps = _lat(vp.l2_access_cycles, p_l2)
    l2_tag_ps = _lat(vp.l2_tags_access_cycles, p_l2)
    cycle_ps = _lat(1, p_core)

    shared_l2 = params.shared_l2
    line = addr >> line_bits
    pI = cachemod.probe(st.l1i, line, params.l1i.num_sets)
    pD = cachemod.probe(st.l1d, line, params.l1d.num_sets)
    if shared_l2:
        pL2 = None   # no private L2: L1 misses go to the home slice
    else:
        pL2 = cachemod.probe(st.l2, line, params.l2.num_sets)

    stamp = _stamp_base(st) + STAMP_STRIDE - 2

    # ---------------------------------------------------- COMPUTE blocks
    is_comp = op == EventOp.COMPUTE
    # COMPUTE arg2 packs (icount | src_reg+1 << 20 | dst_reg+1 << 25) —
    # events/schema.py register annotations for the iocoom scoreboard.
    icount_ev = jnp.maximum(arg2 & ((1 << 20) - 1), 0).astype(jnp.int64)
    n_lines = jnp.maximum(
        (icount_ev * ICACHE_BYTES_PER_INSTRUCTION + params.line_size - 1)
        // params.line_size, 1)
    cost_ps = _lat(jnp.maximum(arg, 0), p_core)
    # i-fetch: every instruction pays one L1I access (SimpleCoreModel
    # modelICache per instruction); on an L1I miss the first line's L2
    # latency is charged for each line of the block (sequential-stream
    # approximation — only the first line's tags are actually filled).
    fetch_ps = icount_ev * l1i_ps
    if shared_l2:
        comp_l2path = jnp.zeros_like(is_comp)
        comp_block = is_comp & ~pI.hit & en
        dt_comp = cost_ps + fetch_ps
    else:
        comp_l2path = is_comp & ~pI.hit & pL2.hit & en
        comp_block = is_comp & ~pI.hit & ~pL2.hit & en
        dt_comp = cost_ps + fetch_ps \
            + jnp.where(~pI.hit, n_lines * l2_ps, 0)
    comp_ok = is_comp & ~comp_block

    # ------------------------------------------------------- BRANCH
    is_br = op == EventOp.BRANCH
    taken = arg != 0
    if params.core.bp_type == "none":
        # No predictor modeled: a branch is a plain 1-cycle
        # instruction (reference: branch_predictor.cc factory returns
        # NULL and no mispredict penalty is ever charged).
        correct = jnp.ones_like(is_br)
        dt_br = cycle_ps + l1i_ps
        bp_table = st.bp_table
    else:
        bidx = (addr % params.core.bp_size).astype(jnp.int32)
        pred = st.bp_table[rows, bidx]
        correct = pred == taken
        dt_br = jnp.where(
            correct, cycle_ps,
            _lat(vp.bp_mispredict_penalty, p_core)) + l1i_ps
        bp_table = st.bp_table.at[
            rows, jnp.where(is_br & en, bidx, params.core.bp_size)
        ].set(taken, mode="drop")

    # ------------------------------------------------- MEMORY OPERANDS
    is_rd = op == EventOp.MEM_READ
    is_at = op == EventOp.ATOMIC
    is_wr = (op == EventOp.MEM_WRITE) | is_at
    is_mem = is_rd | is_wr
    # Writable states: M only — except shared-L2 MESI, where an
    # E-granted L1 line is silently writable (the exclusive owner
    # upgrades E->M locally without telling the home slice; reference
    # pr_l1_sh_l2_mesi l1_cache_cntlr store-on-E path).
    mesi_local = params.protocol_kind == "sh_l2_mesi"
    writable = pD.state >= (E if mesi_local else M)
    l1_ok = pD.hit & (is_rd | writable)
    mem_l1 = is_mem & l1_ok & en
    if shared_l2:
        mem_l2 = jnp.zeros_like(mem_l1)
        mem_rem = is_mem & ~l1_ok & en
    else:
        l2_ok = pL2.hit & (is_rd | (pL2.state == M))
        mem_l2 = is_mem & ~l1_ok & l2_ok & en
        mem_rem = is_mem & ~l1_ok & ~l2_ok & en
    at_extra = jnp.where(is_at, cycle_ps, 0)
    dt_mem_l1 = l1d_ps + at_extra
    dt_mem_l2 = l1d_ps + l2_ps + at_extra

    # --------------------------------------------- USER NETWORK (CAPI)
    is_send_op = op == EventOp.SEND
    is_recv = op == EventOp.RECV
    if st.has_capi:
        chan_depth = st.ch_time.shape[0]
        dst = jnp.clip(arg2, 0, T - 1)
        sent_row = st.ch_sent[rows, dst]
        recvd_row = st.ch_recvd[rows, dst]
        ch_full = (sent_row - recvd_row) >= chan_depth
        is_send = is_send_op & ~ch_full
        send_block = is_send_op & ch_full
        slot_idx = sent_row % chan_depth
        # The reused ring slot holds the consuming recv's completion time
        # (written by resolve_recv): even when the count check shows space,
        # the message can't occupy the slot before the recv that freed it.
        slot_freed = st.ch_time[slot_idx, rows, dst]
        depart = jnp.maximum(clk + cycle_ps, slot_freed)
        if noc_flight.contended(params.net_user):
            # CAPI data packets contend per link on the user mesh
            # (reference: the USER network's own hop-by-hop model +
            # queue models, network_model_emesh_hop_by_hop.cc).
            fl = noc_flight.flight(
                params.net_user, params.mesh_width, params.mesh_height,
                rows.astype(jnp.int32), dst, depart,
                noc.num_flits(jnp.maximum(arg, 0),
                              vp.net_user.flit_width_bits),
                is_send & active, st.link_user, p_nu,
                vnet=vp.net_user)
            st = st._replace(link_user=fl.rings)
            c = c._replace(net_link_wait_ps=c.net_link_wait_ps
                           + jnp.where(is_send & active & en,
                                       fl.wait_ps, 0))
            arrival = jnp.where(is_send, fl.arrival, depart)
        else:
            send_net_ps = noc.unicast_ps(
                params.net_user, rows, dst, jnp.maximum(arg, 0), p_nu,
                params.mesh_width, vnet=vp.net_user)
            arrival = depart + send_net_ps
        rows_send = jnp.where(is_send, rows, T).astype(jnp.int32)
        ch_time = st.ch_time.at[slot_idx, rows_send, dst].set(
            arrival, mode="drop")
        ch_sent = st.ch_sent.at[rows_send, dst].add(1, mode="drop")
    else:
        is_send = jnp.zeros_like(is_send_op)
        send_block = is_send_op          # a CAPI-less state can't send
        ch_time, ch_sent = st.ch_time, st.ch_sent
    dt_send = cycle_ps

    # ------------------------------------------------------ SYNC OPS
    is_bar = op == EventOp.BARRIER_WAIT
    is_lock = op == EventOp.MUTEX_LOCK
    is_unlock = op == EventOp.MUTEX_UNLOCK
    to_mcp_ps = noc.unicast_ps(
        params.net_user, rows, jnp.full((T,), mcp), 8, p_nu,
        params.mesh_width, vnet=vp.net_user)
    NEG = jnp.int64(-(2**62))
    # barrier arrival bookkeeping (server side of SimBarrier)
    bar_id = jnp.clip(arg, 0, num_bars - 1)
    bar_oh = dense.onehot(bar_id, num_bars)
    bar_count = st.bar_count + dense.binsum(
        bar_oh, is_bar, 1).astype(st.bar_count.dtype)
    bar_time = jnp.maximum(st.bar_time, dense.binmax(
        bar_oh, is_bar, clk + to_mcp_ps, NEG))
    # unlock: release the mutex at MCP-arrival time; requester pays the
    # round trip (SyncClient blocks on the ack, sync_client.h:10-30).
    # COND_WAIT releases its held mutex the same way (SimCond::wait
    # calls unlock, sync_server.cc:73) — its lock id is in arg2.
    is_cwait = op == EventOp.COND_WAIT
    is_csig = op == EventOp.COND_SIGNAL
    is_cbc = op == EventOp.COND_BROADCAST
    is_join = op == EventOp.JOIN
    is_tstart = op == EventOp.THREAD_START
    release = is_unlock | is_cwait
    lock_id = jnp.clip(jnp.where(is_cwait, arg2, arg), 0, num_locks - 1)
    ul_oh = dense.onehot(lock_id, num_locks) & release[:, None]
    lock_holder = jnp.where(ul_oh.any(axis=0), 0, st.lock_holder)
    lock_free_at = jnp.maximum(st.lock_free_at, dense.binmax(
        ul_oh, release, clk + to_mcp_ps + cycle_ps, NEG))
    dt_unlock = 2 * to_mcp_ps + 2 * cycle_ps

    # cond signal/broadcast: the poster PARKS as the token itself
    # (PEND_CSIG/PEND_CBC with its MCP-arrival timestamp); resolve_cond
    # matches tokens to waiters in exact time order and acks the
    # poster with a timestamp-based completion (SimCond::signal/
    # broadcast, sync_server.cc:76-119).

    # spawn: start the child's stream once the spawn request lands on
    # its tile (ThreadManager::spawnThread -> masterSpawnThread path).
    # ``child`` is a STREAM id; placement is child % T (scheduler's
    # static round-robin; identity when streams == tiles).
    is_spawn = op == EventOp.SPAWN
    S_ids = st.spawned_at.shape[0]
    child = jnp.clip(arg2, 0, S_ids - 1)
    spawn_land = clk + _lat(jnp.maximum(arg, 0), p_core) \
        + noc.unicast_ps(params.net_user, rows, child % T, 8, p_nu,
                         params.mesh_width, vnet=vp.net_user)
    spawned_at = st.spawned_at.at[
        jnp.where(is_spawn, child, S_ids)].max(spawn_land, mode="drop")

    # ------------------------------------------------ SIMPLE/DYNAMIC OPS
    is_stall = op == EventOp.STALL
    is_sync = op == EventOp.SYNC
    is_dvfs = op == EventOp.DVFS_SET
    is_done = op == EventOp.DONE
    # YIELD: MCP round trip to the ThreadScheduler
    # (ThreadScheduler::yieldThread netSends a request and waits for the
    # reply, thread_scheduler.cc:645-668); the rotation itself happens at
    # the next quantum boundary (schedule_rotate).  With one stream per
    # tile there is nothing to rotate to and the event is cost-only.
    is_yield = op == EventOp.YIELD
    dt_spawn = _lat(jnp.maximum(arg, 0), p_core)
    dt_dvfs = _lat(vp.dvfs_sync_delay_cycles, p_core)

    # SYSCALL: marshalled args ride the user network to the MCP's syscall
    # server, service takes the per-class cost, the result rides back
    # (SyscallMdl round trip, syscall_model.cc; server dispatch
    # syscall_server.cc:43-130).  Closed-form — no cross-tile dependency,
    # so no park.  Futexes never reach here (they surface as sync events).
    is_sysc = op == EventOp.SYSCALL
    svc_tbl = vp.syscall_cost_cycles
    svc_ps = _lat(svc_tbl[jnp.clip(arg, 0, len(params.syscall_cost_cycles)
                                   - 1)], p_core)
    sys_req_ps = noc.unicast_ps(
        params.net_user, rows, jnp.full((T,), mcp),
        jnp.maximum(arg2, 0), p_nu, params.mesh_width, vnet=vp.net_user)
    dt_sysc = sys_req_ps + svc_ps + to_mcp_ps + cycle_ps
    nmod = state.period_ps.shape[1]
    mod_oh = is_dvfs[:, None] & dense.onehot(
        jnp.clip(arg, 0, nmod - 1), nmod)
    # arg2 carries the new frequency in MHz (schema dvfs_set);
    # period_ps = round(1e6 / MHz).
    mhz = jnp.maximum(arg2, 1)
    new_period = ((1_000_000 + mhz // 2) // mhz).astype(jnp.int32)
    period_ps = jnp.where(mod_oh, new_period[:, None], st.period_ps)

    # ------------------------------------------------------ combine dt
    dt = jnp.zeros(T, dtype=jnp.int64)
    dt = jnp.where(comp_ok & en, dt_comp, dt)
    dt = jnp.where(is_br & en, dt_br, dt)
    dt = jnp.where(mem_l1, dt_mem_l1, dt)
    dt = jnp.where(mem_l2, dt_mem_l2, dt)
    dt = jnp.where(is_send, dt_send, dt)
    dt = jnp.where(is_unlock, dt_unlock, dt)
    dt = jnp.where(is_spawn, dt_spawn, dt)
    dt = jnp.where(is_dvfs, dt_dvfs, dt)
    # ROI-gated like compute/memory: with models off a syscall still
    # executes functionally but charges no simulated time.
    dt = jnp.where(is_sysc & en, dt_sysc, dt)
    dt = jnp.where(is_yield & en, 2 * to_mcp_ps + cycle_ps, dt)

    new_clock = clk + dt
    new_clock = jnp.where(
        is_stall, jnp.maximum(clk, addr), new_clock)
    new_clock = jnp.where(
        is_sync,
        jnp.maximum(clk, addr) + _lat(jnp.maximum(arg, 0), p_core),
        new_clock)

    # ------------------------------------------------- blocking events
    # With miss chaining, memory misses BANK as chain element 0 instead of
    # parking (the tile runs on with the line installed; resolve prices
    # the chain) — so PEND_SH/EX/IFETCH parks never occur when P > 0 and
    # the resolve pass compiles without the park machinery.
    P = params.miss_chain
    bank = (mem_rem | comp_block) if P > 0 \
        else jnp.zeros_like(mem_rem)
    blocked = ((comp_block | mem_rem) & ~bank) | is_recv | is_bar \
        | is_lock | send_block | is_cwait | is_csig | is_cbc | is_join \
        | is_tstart
    kind = jnp.where(comp_block, PEND_IFETCH, PEND_NONE)
    kind = jnp.where(mem_rem & is_rd, PEND_SH_REQ, kind)
    kind = jnp.where(mem_rem & is_wr, PEND_EX_REQ, kind)
    kind = jnp.where(is_recv, PEND_RECV, kind)
    kind = jnp.where(is_bar, PEND_BARRIER, kind)
    kind = jnp.where(is_lock, PEND_MUTEX, kind)
    kind = jnp.where(send_block, PEND_SEND, kind)
    kind = jnp.where(is_cwait, PEND_COND, kind)
    kind = jnp.where(is_csig, PEND_CSIG, kind)
    kind = jnp.where(is_cbc, PEND_CBC, kind)
    kind = jnp.where(is_join, PEND_JOIN, kind)
    kind = jnp.where(is_tstart, PEND_START, kind)
    pend_kind = jnp.where(blocked, kind, st.pend_kind)
    pend_addr = jnp.where(
        is_bar | is_lock | is_cwait | is_csig | is_cbc, jnp.int64(arg),
        jnp.where(send_block, jnp.int64(jnp.maximum(arg, 0)),
                  jnp.where(blocked, addr, st.pend_addr)))
    # Request-issue point: after the local tag checks that discovered
    # the miss (L1 only under shared L2 — there is no private L2 tag
    # array to consult before going to the home slice).
    miss_tags_ps = cycle_ps if shared_l2 else l2_tag_ps
    issue = clk + jnp.where(
        comp_block, l1i_ps + miss_tags_ps,
        jnp.where(mem_rem, l1d_ps + miss_tags_ps, cycle_ps))
    # Cond waits AND signal/broadcast tokens park with their MCP
    # arrival time (eligibility compares at the server, SimCond's
    # timestamps); THREAD_START parks at the local clock.
    issue = jnp.where(is_cwait | is_csig | is_cbc,
                      clk + to_mcp_ps, issue)
    issue = jnp.where(is_tstart, clk, issue)
    pend_issue = jnp.where(blocked, issue, st.pend_issue)
    # For memory requests pend_aux carries the atomic flag (resolve
    # needs it: iocoom lets plain loads/stores complete out-of-order
    # but atomics wait their full round trip) plus, for scoreboarded
    # loads, the destination register + 1 in bits 8-12 (resolve lands
    # the unpark time there — reference executeLoad feeding
    # _register_scoreboard via write_operands_ready).
    mdreg = jnp.where(is_rd, (arg2 >> 8) & 31, 0)   # dest reg + 1
    pend_aux = jnp.where(blocked,
                         jnp.where(mem_rem,
                                   is_at.astype(jnp.int32) | (mdreg << 8),
                                   arg2),
                         st.pend_aux)
    # Local cost still owed once the remote part resolves: a blocked
    # COMPUTE block's execution + fetch time (minus the remotely
    # fetched first line, which resolve prices; under shared L2 the
    # later lines' fetch rides the same slice round trip), an atomic's
    # RMW cycle.
    extra = jnp.where(
        comp_block,
        cost_ps + fetch_ps
        + (0 if shared_l2 else (n_lines - 1) * l2_ps),
        jnp.where(mem_rem, at_extra, 0))
    pend_extra = jnp.where(blocked, extra, st.pend_extra)

    # ---- bank the miss as chain element 0 (P > 0; the complex slot only
    # runs on an empty chain, so slot 0 is free).  No local install —
    # the resolve pass fills the line at serve time (blocking
    # semantics), same as the window path.
    if P > 0:
        kind_ev = jnp.where(comp_block, PEND_IFETCH,
                            jnp.where(is_wr, PEND_EX_REQ,
                                      PEND_SH_REQ)).astype(jnp.int64)
        mq_req0 = kind_ev | (is_at.astype(jnp.int64) << 3) | (line << 8)
        mq_delta0 = issue          # element 0: absolute issue time
        mq_extra0 = extra
        mq_count = jnp.where(bank, 1, st.mq_count)
        chain_rel = jnp.where(bank, 0, st.chain_rel)
    else:
        mq_count = st.mq_count
        chain_rel = st.chain_rel

    # ------------------------------------------------- cache updates
    l1i = cachemod.touch(st.l1i, pI.set_idx, pI.way, is_comp & pI.hit & en,
                         _row_word(pI.row, pI.way), stamp)
    if shared_l2:
        l2 = st.l2
        d_word = _row_word(pD.row, pD.way)
        if mesi_local:
            # Silent E->M upgrade on a store hit to an E-granted line.
            d_word = cachemod.with_state(
                d_word, jnp.where(mem_l1 & is_wr & (pD.state == E),
                                  M, pD.state))
        l1d = cachemod.touch(st.l1d, pD.set_idx, pD.way, mem_l1,
                             d_word, stamp)
    else:
        fI = cachemod.fill(l1i, line, jnp.full(T, S, dtype=jnp.int32),
                           comp_l2path, params.l1i.num_sets,
                           params.l1i.replacement, stamp)
        l1i = fI.cache
        l2 = cachemod.touch(st.l2, pL2.set_idx, pL2.way,
                            (comp_l2path | mem_l2),
                            _row_word(pL2.row, pL2.way), stamp)

        l1d = cachemod.touch(st.l1d, pD.set_idx, pD.way, mem_l1,
                             _row_word(pD.row, pD.way), stamp)
        # L1D fill from a local L2 hit; dirty L1 victims fold into the
        # (inclusive) L2 copy, which already holds M state — timing-only.
        fD = cachemod.fill(l1d, line,
                           jnp.where(is_wr, M, S).astype(jnp.int32),
                           mem_l2, params.l1d.num_sets,
                           params.l1d.replacement, stamp)
        l1d = fD.cache

    # ------------------------------------------------------- counters
    # (all gated on the ROI flag: outside it nothing accumulates)
    def add(x, mask, val=1):
        return x + jnp.where(mask & en, jnp.int64(val), 0)

    c = c._replace(
        icount=c.icount
        + jnp.where(is_comp & en, icount_ev, 0)
        + jnp.where(((is_mem & ((arg2 & 0xFF) == 0)) | is_br) & en, 1, 0),
        l1i_access=c.l1i_access + jnp.where(is_comp & en, icount_ev, 0)
        + jnp.where(is_br & en, 1, 0),
        l1i_miss=c.l1i_miss + jnp.where(is_comp & ~pI.hit & active & en,
                                        n_lines, 0),
        l1d_read=add(c.l1d_read, is_rd),
        l1d_read_miss=add(c.l1d_read_miss, is_rd & ~l1_ok),
        l1d_write=add(c.l1d_write, is_wr),
        l1d_write_miss=add(c.l1d_write_miss, is_wr & ~l1_ok),
        # Under shared L2 the slice accesses are counted at the home
        # tile by the resolve phase, not locally.
        l2_access=c.l2_access if shared_l2 else add(
            c.l2_access, mem_l2 | mem_rem | comp_l2path | comp_block),
        l2_miss=c.l2_miss if shared_l2 else add(
            c.l2_miss, mem_rem | comp_block),
        branches=add(c.branches, is_br),
        mispredicts=add(c.mispredicts, is_br & ~correct),
        net_user_pkts=add(c.net_user_pkts, is_send),
        net_user_flits=c.net_user_flits + jnp.where(
            is_send & en,
            noc.num_flits(jnp.maximum(arg, 0),
                          vp.net_user.flit_width_bits), 0),
        sends=add(c.sends, is_send),
        barriers=add(c.barriers, is_bar),
        cond_waits=add(c.cond_waits, is_cwait),
        cond_signals=add(c.cond_signals, is_csig | is_cbc),
        spawns=add(c.spawns, is_spawn),
        syscalls=add(c.syscalls, is_sysc),
        syscall_ps=c.syscall_ps + jnp.where(is_sysc & en, dt_sysc, 0),
    )

    if st.sched_enabled:
        done_at = st.done_at.at[
            jnp.where(is_done, srow, S_ids)].set(clk, mode="drop")
        st = st._replace(seat_yield=st.seat_yield | is_yield)
    else:
        done_at = jnp.where(is_done, clk, st.done_at)

    # Scoreboard writes (iocoom): a register-writing COMPUTE lands its
    # completion; a HITTING load lands the load completion (missing
    # loads land via resolve, carried in pend_aux bits 8-12).  Reference:
    # iocoom_core_model.cc:188-199 (_register_scoreboard[reg] =
    # write_operands_ready).
    if params.core.model == "iocoom":
        NREG = st.reg_ready.shape[0]
        dregc = (arg2 >> 25) & 31
        wreg = jnp.where(is_comp & (dregc > 0), dregc,
                         jnp.where((mem_l1 | mem_l2) & is_rd
                                   & (mdreg > 0), mdreg, 0))
        sb_write = (wreg > 0) & active
        if params.core.mixed:
            sb_write = sb_write & jnp.asarray(params.core.iocoom_mask)
        st = st._replace(reg_ready=st.reg_ready.at[
            jnp.where(sb_write, wreg - 1, NREG),
            rows].max(new_clock, mode="drop"))
    st = st._replace(
        clock=new_clock,
        cursor=st.cursor + jnp.where(active & ~blocked, 1, 0),
        done=st.done | is_done,
        done_at=done_at,
        spawned_at=spawned_at,
        models_enabled=models_enabled,
        pend_kind=pend_kind,
        pend_addr=pend_addr,
        pend_issue=pend_issue,
        pend_aux=pend_aux,
        pend_extra=pend_extra,
        bp_table=bp_table,
        l1i=l1i, l1d=l1d, l2=l2,
        period_ps=period_ps,
        lock_holder=lock_holder,
        lock_free_at=lock_free_at,
        bar_count=bar_count,
        bar_time=bar_time,
        ch_sent=ch_sent,
        ch_time=ch_time,
        round_ctr=st.round_ctr + 1,
        ctr_complex=st.ctr_complex + 1,
        counters=c,
        # VMManager accounting (reference vm_manager.cc; engine/vm.py):
        # mmap/munmap lengths and the requested break ride the SYSCALL
        # event's addr field.  Functional, so not ROI-gated — the
        # reference executes memory-management syscalls regardless.
        vm_mmap_bytes=st.vm_mmap_bytes + jnp.sum(jnp.where(
            is_sysc & (arg == int(SyscallClass.MMAP)), addr, 0)),
        vm_munmap_bytes=st.vm_munmap_bytes + jnp.sum(jnp.where(
            is_sysc & (arg == int(SyscallClass.MUNMAP)), addr, 0)),
        vm_brk=jnp.maximum(st.vm_brk, jnp.max(jnp.where(
            is_sysc & (arg == int(SyscallClass.BRK)), addr, 0))),
    )
    if P > 0:
        st = st._replace(
            mq_req=st.mq_req.at[0].set(
                jnp.where(bank, mq_req0, st.mq_req[0])),
            mq_delta=st.mq_delta.at[0].set(
                jnp.where(bank, mq_delta0, st.mq_delta[0])),
            mq_extra=st.mq_extra.at[0].set(
                jnp.where(bank, mq_extra0, st.mq_extra[0])),
            mq_count=mq_count,
            chain_rel=chain_rel,
        )
    return st


@jax.named_scope(scopes.COMPLEX_SLOT)
def _complex_slot_guarded(params: SimParams, vp: VariantParams,
                          state: SimState,
                          trace: TraceArrays) -> SimState:
    """Run the general slot only when some tile can use it (P > 0): a
    mid-chain tile waits for resolve, so on miss-dominated stretches the
    slot's gathers/scatters — a whole engine round — would execute as a
    pure no-op between every pair of banking rounds.  The guard is the
    slot's own active mask, so skipping is result-identical; at P == 0
    the slot runs unconditionally (bit-identity with the seed engine)."""
    if params.miss_chain <= 0:
        return _complex_slot(params, vp, state, trace)
    N = trace.num_events
    gbound = _spanned_bound(params, vp, state.boundary)
    eligible = (~state.done) & (state.pend_kind == PEND_NONE) \
        & (state.clock < gbound) & (state.cursor < N) \
        & (state.mq_count == 0)
    # The window phase retires (or banks) every simple-class event, so
    # the general slot is needed only when an ELIGIBLE tile's next event
    # is one the window never takes: sync/thread/network/lifecycle ops,
    # atomics, syscalls, DVFS, DONE, ROI flips — or when models are off
    # (the window retires nothing then) or under iocoom (annotated
    # events decline the window).  One [T] op gather decides; skipping
    # saves a whole engine round between every pair of banking rounds
    # on miss-dominated stretches.  With the window phase DISABLED
    # (block_events = 0) the general slot is the only executor, so the
    # op-class refinement must not apply (it would deadlock every
    # simple-class event).
    if params.core.model != "iocoom" and params.block_events > 0:
        cur = jnp.minimum(state.cursor, N - 1)
        srow = state.seat_stream if state.sched_enabled \
            else jnp.arange(params.num_tiles)
        op = trace.meta[0, srow, trace.local_cols(cur, rows=srow)]
        window_class = ((op == EventOp.COMPUTE) | (op == EventOp.BRANCH)
                        | (op == EventOp.MEM_READ)
                        | (op == EventOp.MEM_WRITE)
                        | (op == EventOp.STALL) | (op == EventOp.SYNC)
                        | (op == EventOp.SPAWN))
        eligible = eligible & (~window_class | ~state.models_enabled)
    return jax.lax.cond(
        eligible.any(),
        lambda s: _complex_slot(params, vp, s, trace), lambda s: s, state)


def local_advance(params: SimParams, state: SimState,
                  trace: TraceArrays,
                  vp: VariantParams = None) -> SimState:
    """Advance every non-blocked tile through events until the quantum
    boundary, stream end, or its first remote-blocking event.  Each loop
    round is a block retirement (a [T, K] window of simple events +
    banked misses) plus one general slot; the loop exits as soon as a
    round retires nothing anywhere (every tile parked / done / at its
    boundary / waiting on its miss chain).

    Progress sums are hoisted into the loop carries (one cursor-sum
    reduction per round, computed in the body; conds compare scalars) —
    the old cond/body pairs each re-swept the [T] cursor array, doubling
    the reduction count on the engine's innermost loops.

    Chain cadence (P > 0): just enough window rounds to fill the bank
    (one for a wide window, a few for a narrow one) + one (guarded)
    general slot per call — banking interleaves with serving at
    sub-round granularity instead of filling whole chains first.  Tiles
    bank ~a chain of misses, the very next resolve pass replays them,
    and the window resumes against post-serve state; nobody sits
    full-chain-stalled while a straggler keeps the local loop alive
    (the round-7 profile: that wait was most of the window-round
    count), and the run-ahead staleness window shrinks to one
    sub-round.  The sub-round loop in quantum_step supplies the
    iteration that the local loop supplies at P == 0.

    ``vp`` threads the VARIANT timing operands (engine/vparams.py);
    omitted, it derives from ``params`` and traces as constants —
    callers outside the sweep engine need not change."""
    if vp is None:
        vp = variant_params(params)
    # Round-12 adaptive fidelity: try the analytic fast-forward FIRST
    # each sub-round — run-ahead spans (the ``fast_forward_span``
    # budget) are priced in closed form and the detailed machinery
    # below resumes at the first disqualifying event.  Statically
    # absent at fast_forward = 0.
    if params.fast_forward > 0:
        state = _fast_forward_guarded(params, vp, state, trace)
    # Wide fast-forward WINDOW rounds: at fast_forward > 0 every window
    # round below runs the unchanged walk over an ``_ff_width`` slice
    # instead of [T, K] — one round retires the hit run AND banks the
    # misses that several narrow rounds would have, so sub-rounds drain
    # more events per resolve pass and the round count drops (the
    # acceptance multiplier).  Static per compile; disabled under
    # iocoom and the ThreadScheduler exactly like the analytic leg.
    wide = _ff_width(params)
    if wide <= params.block_events or params.core.model == "iocoom" \
            or state.sched_enabled:
        wide = None
    if params.miss_chain > 0:
        if params.block_events > 0:
            # Enough window rounds per sub-round to fill the chain bank
            # at the miss-dominated worst case (~2 local events per
            # bankable miss), capped small so serves stay fresh; the
            # loop still exits the moment a round retires nothing, and
            # is skipped OUTRIGHT when no tile can possibly retire (all
            # candidates chain-full or past the quantum boundary — the
            # window's own in_b gate would mask every event, so the
            # skip is result-identical and saves the probe round).
            K = params.block_events if wide is None else wide
            cap_w = max(1, -(-params.miss_chain * 3 // (2 * K)))
            N = trace.num_events
            qps = vp.quantum_ps

            def wprog(st):
                return jnp.sum(st.cursor.astype(jnp.int64))

            def _can_retire(st):
                # A tile can use another window round iff it is live,
                # un-parked, not at stream end, and either mid-chain
                # with bank room + overrun credit left, or empty-chain
                # inside the (possibly spanned) boundary.  Elementwise
                # [T] — far cheaper than the probe round it replaces.
                mid_ = st.mq_count > 0
                wb_ = _spanned_bound(params, vp, st.boundary)
                return (~st.done) & (st.pend_kind == PEND_NONE) \
                    & (st.cursor < N) \
                    & jnp.where(mid_,
                                (st.chain_rel < qps)
                                & (st.mq_count < params.miss_chain),
                                st.clock < wb_)

            # Round-9 adaptive skip (fanout_replay): the carried
            # (progress, anyone-can-still-retire) pair ends the
            # scheduled window rounds the moment every active tile is
            # mid-chain and saturated — the round-8 loop burned a whole
            # probe round to discover the same thing.  With the replay
            # off, ``more`` is pinned True and the loop is the round-8
            # progress-only form, bit-exactly.
            if params.fanout_replay:
                def wmore(s):
                    return _can_retire(s).any()
            else:
                def wmore(s):
                    return jnp.asarray(True)

            def wcond(c):
                j, pv, cv, more, _s = c
                return (j < cap_w) & ((j == 0) | ((cv > pv) & more))

            def wbody(c):
                j, _pv, cv, _more, s = c
                s = _block_retire(params, vp, s, trace, width=wide)
                return j + 1, cv, wprog(s), wmore(s), s

            def wloop(st):
                _, _, _, _, out = jax.lax.while_loop(
                    wcond, wbody,
                    (jnp.int32(0), jnp.int64(-1), wprog(st),
                     jnp.asarray(True), st))
                return out

            can_retire = _can_retire(state)
            state = jax.lax.cond(can_retire.any(), wloop,
                                 lambda s: s, state)
        return _complex_slot_guarded(params, vp, state, trace)

    def progress(st):
        return jnp.sum(st.cursor.astype(jnp.int64))

    def cond(carry):
        i, prev, cur, _st = carry
        return (i < params.max_events_per_quantum) \
            & ((i == 0) | (cur > prev))

    def body(carry):
        i, _prev, cur, st = carry
        if params.block_events > 0:
            # Inner window-only loop: the general slot costs as much as a
            # whole window but usually has nothing to do — run windows
            # until they stop retiring, THEN one general slot, repeat.
            # The carried ``cur`` is the cursor sum at body entry, so it
            # seeds the inner carry for free.
            def wcond(c):
                j, pv, cv, _s = c
                return (j < params.max_events_per_quantum) \
                    & ((j == 0) | (cv > pv))

            def wbody(c):
                j, _pv, cv, s = c
                s = _block_retire(params, vp, s, trace, width=wide)
                return j + 1, cv, progress(s), s

            _, _, _, st = jax.lax.while_loop(
                wcond, wbody, (jnp.int32(0), jnp.int64(-1), cur, st))
        st = _complex_slot(params, vp, st, trace)
        return i + 1, cur, progress(st), st

    _, _, _, state = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), jnp.int64(-1), progress(state), state))
    return state
