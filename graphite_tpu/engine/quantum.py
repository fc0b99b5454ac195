"""The quantum engine: lax-barrier-synchronized stepping of all tiles.

The reference bounds target-time skew with the lax-barrier scheme: every
tile that crosses the current quantum boundary blocks at a barrier server
on the MCP until all running tiles arrive, then the boundary advances one
quantum (skipping empty quanta) and everyone releases (reference:
clock_skew_management_schemes/lax_barrier_sync_server.cc:42-160, client
:32-59; SURVEY.md 3.5).

Here the same contract is a reduction: the boundary is recomputed from the
min clock over runnable tiles (a `jnp.min` — under a sharded mesh this is
the `lax.psum`-family collective the north star names), and a quantum step
is ``rounds_per_quantum`` repetitions of (local_advance ; resolve).  Tiles
parked on sync objects (barrier/mutex/recv) are excluded from the min —
the reference likewise excludes sleeping/stalled threads from
isBarrierReached (lax_barrier_sync_server.cc:88-115) — so producers can
run ahead and release them.

``lax`` (no sync) and ``lax_p2p`` (random-pair clamping) map onto the same
engine: the quantum already bounds skew at least as tightly as either, so
they differ only in the modeled sync *cost*, which is zero for all three
(the reference charges no time for barrier waits either — wait time is
simply simulated-time made equal across tiles).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from graphite_tpu.engine.core import local_advance
from graphite_tpu.engine.resolve import resolve
from graphite_tpu.engine.state import (
    PEND_BARRIER, PEND_CBC, PEND_COND, PEND_CSIG, PEND_EX_REQ, PEND_IFETCH,
    PEND_JOIN, PEND_MUTEX, PEND_RECV, PEND_SEND, PEND_SH_REQ, PEND_START,
    SimState, TraceArrays, sampling_enabled, stats_ring_enabled)
from graphite_tpu.engine.vparams import VariantParams, variant_params
from graphite_tpu.obs import scopes
from graphite_tpu.params import SimParams
from graphite_tpu.time_base import TIME_MAX


def next_boundary(params: SimParams, state: SimState,
                  vp: VariantParams = None) -> jnp.ndarray:
    """Advance the barrier boundary past the slowest runnable tile,
    skipping empty quanta (reference barrierRelease's quantum skip,
    lax_barrier_sync_server.cc:118-160)."""
    sync_blocked = ((state.pend_kind == PEND_RECV)
                    | (state.pend_kind == PEND_BARRIER)
                    | (state.pend_kind == PEND_MUTEX)
                    | (state.pend_kind == PEND_SEND)
                    | (state.pend_kind == PEND_COND)
                    | (state.pend_kind == PEND_CSIG)
                    | (state.pend_kind == PEND_CBC)
                    | (state.pend_kind == PEND_JOIN)
                    | (state.pend_kind == PEND_START))
    runnable = ~state.done & ~sync_blocked
    clk = state.clock
    if params.miss_chain > 0 and params.fanout_replay:
        # A mid-chain tile's seat clock is FROZEN at its pre-bank value
        # until the drain restores it; its served progress lives in
        # chain_base (the last served element's completion).  Taking the
        # frozen clock pinned the barrier a whole chain-service span
        # behind the machine's real time — empty-ish quanta whose rounds
        # the budget pays for.  chain_base is a sound lower bound on the
        # tile's post-drain clock, so the boundary may advance past it
        # (round 9; off with fanout_replay=0 — the round-8 cadence).
        clk = jnp.where(state.mq_head > 0,
                        jnp.maximum(clk, state.chain_base), clk)
    masked = jnp.where(runnable, clk, TIME_MAX)
    if params.tile_shards > 1:
        # Sharded quantum barrier (round 11): each shard reduces its own
        # T/S tile slice, then a pmin over the mesh axis produces the
        # global minimum — the explicit-collective form of the barrier
        # server, exactly equal to the full-T min (integer clocks, and
        # the shard slices partition the tile axis).
        from graphite_tpu.parallel.mesh import TILE_AXIS, pmin_i64
        TL = masked.shape[0] // params.tile_shards
        i = jax.lax.axis_index(TILE_AXIS)
        local = jnp.min(jax.lax.dynamic_slice_in_dim(masked, i * TL, TL, 0))
        min_clock = pmin_i64(local)
    else:
        min_clock = jnp.min(masked)
    q = vp.quantum_ps if vp is not None else jnp.int64(params.quantum_ps)
    nb = (min_clock // q + 1) * q
    return jnp.where(runnable.any(), nb,
                     state.boundary + q).astype(jnp.int64)


def _tel_gauges(st: SimState) -> jnp.ndarray:
    """Engine-health gauge rows (order: obs/metrics.TEL_SERIES) — the
    simulator's own vitals, sampled beside the simulated machine's
    statistics so every run doubles as a profile (PROFILE.md's
    hand-differenced rounds/occupancy numbers, computed in-engine)."""
    k = st.pend_kind
    alive = ~st.done
    mem = ((k == PEND_SH_REQ) | (k == PEND_EX_REQ)
           | (k == PEND_IFETCH)) & alive
    sync = ((k == PEND_BARRIER) | (k == PEND_MUTEX) | (k == PEND_COND)
            | (k == PEND_CSIG) | (k == PEND_CBC) | (k == PEND_JOIN)
            | (k == PEND_START)) & alive
    msg = ((k == PEND_SEND) | (k == PEND_RECV)) & alive
    live_clock = jnp.where(alive, st.clock, TIME_MAX)
    any_alive = alive.any()
    # Under the ThreadScheduler the seat arrays hold only the running
    # subset; cumulative series must fold in the stream store (seat
    # values patched over it, as in all_done) or a rotation would make
    # them non-monotone.
    if st.sched_enabled:
        cursor_all = st.strm_cursor.at[st.seat_stream].set(st.cursor)
        done_all = st.strm_done.at[st.seat_stream].set(st.done)
    else:
        cursor_all, done_all = st.cursor, st.done
    return jnp.stack([
        jnp.sum(cursor_all.astype(jnp.int64)),
        jnp.sum(st.counters.icount),
        jnp.sum(done_all, dtype=jnp.int64),
        jnp.sum(mem, dtype=jnp.int64),
        jnp.sum(sync, dtype=jnp.int64),
        jnp.sum(msg, dtype=jnp.int64),
        st.ctr_quantum,
        st.ctr_window,
        st.ctr_complex,
        st.ctr_conflict,
        st.ctr_resolve,
        jnp.where(any_alive, jnp.min(live_clock), jnp.max(st.clock)),
        jnp.max(st.clock),
    ])


@jax.named_scope(scopes.SAMPLE)
def _maybe_sample(params: SimParams, state: SimState) -> SimState:
    """Record one statistics/progress sample when the quantum boundary
    crosses the sampling interval (the reference samples on barrier
    releases the same way — lax_barrier_sync_server.cc:157-159 notifying
    statistics_thread.cc; series list per statistics_manager.cc:41-114)."""
    from graphite_tpu.engine import cache as cachemod
    from graphite_tpu.engine.state import dir_live_entries
    S = state.stat_time.shape[0]
    interval = jnp.int64(params.stat_interval_ps)
    do = (state.boundary >= state.stat_next) & (state.stat_filled < S)

    def take(st: SimState) -> SimState:
        idx = jnp.minimum(st.stat_filled, S - 1)
        c = st.counters
        if stats_ring_enabled(params):
            if params.shared_l2:
                live = dir_live_entries(st.dir_word)
            else:
                live = jnp.sum(cachemod.meta_state(st.l2.meta) != 0,
                               dtype=jnp.int64)
            # cache_line_replication analog: total tracked sharer bits
            repl = jnp.sum(jnp.bitwise_count(st.dir_sharers),
                           dtype=jnp.int64)
            scalars = jnp.stack([
                jnp.sum(c.icount), jnp.sum(c.net_mem_flits),
                jnp.sum(c.net_user_flits), jnp.sum(c.dram_reads),
                jnp.sum(c.dram_writes), live, repl,
                jnp.sum(c.net_link_wait_ps),
                # Energy-bearing counters for the power trace
                # ([runtime_energy_modeling/power_trace]; energy.power_trace
                # diffs consecutive samples into per-interval watts).
                jnp.sum(c.l1i_access),
                jnp.sum(c.l1d_read) + jnp.sum(c.l1d_write),
                jnp.sum(c.l2_access), jnp.sum(c.branches),
                jnp.sum(c.dir_sh_req) + jnp.sum(c.dir_ex_req)
                + jnp.sum(c.dir_invalidations)])
            st = st._replace(
                stat_scalars=st.stat_scalars.at[:, idx].set(scalars))
        if params.telemetry_enabled:
            st = st._replace(
                tel_gauges=st.tel_gauges.at[:, idx].set(
                    _tel_gauges(st)),
                tel_cursor=st.tel_cursor.at[idx].set(st.cursor),
                tel_pend=st.tel_pend.at[idx].set(st.pend_kind))
        st = st._replace(
            stat_time=st.stat_time.at[idx].set(st.boundary),
            stat_filled=st.stat_filled + 1,
            stat_next=(st.boundary // interval + 1) * interval)
        if params.progress_enabled:
            st = st._replace(
                stat_icount=st.stat_icount.at[idx].set(c.icount))
        return st

    # lax.cond skips the metadata scans entirely on non-sampling quanta
    # (most of them, at typical interval >> quantum ratios).
    return jax.lax.cond(do, take, lambda st: st, state)


@jax.named_scope(scopes.SCHEDULE)
def schedule_rotate(params: SimParams, state: SimState,
                    vp: VariantParams = None) -> SimState:
    """ThreadScheduler seat rotation (reference: thread_scheduler.h:30-56,
    round_robin_thread_scheduler.cc; yield path thread_scheduler.cc:615-660).

    Streams are placed round-robin (strm_tile = s % num_tiles — the
    reference's default placement for uniform spawns; affinity/migration
    are not implemented and rejected nowhere since no event emits them).
    Each tile SEATS one stream; the engine's [T] context arrays are the
    seats.  A seat rotates to the tile's lowest-strm_key waiting stream
    when the seated stream (a) is done, (b) retired a YIELD, (c) parked
    on THREAD_START unspawned, or (d) held the seat past the preemption
    quantum — measured in simulated time (the reference uses host
    seconds, thread_scheduler.cc:632-636).  (d) also rotates streams
    parked on sync objects, so a lock holder queued behind its waiter
    eventually runs (round-robin => no starvation); a rotated-out park
    freezes until the stream is reseated, which skews sync wakeups by at
    most the rotation period — the scheduler's own artifact in the
    reference too.  Memory parks (SH/EX/IFETCH) never rotate: resolve
    serves them within a few rounds.
    """
    T = params.num_tiles
    S = state.strm_cursor.shape[0]
    sst = state.seat_stream                               # [T]
    tiles = jnp.arange(T, dtype=jnp.int32)
    strm_tile = (jnp.arange(S, dtype=jnp.int32) % T)      # static placement

    # Sync the stream store's bookkeeping for seated streams.
    strm_done = state.strm_done.at[sst].set(state.done)
    state = state._replace(strm_done=strm_done)

    k = state.pend_kind
    # Tiles mid-memory-transaction never rotate: parked requests
    # (SH/EX/IFETCH) resolve within a few rounds, and a non-empty miss
    # chain (mq_count > 0, tpu/miss_chain > 0) is tile-resident bank
    # state belonging to the seated stream — rotating under it would
    # drain the old stream's banked requests against the new stream's
    # clock.  EVERY sync park rotates freely (preemption must be able to
    # seat the peer a parked stream is waiting FOR — pinning any sync
    # park can deadlock a waiter queued on the same tile as its waker):
    # mutex/join/recv/send/start wakes are persistent state re-checked on
    # reseat; cond signal/broadcast tokens are durable parked entries
    # whose loss bound covers descheduled streams (resolve_cond lb);
    # barrier releases wake descheduled waiters directly in the stream
    # store (resolve_barrier).
    mem_park = ((k == PEND_SH_REQ) | (k == PEND_EX_REQ)
                | (k == PEND_IFETCH)) | (state.mq_count > 0)
    unspawned_gate = (k == PEND_START) \
        & (state.spawned_at[sst] < 0)
    switch_q = vp.thread_switch_quantum_ps if vp is not None \
        else jnp.int64(params.thread_switch_quantum_ps)
    expired = (state.boundary - state.seat_since) >= switch_q
    give_up = (state.done | state.seat_yield | unspawned_gate
               | expired) & ~mem_park

    # Waiting streams per tile (not seated, not done), FCFS by strm_key.
    seated = jnp.zeros(S, dtype=bool).at[sst].set(True)
    waiting = ~seated & ~strm_done
    BIG = jnp.int64(2**62)
    tbl = jnp.full((T,), BIG, jnp.int64).at[
        jnp.where(waiting, strm_tile, T)].min(state.strm_key, mode="drop")
    has_wait = tbl < BIG
    rotate = give_up & has_wait                           # [T]
    winner = waiting & (tbl[strm_tile] == state.strm_key) \
        & rotate[strm_tile]                               # [S]
    in_s = jnp.zeros(T, dtype=jnp.int32).at[
        jnp.where(winner, strm_tile, T)].max(
        jnp.arange(S, dtype=jnp.int32), mode="drop")      # [T]

    # Save the outgoing context into the store (rotating tiles only).
    out_s = jnp.where(rotate, sst, S)
    def save(store, seat_val):
        return store.at[out_s].set(seat_val, mode="drop")
    max_key = jnp.max(state.strm_key)
    state = state._replace(
        strm_cursor=save(state.strm_cursor, state.cursor),
        strm_clock=save(state.strm_clock, state.clock),
        strm_pend_kind=save(state.strm_pend_kind, state.pend_kind),
        strm_pend_addr=save(state.strm_pend_addr, state.pend_addr),
        strm_pend_issue=save(state.strm_pend_issue, state.pend_issue),
        strm_pend_aux=save(state.strm_pend_aux, state.pend_aux),
        strm_pend_extra=save(state.strm_pend_extra, state.pend_extra),
        strm_done=state.strm_done.at[out_s].set(state.done, mode="drop"),
        # Outgoing stream goes to the back of the queue: keys stay unique
        # because each rotating tile adds a distinct offset.
        strm_key=state.strm_key.at[out_s].set(
            max_key + 1 + tiles.astype(jnp.int64), mode="drop"),
    )
    # Load the incoming context; the core is serial, so the incoming
    # stream's clock can never precede the outgoing one's.
    def load(seat_val, store):
        return jnp.where(rotate, store[in_s], seat_val)
    state = state._replace(
        cursor=load(state.cursor, state.strm_cursor),
        clock=jnp.where(rotate,
                        jnp.maximum(state.strm_clock[in_s], state.clock),
                        state.clock),
        done=load(state.done, state.strm_done),
        pend_kind=load(state.pend_kind, state.strm_pend_kind),
        pend_addr=load(state.pend_addr, state.strm_pend_addr),
        pend_issue=load(state.pend_issue, state.strm_pend_issue),
        pend_aux=load(state.pend_aux, state.strm_pend_aux),
        pend_extra=load(state.pend_extra, state.strm_pend_extra),
        seat_stream=jnp.where(rotate, in_s, sst),
        seat_since=jnp.where(rotate, state.boundary, state.seat_since),
        seat_yield=jnp.where(rotate, False, state.seat_yield),
    )
    # A context switch restores the incoming thread's registers, so its
    # scoreboard starts all-ready — clearing the tile's reg_ready stops
    # the outgoing stream's pending register writes from imposing false
    # RAW stalls on the new stream (iocoom only; [0, T] otherwise).
    # Outstanding LQ/SQ completion times stay: they are absolute-time
    # hardware occupancy the new stream genuinely contends with.
    if state.reg_ready.shape[0] > 0:
        state = state._replace(
            reg_ready=jnp.where(rotate[None, :], 0, state.reg_ready))
    return state


def quantum_step(params: SimParams, state: SimState,
                 trace: TraceArrays,
                 vp: VariantParams = None) -> SimState:
    """One barrier quantum: all tiles advance to the new boundary.

    Sub-rounds of (local_advance ; resolve) repeat while they make
    progress (any event retired or unblocked — the cursor sum moves),
    capped at ``rounds_per_quantum``; quanta whose work drains in one
    sub-round (most of them) pay for one instead of the full cap.

    The progress reductions are HOISTED out of the loop predicate: each
    round computes its post-round sum once in the body and carries
    (prev, cur) as scalars, so the cond is pure scalar compares.  The
    old shape recomputed both full-[T] sums in cond AND body — four
    reduction sweeps per round where one suffices (PROFILE.md: the
    round is fixed-op bound at small T).

    ``vp`` threads the VARIANT timing operands (engine/vparams.py): the
    sweep engine passes a batched pytree under ``vmap``; omitted, it
    derives from ``params`` and traces as constants."""
    if vp is None:
        vp = variant_params(params)
    with jax.named_scope(scopes.BARRIER):
        state = state._replace(boundary=next_boundary(params, state, vp=vp),
                               ctr_quantum=state.ctr_quantum + 1)
    if state.sched_enabled:
        state = schedule_rotate(params, state, vp=vp)

    # Chain cadence (tpu/miss_chain > 0): local_advance is ONE window
    # round + a guarded general slot, so the sub-round loop here is what
    # alternates banking with resolve passes — its cap must admit a full
    # quantum's worth of window rounds, and the progress metric must see
    # mid-chain serves (they move neither cursor nor clock until the
    # chain drains; the memory-stall counter strictly increases on every
    # served element, so it is the monotone witness).
    P = params.miss_chain
    cap = params.rounds_per_quantum if P == 0 \
        else max(params.rounds_per_quantum, params.max_events_per_quantum)

    def progress(st):
        # cursor moves on any retire/bank/unblock; clock moves when a
        # resolve pass drains a miss chain without retiring new events.
        p = jnp.sum(st.cursor.astype(jnp.int64)) + jnp.sum(st.clock)
        if P > 0:
            p = p + jnp.sum(st.counters.mem_stall_ps)
        return p

    def cond(carry):
        i, prev, cur, _st = carry
        return (i < cap) & ((i == 0) | (cur > prev))

    def body(carry):
        i, _prev, cur, st = carry
        st = local_advance(params, st, trace, vp=vp)
        st = resolve(params, st, vp=vp)
        # cur (this round's entry progress) becomes the next compare
        # floor; one reduction pass per round, in the body where it
        # fuses with the round's epilogue.
        return i + 1, cur, progress(st), st

    ff0 = state.ctr_ff if params.fast_forward > 0 else None
    _, _, _, state = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), jnp.int64(-1), progress(state), state))
    if params.fast_forward > 0:
        # Fast-forwarded-quanta attribution (round 12): a quantum counts
        # once iff some sub-round committed an analytic span — the
        # bench's ff-quanta fraction is ctr_ffq / ctr_quantum.  No
        # boundary patch is needed here: committed spans advance
        # st.clock DIRECTLY (unlike chain serves, which park progress in
        # chain_base), so next_boundary's min-clock already leaps past
        # fast-forwarded progress.
        state = state._replace(
            ctr_ffq=state.ctr_ffq + (state.ctr_ff > ff0).astype(jnp.int64))
    if sampling_enabled(params):
        state = _maybe_sample(params, state)
    return state


def _megastep_impl(params: SimParams, state: SimState,
                   trace: TraceArrays) -> SimState:
    from graphite_tpu.parallel.mesh import shard_wrap
    vp = variant_params(params)

    def run(state, trace):
        def body(st, _):
            return quantum_step(params, st, trace, vp=vp), None

        st, _ = jax.lax.scan(body, state, None,
                             length=params.quanta_per_step)
        return st

    return shard_wrap(params.tile_shards, run, 2)(state, trace)


# State donation is OFF by default.  Chained donation (each window's
# output donated as the next window's input) races buffer lifetime on
# the CPU PJRT client: a long-lived final state can end up referencing
# storage the allocator hands to a LATER compiled program, which then
# scribbles over it — observed as garbage in pass-through leaves
# (period_ps) once more simulations ran in the same process.  The
# corruption reproduces on the pre-round-11 tree with sharding never
# touched, so it is the donation chain itself, not shard_map; it is
# also racy (allocation-order dependent), which is how it survived ten
# rounds of green tests.  GRAPHITE_DONATE_STATE=1 opts back into
# donation (halves peak state memory on HBM-bound runs) for runtimes
# where the chain is known safe; the sharded path never donates.
def state_donation_enabled() -> bool:
    import os
    return os.environ.get("GRAPHITE_DONATE_STATE", "") == "1"


_megastep_donate = partial(jax.jit, static_argnums=0,
                           donate_argnums=1)(_megastep_impl)
_megastep_nodonate = partial(jax.jit, static_argnums=0)(_megastep_impl)


def megastep(params: SimParams, state: SimState,
             trace: TraceArrays) -> SimState:
    """``quanta_per_step`` quantum steps fused into one device program —
    the unit the host driver launches (and the unit `bench.py` times).

    With ``tpu/tile_shards`` > 1 the whole step body runs under
    shard_map (parallel/mesh.shard_wrap): state and trace stay
    replicated, the window walk slices to per-shard tiles inside
    (kernels/window.run_window_sharded), and the quantum barrier is a
    pmin.  At 1 the wrapper is the identity — today's program."""
    if params.shard_state == "resident":
        raise ValueError("tpu/shard_state=resident runs through "
                         "engine/resident.megarun, not the replicated "
                         "quantum program")
    if params.tile_shards <= 1 and state_donation_enabled():
        return _megastep_donate(params, state, trace)
    return _megastep_nodonate(params, state, trace)


def megarun_loop(params: SimParams, vp: VariantParams, state: SimState,
                 trace: TraceArrays, max_quanta,
                 masked: bool = True) -> SimState:
    """The megarun while_loop body, vp-threaded and UNJITTED — shared by
    the serial ``megarun`` wrapper below (vp traces as constants) and the
    sweep engine's vmapped invocation (graphite_tpu/sweep/batch.py, vp a
    [V]-batched operand pytree).

    With ``masked`` the body commits a quantum_step's result only where
    the run was not already complete: under ``vmap`` the loop runs to
    the SLOWEST variant and the mask freezes finished lanes bit-exactly
    — their clocks, counters, and quantum counts stay what a solo run
    would have produced.  The serial wrapper passes ``masked=False``:
    its scalar cond already gates the body on ~done, so the mask could
    only ever select the new state — skipping it is result-identical
    and avoids a whole-SimState select per quantum (pass-through state
    copies are a measured per-round cost on TPU; see resolve()'s
    gating note).
    """
    start = state.ctr_quantum
    budget = jnp.asarray(max_quanta, jnp.int64)

    # The all_done reduction is carried: computed once per quantum at the
    # END of the body (where it fuses with the quantum's epilogue ops)
    # instead of re-sweeping the done/strm_done arrays in the cond — the
    # cond then reads two scalars.
    def cond(carry):
        st, done = carry
        return (~done) & ((st.ctr_quantum - start) < budget)

    def body(carry):
        st, done = carry
        new = quantum_step(params, st, trace, vp=vp)
        if masked:
            st = jax.tree_util.tree_map(
                lambda o, n: jnp.where(done, o, n), st, new)
        else:
            st = new
        return st, st.all_done()

    state, _ = jax.lax.while_loop(cond, body, (state, state.all_done()))
    return state


def _megarun_impl(params: SimParams, state: SimState, trace: TraceArrays,
                  max_quanta) -> SimState:
    from graphite_tpu.parallel.mesh import shard_wrap

    def run(state, trace, vp, mq):
        return megarun_loop(params, vp, state, trace, mq, masked=False)

    return shard_wrap(params.tile_shards, run, 4)(
        state, trace, variant_params(params), max_quanta)


_megarun_donate = partial(jax.jit, static_argnums=0,
                          donate_argnums=1)(_megarun_impl)
_megarun_nodonate = partial(jax.jit, static_argnums=0)(_megarun_impl)


def megarun(params: SimParams, state: SimState, trace: TraceArrays,
            max_quanta) -> SimState:
    """Run quantum steps ON DEVICE until the simulation completes or
    ``max_quanta`` quanta elapse — one host dispatch per polling window.

    ``megastep`` pays one host->device dispatch per ``quanta_per_step``
    quanta, and at small tile counts those dispatches — not device
    compute — dominated bench wall-clock (r5 profile).  The body here is
    the SAME ``quantum_step``, so timing semantics are bit-identical;
    the while_loop just moves the step loop and the done check across
    the dispatch boundary.  ``max_quanta`` is a TRACED scalar so every
    window size shares one compiled program (the warm-up run must warm
    the real program).

    Sharding rides the same wrapper as ``megastep``: with
    ``tpu/tile_shards`` > 1 the loop body (window slicing, the pmin
    barrier, the replicated resolve) runs under shard_map; at 1 the
    wrapper is the identity and this is today's program, bit for bit.
    State donation is opt-in and 1-only (see the note above
    ``state_donation_enabled``).
    """
    if params.shard_state == "resident":
        raise ValueError("tpu/shard_state=resident runs through "
                         "engine/resident.megarun, not the replicated "
                         "quantum program")
    if params.tile_shards <= 1 and state_donation_enabled():
        return _megarun_donate(params, state, trace, max_quanta)
    return _megarun_nodonate(params, state, trace, max_quanta)
