"""Cross-tile resolution of pending requests — the engine's "sim-thread side".

In the reference, anything one tile needs from another travels as modeled
packets serviced by per-tile sim threads and MCP server threads: shared-
memory requests walk L2 -> home DRAM-directory -> owner/sharers -> back
(reference: common/tile/memory_subsystem/pr_l1_pr_l2_dram_directory_msi/
dram_directory_cntlr.cc, call stack SURVEY.md 3.3); sync ops are served by
the MCP's SyncServer (common/system/sync_server.h); CAPI receives match
sends in Network::netRecv (common/network/network.cc:358).

Here, all of that is one batched phase per sub-round: every parked request
from every tile is priced and applied simultaneously with gathers/scatters
over the tile-sharded state.  Same-line races — which the reference
serializes through the home directory's FSM (with NULLIFY/retry) — are
serialized by *conflict rounds*: per round, only each line's earliest
pending request transacts; later requests observe the post-transaction
directory state in a later round and are charged the wait through a
per-line availability floor.  Requests left after
``directory_conflict_rounds`` rounds simply stay parked for the next
sub-round — bounded work per step, no starvation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from graphite_tpu.engine import cache as cachemod
from graphite_tpu.engine import dense
from graphite_tpu.engine import directory as dirmod
from graphite_tpu.engine import noc
from graphite_tpu.engine import noc_flight
from graphite_tpu.engine import queue_models
from graphite_tpu.engine.core import STAMP_STRIDE, _lat, _period, mcp_tile
from graphite_tpu.engine.kernels import chain as kchain
from graphite_tpu.engine.kernels import dispatch as kdispatch
from graphite_tpu.engine.state import (
    PEND_BARRIER, PEND_CBC, PEND_COND, PEND_CSIG, PEND_EX_REQ, PEND_IFETCH,
    PEND_JOIN, PEND_MUTEX, PEND_NONE, PEND_RECV, PEND_SEND, PEND_SH_REQ,
    PEND_START, SimState, dir_gather, dir_set, dword_owner, dword_pack,
    dword_stamp, dword_state, dword_tag, dword_with_meta, sharer_words,
    sharers_add, sharers_gather)
from graphite_tpu.engine.vparams import VariantParams, variant_params
from graphite_tpu.isa import DVFSModule
from graphite_tpu.obs import scopes
from graphite_tpu.params import SimParams

I, S, O, E, M = (cachemod.I, cachemod.S, cachemod.O, cachemod.E,
                 cachemod.M)

# Control-message payload bytes + per-target owner-delivery budget —
# shared with the chain classify kernel (round 10 moved the definitions
# to engine/kernels/chain.py; reference ShmemMsg header,
# shmem_msg.h:12-29).
CTRL_BYTES = kchain.CTRL_BYTES
J_OWN = kchain.J_OWN


# Line -> home-slot fold: ONE definition in dense.py (round 10 — the
# chain classify kernel's slice->controller legs use it too; streams
# still spread like the reference's low-bit interleaving,
# address_home_lookup.cc, but a plain ``line % n`` sends every
# power-of-two-strided per-tile region to ONE home — observed: 1024
# tiles, 98k deferrals, one 48 us DRAM horizon).
_home_fold = dense.home_fold


def home_of_line(params: SimParams, line: jnp.ndarray) -> jnp.ndarray:
    """Home tile serving a line's coherence requests.

    Private-L2 protocols: the memory-controller/directory tile — lines
    interleave across the controllers, controllers spread over the mesh
    with a fixed stride (reference: address_home_lookup.cc + [dram]
    controller placement).  Shared-L2 protocols: every tile hosts an L2
    slice, lines interleave across all of them (reference:
    pr_l1_sh_l2_msi/l2_cache_hash_fn.cc)."""
    if params.shared_l2:
        return _home_fold(line, params.num_tiles)
    return _home_fold(line, params.dram.num_controllers) \
        * params.dram.controller_home_stride


def dram_site_of_line(params: SimParams, line: jnp.ndarray) -> jnp.ndarray:
    """Memory-controller tile for a line (== home_of_line for private-L2
    protocols; under shared L2 the slice home and the DRAM controller can
    be different tiles, adding a slice->controller leg)."""
    return _home_fold(line, params.dram.num_controllers) \
        * params.dram.controller_home_stride


def dir_set_of_line(params: SimParams, line: jnp.ndarray) -> jnp.ndarray:
    """Directory/slice set within a home tile, XOR-folding the high line
    bits.

    A plain ``(line // nslices) % ndsets`` aliases power-of-two-strided
    allocations (e.g. per-tile buffers spaced nslices*ndsets lines apart)
    into the same set and thrashes an otherwise nearly-empty directory;
    folding the bits above the set index breaks such strides.  (The
    reference's directory cache hashes the address into its sets the same
    way generic caches do — directory_cache.cc getSetIndex.)
    """
    ndsets = params.directory.num_sets
    nslices = params.num_tiles if params.shared_l2 \
        else params.dram.num_controllers
    x = line // nslices
    bits = ndsets.bit_length() - 1
    x = x ^ (x >> bits) ^ (x >> (2 * bits)) ^ (x >> (3 * bits))
    return (x % ndsets).astype(jnp.int32)


_BIG = jnp.int64(2**62)

_oh = dense.onehot
_sel = dense.sel
_binsum = dense.binsum
_DENSE_MAX_ELEMS = dense.DENSE_MAX_ELEMS


# FCFS election helpers — moved to engine/dense.py (round 10) so the
# chain classify kernel (engine/kernels/chain.py) and these conflict
# rounds share ONE implementation; aliased here for the round loop.
_fcfs_keys = dense.fcfs_keys
_elect = dense.elect
_grouped_rank = dense.grouped_rank


def _unblock(state: SimState, mask, completion, sync: bool) -> SimState:
    c = state.counters
    stall = jnp.where(mask, completion - state.pend_issue, 0)
    if sync:
        c = c._replace(sync_stall_ps=c.sync_stall_ps + stall)
    else:
        c = c._replace(mem_stall_ps=c.mem_stall_ps + stall)
    return state._replace(
        clock=jnp.where(mask, completion, state.clock),
        cursor=state.cursor + jnp.where(mask, 1, 0),
        pend_kind=jnp.where(mask, PEND_NONE, state.pend_kind),
        counters=c,
    )


# ===================================================================== memory

def chain_fast_pass(params: SimParams, vp: VariantParams, state: SimState,
                    H: int, ftbl: jnp.ndarray):
    """Serve whole banked miss chains in ONE resolve pass with BLOCKING
    semantics — the round-7 throughput core (PROFILE.md lever 1).

    The conflict-round loop below serves one chain element per tile per
    round, so its round count equals the longest chain — ~one engine
    round per miss, the round-3 wall-clock floor.  This pass instead
    replays each tile's chain SEQUENTIALLY inside one engine round: a
    bounded ``lax.fori_loop`` of P iterations prices and applies every
    tile's CURRENT chain head together, so element k+1 probes the
    directory state element k (and the other tiles' already-served
    elements) wrote, and installs its line into the requester's caches
    at serve time — the same math, the same election tables, and the
    same scatters as one conflict round.  Rounds needed ~= misses /
    chain instead of misses.

    Cross-tile same-line requests serialize through the SLOT AXIS: when
    tile A banked line X at slot 3 and tile B at slot 7, iteration 3
    installs A's grant and iteration 7's probe finds it — B pays the
    owner flush / upgrade transition against A's entry plus X's
    serialization floor, exactly as two consecutive conflict rounds
    would price it (service order follows chain position rather than
    the two issue times; the floor keeps the timing serialized either
    way, and the 2% oracle gate bounds the residual inversion skew).
    Owner flush/downgrade legs are priced in-pass — the round loop's
    zero-load unicast math, with owner-side downgrades delivered
    through the same J_OWN-budgeted per-target line lists — because
    single-owner migratory sharing (every radix permute write)
    dominates contended miss traffic.

    Conflict fallback: a chain stops at its first element whose
    transition needs machinery this replay does not carry —
    invalidation fan-out (EX against multi-sharer entries), live
    directory-victim entries, per-owner delivery-budget overflow, or a
    same-iteration (home, dset, way)-election loss (which covers two
    tiles banking one line at the SAME slot index).  From that element
    on the chain stays banked for the exact one-element-per-round loop
    that follows, so fan-out traffic always goes through the same
    budgeted FCFS election the one-parked-request oracle applies —
    which is what makes this a fast path and not a different machine
    (the round-4 attempt installed lines optimistically at bank time
    and modeled a non-blocking MSHR core; the de-xfailed equality
    tests in tests/test_chain_equivalence.py are the gate).

    The serialization-floor table ``ftbl`` is shared with the round
    loop: the pass WRITES the floors its services create, so leftover
    round-loop elements (the genuinely concurrent contenders) observe
    fast-served lines' data-availability times; it does not READ floors
    itself — in-pass same-line successors are serialized by the
    directory-state replay (owner flush / upgrade against the
    predecessor's entry), which is how the oracle prices the same pair
    across two of its passes (see the ``arrive`` note in the body).

    Restrictions (the round loop serves everything instead): simple
    in-order cores (iocoom threads its LQ/SQ rings through the round
    loop), full_map directories (limited schemes take per-request
    pointer/trap actions that must serialize), and uncontended NoC
    models (emesh_hop_by_hop link flights thread per-link horizons
    through every leg in round order).
    """
    P = params.miss_chain
    T = params.num_tiles
    A = params.directory.associativity
    W = sharer_words(T)
    ndsets = params.directory.num_sets
    rows = jnp.arange(T)
    shared_l2 = params.shared_l2
    head0 = state.mq_head
    stop_hi = state.mq_count
    # Round-9 batched invalidation leg (tpu/fanout_replay): multi-sharer
    # EX/upgrade heads serve IN-PASS — the sharer bitmap expands to the
    # per-sharer INV target mask and the fan-out send + ack-combining is
    # priced with the round loop's exact math (max-hop unicast over the
    # mask — the ATAC hub broadcast leg via noc_atac behind
    # max_hop_to_mask_ps — doubled for the round trip, plus the
    # directory's ack-combining cycles), budgeted at KF deliveries per
    # replay iteration in FCFS order; budget losers RETRY the next
    # iteration like election losers instead of demoting the chain tail
    # to the one-element-per-round fallback.  LimitLESS software traps
    # never reach here (the fast pass is full_map-only), and live
    # directory victims still fall back — exactly the trap-only slow
    # path LimitLESS argues for.
    fanout = params.fanout_replay
    KF = min(params.max_inv_fanout_per_round, T)

    # ---- per-tile constants of the pass (clock periods only change in
    # a complex slot, never mid-resolve)
    p_net = _period(state, DVFSModule.NETWORK_MEMORY)
    p_dir = _period(state, DVFSModule.L2_CACHE if shared_l2
                    else DVFSModule.DIRECTORY)
    p_l2 = _period(state, DVFSModule.L2_CACHE)
    p_l1d = _period(state, DVFSModule.L1_DCACHE)
    p_l1i = _period(state, DVFSModule.L1_ICACHE)
    p_core = _period(state, DVFSModule.CORE)
    # (ack-combining cost is priced inside the classify kernel now —
    # chain_classify derives it from p_core itself)
    dram_access_ps = vp.dram_latency_ps
    dram_service_ps = vp.dram_processing_ps
    flits_req = noc.num_flits(CTRL_BYTES, vp.net_memory.flit_width_bits)
    flits_data = noc.num_flits(params.line_size + CTRL_BYTES,
                               vp.net_memory.flit_width_bits)
    rstamp = state.round_ctr * STAMP_STRIDE + STAMP_STRIDE - 1

    def slot_body(p, carry):
        # Each iteration serves every tile's CURRENT head (not the
        # static slot p): an election loser retries the same element
        # next iteration while the winner's chain moves on.  P
        # iterations serve up to P elements per tile — the whole bank
        # when nothing collides.
        #
        # Round-10 shape: the head gathers and the directory row
        # gathers stay here; the classify/elect/combine/price sub-chain
        # — victim-way tables, the (home, dset, way) FCFS election,
        # fan-out/owner budgets, SH combining, the zero-load timing
        # legs — runs through engine/kernels/chain.py (inline lax with
        # tpu/pallas_kernels off, ONE fused Pallas kernel per iteration
        # otherwise, bit-identically); the loop-carried DRAM queue
        # probe and the apply scatters stay here.  See chain_classify
        # for the transplanted commentary.
        del p
        state, stopped, head, base, ftbl = carry
        hsel = jnp.clip(head, 0, max(P - 1, 0))[None, :]
        req = jnp.take_along_axis(state.mq_req, hsel, axis=0)[0]   # [T]
        delta = jnp.take_along_axis(state.mq_delta, hsel, axis=0)[0]
        extra = jnp.take_along_axis(state.mq_extra, hsel, axis=0)[0]
        active = (~stopped) & (head < stop_hi)
        kind = (req & 7).astype(jnp.int32)
        line = jnp.where(active, req >> 8, 0)
        is_ex = active & (kind == PEND_EX_REQ)
        is_if = active & (kind == PEND_IFETCH)
        home = home_of_line(params, line)
        dset = dir_set_of_line(params, line)
        fidx = (home * ndsets + dset).astype(jnp.int32)
        # Blocking chain composition: element p's issue point is the
        # previous element's completion (the carried base) plus its
        # recorded local delta.
        issue = base + delta
        hidx = (dense.fmix64(line) % jnp.uint64(H)).astype(jnp.int32)

        # ---- directory entry rows at (home, dset) — ONE gather each
        drow = dir_gather(state.dir_word, fidx, A)             # [T, A]
        dsharers = sharers_gather(state.dir_sharers, fidx, A, W)  # [T, A, W]

        queue_on = params.dram.queue_model_enabled
        ci = kchain.ChainIn(
            active=active, is_ex=is_ex, is_if=is_if, line=line,
            issue=issue, extra=extra, home=home, dset=dset, fidx=fidx,
            hidx=hidx, drow=drow, dsharers=dsharers,
            p_net=p_net, p_dir=p_dir, p_l2=p_l2, p_l1d=p_l1d,
            p_l1i=p_l1i, p_core=p_core,
            ftbl=None if queue_on else ftbl)
        co = kchain.run_chain(params, vp, ci, H,
                              kdispatch.chain_mode(params))
        serve, serve_all, member = co.serve, co.serve_all, co.member
        way, owner_leg, fan_go = co.way, co.owner_leg, co.fan_go
        owner, evicting = co.owner, co.evicting
        need_read, dram_wb = co.need_read, co.dram_wb
        t_dir, inv_count = co.t_dir, co.inv_count
        stopped = stopped | co.hard_stop

        # ---- DRAM queue + completion (the loop-carried stretch the
        # kernel hands back; with the queue model off the kernel
        # already produced completion/t_data and wrote the floors)
        dsite = dram_site_of_line(params, line) if shared_l2 else home
        if queue_on:
            # record_split: a chain iteration's batch mixes tiles at
            # very different simulated times — split busy-interval
            # records stop one tile's far-future element from convoying
            # another tile's whole chain (fcfs_ring's phantom-convoy
            # note).
            q_start, _, _, rs_, re_, rp_, mg1_ = queue_models.probe(
                params.dram.queue_model_type,
                dsite, co.dram_arrival, jnp.full(T, dram_service_ps),
                need_read, state.dram_ring_start, state.dram_ring_end,
                state.dram_ring_ptr, state.dram_qacc,
                occ_res=dsite, occ_arr=co.dram_arrival,
                occ_svc=jnp.full(T, dram_service_ps), occ_valid=dram_wb,
                ma_window=params.dram.basic_ma_window,
                record_split=2 if fanout else 1)
            state = state._replace(dram_ring_start=rs_, dram_ring_end=re_,
                                   dram_ring_ptr=rp_, dram_qacc=mg1_)
            dram_start = jnp.where(need_read, q_start, 0)
            dram_ready = dram_start + dram_access_ps + dram_service_ps \
                + co.from_dram_ps
            t_data = jnp.maximum(t_dir + co.owner_ps,
                                 jnp.where(need_read, dram_ready, 0))
            if fanout:
                # The data grant waits on the last invalidation ack —
                # the round loop's exact completion rule.
                t_data = jnp.maximum(t_data, t_dir + co.inv_ps)
            reply_done = t_data + co.reply_ps
            if shared_l2:
                completion = reply_done + co.l1_fill_ps + extra
            else:
                completion = reply_done \
                    + _lat(vp.l2_access_cycles, p_l2) + co.l1_fill_ps \
                    + extra
        else:
            t_data, completion, ftbl = co.t_data, co.completion, co.ftbl

        # ---- apply: directory entry + sharer-bitmap delta (winners
        # hold distinct (home, dset, way) slots by the election above)
        state = state._replace(dir_word=dir_set(
            state.dir_word, A, serve, fidx, way,
            dword_pack(line, state.round_ctr, co.new_state, co.new_owner)))
        # Reps rewrite their row (old + delta); combining members add
        # their own bit on top of the rep's rewritten row — ONE merged
        # scatter-add, as in the round loop.
        old_row = jnp.take_along_axis(
            dsharers, way[:, None, None], axis=1)[:, 0, :]
        state = state._replace(dir_sharers=sharers_add(
            state.dir_sharers, A, W,
            rows=(serve, fidx, way, old_row, old_row + co.delta_sh),
            bits=(co.member_add, fidx, way, rows)))

        # ---- owner-side downgrade deliveries: per-target [T, J_OWN]
        # line lists (ranks < J_OWN are unique per target by the budget
        # election), one invalidate/downgrade sweep per cache.
        ow_tgt = jnp.where(owner_leg, owner, T).astype(jnp.int32)
        ow_slot = co.ow_slot
        own_lines = jnp.zeros((T, J_OWN), dtype=jnp.int64).at[
            ow_tgt, ow_slot].set(line, mode="drop")
        own_valid = jnp.zeros((T, J_OWN), dtype=bool).at[
            ow_tgt, ow_slot].set(True, mode="drop")
        own_down = jnp.zeros((T, J_OWN), dtype=jnp.int32).at[
            ow_tgt, ow_slot].set(co.down_to, mode="drop")
        if fanout:
            # Fan-out INV deliveries ride the same per-target sweep.
            dlv_lines = jnp.concatenate(
                [own_lines,
                 jnp.broadcast_to(co.line_fr[None, :], (T, KF))],
                axis=1)
            dlv_valid = jnp.concatenate([own_valid, co.inv_bool.T],
                                        axis=1)
            dlv_down = jnp.concatenate(
                [own_down, jnp.full((T, KF), I, dtype=jnp.int32)], axis=1)
        else:
            dlv_lines, dlv_valid, dlv_down = own_lines, own_valid, own_down
        state = state._replace(
            l2=cachemod.invalidate_by_value(
                state.l2, dlv_lines, dlv_valid, dlv_down),
            l1d=cachemod.invalidate_by_value(
                state.l1d, dlv_lines, dlv_valid, dlv_down))

        # ---- requester-side fills at serve time (the round loop's
        # winner path) + victim notify / DRAM writeback occupancy
        granted_e = serve & ~is_ex & (co.new_state == E)
        if shared_l2:
            l1_state = jnp.where(is_ex, M,
                                 jnp.where(granted_e, E, S)).astype(
                                     jnp.int32)
            fd = cachemod.fill(state.l1d, line, l1_state, serve_all & ~is_if,
                               params.l1d.num_sets, params.l1d.replacement,
                               rstamp)
            fi = cachemod.fill(state.l1i, line,
                               jnp.full(T, S, dtype=jnp.int32),
                               serve_all & is_if, params.l1i.num_sets,
                               params.l1i.replacement, rstamp)
            state = state._replace(l1d=fd.cache, l1i=fi.cache)
            vs1 = jnp.where(serve_all & ~is_if, fd.victim_state, I)
            vlive1 = serve_all & (vs1 != I)
            victim_dirty = vlive1 & (vs1 == M)
            state = _sh_l1_evict_notify(params, state, rows,
                                        fd.victim_tag, vs1, vlive1)
            state = _sh_l1_evict_notify(
                params, state, rows, fi.victim_tag, fi.victim_state,
                serve_all & is_if & (fi.victim_state != I))
        else:
            f2 = cachemod.fill(state.l2, line,
                               jnp.where(is_ex, M, S).astype(jnp.int32),
                               serve_all, params.l2.num_sets,
                               params.l2.replacement, rstamp)
            state = state._replace(l2=f2.cache)
            vt1, vs1 = f2.victim_tag, f2.victim_state
            # Inclusion: the L2 victim's L1D copy drops with it.
            state = state._replace(l1d=cachemod.invalidate_by_value(
                state.l1d, vt1[:, None],
                (serve_all & (vs1 != I))[:, None],
                jnp.full((T, 1), I, dtype=jnp.int32)))
            fd = cachemod.fill(state.l1d, line,
                               jnp.where(is_ex, M, S).astype(jnp.int32),
                               serve_all & ~is_if, params.l1d.num_sets,
                               params.l1d.replacement, rstamp)
            fi = cachemod.fill(state.l1i, line,
                               jnp.full(T, S, dtype=jnp.int32),
                               serve_all & is_if, params.l1i.num_sets,
                               params.l1i.replacement, rstamp)
            state = state._replace(l1d=fd.cache, l1i=fi.cache)
            victim_dirty = serve_all & ((vs1 == M) | (vs1 == O))
            victim_live = serve_all & (vs1 != I)
            victim_home = dram_site_of_line(params, vt1)
            if params.dram.queue_model_enabled:
                r3 = queue_models.occupy(
                    params.dram.queue_model_type,
                    state.dram_ring_start, state.dram_ring_end,
                    state.dram_ring_ptr, state.dram_qacc,
                    victim_home, t_dir, dram_service_ps, victim_dirty,
                    ma_window=params.dram.basic_ma_window)
                state = state._replace(dram_ring_start=r3[0],
                                       dram_ring_end=r3[1],
                                       dram_ring_ptr=r3[2],
                                       dram_qacc=r3[3])
            state = _dir_evict_notify(params, state, rows, vt1, vs1,
                                      victim_live)

        # ---- miss-type classification (same rules as the round loop)
        if params.track_miss_types:
            HF = state.seen_filter.shape[1]
            fslot = (dense.fmix64(line) % jnp.uint64(HF)).astype(jnp.int32)
            key32 = (line + 1).astype(jnp.int32)
            seen_hit = jnp.take_along_axis(
                state.seen_filter, fslot[:, None], axis=1)[:, 0] == key32
            inv_hit = jnp.take_along_axis(
                state.inv_filter, fslot[:, None], axis=1)[:, 0] == key32
            m_shar = serve_all & inv_hit
            c2 = state.counters
            state = state._replace(counters=c2._replace(
                l2_miss_cold=c2.l2_miss_cold
                + (serve_all & ~inv_hit & ~seen_hit).astype(jnp.int64),
                l2_miss_capacity=c2.l2_miss_capacity
                + (serve_all & ~inv_hit & seen_hit).astype(jnp.int64),
                l2_miss_sharing=c2.l2_miss_sharing
                + m_shar.astype(jnp.int64)))
            rows_w = jnp.where(serve_all, rows, T).astype(jnp.int32)
            state = state._replace(
                seen_filter=state.seen_filter.at[rows_w, fslot].set(
                    key32, mode="drop"),
                inv_filter=state.inv_filter.at[
                    jnp.where(m_shar, rows, T), fslot].set(
                    0, mode="drop"))
            # Record coherence take-aways (the round loop's inv_dlv
            # rule) on the TARGET tiles' filters.
            inv_dlv = dlv_valid & (dlv_down == I)
            dlv_line = dlv_lines
            dslot = (dense.fmix64(dlv_line)
                     % jnp.uint64(HF)).astype(jnp.int32)
            tgt_rows = jnp.where(
                inv_dlv, jnp.arange(T, dtype=jnp.int32)[:, None], T)
            state = state._replace(
                inv_filter=state.inv_filter.at[tgt_rows, dslot].set(
                    (dlv_line + 1).astype(jnp.int32), mode="drop"))

        # ---- counters (home-binned tallies via one stacked scatter)
        b = lambda m: m.astype(jnp.int64)
        home_cols = [
            b(serve_all & ~is_ex), b(serve & is_ex),  # dir_sh/ex_req
            b(evicting),                          # dir_evictions
            b(owner_leg),                         # dir_writebacks
            b(owner_leg & ~co.dram_write),        # dir_forwards
            b(serve_all) + inv_count,             # net_mem_pkts @home
            jnp.where(serve_all, flits_data, 0)
            + inv_count * flits_req,              # net_mem_flits @home
            inv_count,                            # dir_invalidations
        ]
        if shared_l2:
            home_cols += [b(serve_all), b(serve_all & ~co.hit)]
            dstack = jnp.stack([b(need_read), b(dram_wb)], axis=1)
            db = jnp.zeros((T, 2), dtype=jnp.int64).at[dsite].add(dstack)
            vic_wr = 0
        else:
            home_cols += [b(need_read), b(dram_wb)]
            vic_wr = jnp.zeros(T, dtype=jnp.int64).at[
                jnp.where(victim_dirty, victim_home, T)].add(
                1, mode="drop")
        hstack = jnp.stack(home_cols, axis=1)
        hb = jnp.zeros((T, hstack.shape[1]), dtype=jnp.int64).at[
            home].add(hstack)
        if not shared_l2:
            db = hb[:, 8:10]
        c = state.counters
        c = c._replace(
            dir_sh_req=c.dir_sh_req + hb[:, 0],
            dir_ex_req=c.dir_ex_req + hb[:, 1],
            dir_evictions=c.dir_evictions + hb[:, 2],
            dir_writebacks=c.dir_writebacks + hb[:, 3],
            dir_forwards=c.dir_forwards + hb[:, 4],
            dir_invalidations=c.dir_invalidations + hb[:, 7],
            dram_reads=c.dram_reads + db[:, 0],
            dram_writes=c.dram_writes + db[:, 1] + vic_wr,
            l2_access=c.l2_access + (hb[:, 8] if shared_l2 else 0),
            l2_miss=c.l2_miss + (hb[:, 9] if shared_l2 else 0),
            net_mem_pkts=c.net_mem_pkts + b(serve_all) + b(victim_dirty)
            + hb[:, 5],
            net_mem_flits=c.net_mem_flits + b(serve_all) * flits_req
            + b(victim_dirty) * flits_data + hb[:, 6],
            mem_stall_ps=c.mem_stall_ps + jnp.where(
                serve_all, completion - issue, 0),
            # Round-9 occupancy: fan-outs served in-pass vs chain heads
            # that hard-stopped into the round-loop fallback.
            chain_fanout_served=c.chain_fanout_served + b(fan_go),
            chain_fallback=c.chain_fallback + b(co.hard_stop),
        )
        state = state._replace(counters=c)

        # ---- serialization floor for later same-line requests (with
        # the queue model off the kernel already wrote it)
        if queue_on:
            tkey = t_data * T + rows
            tmax_t = jnp.full((H,), -1, jnp.int64).at[
                jnp.where(serve_all, hidx, H)].max(tkey, mode="drop")
            fwin = serve_all & (tmax_t[hidx] == tkey)
            ftbl = dense.stacked_set_table(hidx, fwin,
                                           jnp.stack([line, t_data]),
                                           ftbl)
        base = jnp.where(serve_all, completion, base)
        head = head + serve_all.astype(jnp.int32)
        return state, stopped, head, base, ftbl

    base0 = jnp.where(head0 == 0, 0, state.chain_base)
    carry = (state, jnp.zeros(T, dtype=bool), head0, base0, ftbl)
    state, _, head, base, ftbl = jax.lax.fori_loop(0, P, slot_body, carry)
    # Drained chains restore the absolute clock (last completion + the
    # local time the window accumulated past the final bank); partial
    # chains keep their continuation base for the round loop.
    drained = (state.mq_count > 0) & (head >= state.mq_count)
    state = state._replace(
        mq_head=jnp.where(drained, 0, head),
        mq_count=jnp.where(drained, 0, state.mq_count),
        chain_base=jnp.where(drained, 0, base),
        clock=jnp.where(drained, base + state.chain_rel, state.clock),
        chain_rel=jnp.where(drained, 0, state.chain_rel),
        round_ctr=state.round_ctr + 1,
    )
    return state, ftbl


@jax.named_scope(scopes.RESOLVE_MEMORY)
def resolve_memory(params: SimParams, vp: VariantParams,
                   state: SimState) -> SimState:
    """Serve all parked L2-miss requests through the home directories.

    Work per conflict round is O(T) + O(budget x T): same-line FCFS
    election and the per-line serialization floor go through scatter-min/max
    hash tables instead of [T, T] comparison matrices, and invalidation
    fan-out (EX-on-S sharer invalidations + shared-victim directory-entry
    evictions) is delivered for at most ``max_inv_fanout_per_round``
    requests per round — the rest defer to the next round (FCFS order
    preserved: a deferred winner re-wins its line next round), counted in
    ``dir_deferrals``.  A hash collision between two different pending
    lines only over-serializes (the loser retries next round); it never
    mis-times a request.
    """
    T = params.num_tiles
    A = params.directory.associativity
    W = sharer_words(T)
    K = min(params.max_inv_fanout_per_round, T)
    # Election hash-table size: keys are fmix64-mixed, so collisions are
    # birthday-random — with up to T concurrent keys the expected number
    # of colliding pairs is ~T^2/2H; 16x keeps spurious one-round
    # deferrals rare while the dense [T, H] election stays small.
    H = max(1024, 16 * T)
    rows = jnp.arange(T)
    line_bits = params.line_size.bit_length() - 1
    nctl = params.dram.num_controllers
    ndsets = params.directory.num_sets

    P = params.miss_chain

    # Per-tile clock periods.  (Shared L2: the "directory" access is the
    # slice's cache access, clocked by the L2 domain.)
    p_net = _period(state, DVFSModule.NETWORK_MEMORY)
    p_dir = _period(state, DVFSModule.L2_CACHE if params.shared_l2
                    else DVFSModule.DIRECTORY)
    p_l2 = _period(state, DVFSModule.L2_CACHE)
    p_l1 = _period(state, DVFSModule.L1_DCACHE)
    p_core = _period(state, DVFSModule.CORE)
    cycle_ps = _lat(1, p_core)
    # Invalidation-round ack-combining cost (directory.inv_ack_cycles,
    # VARIANT operand; default 1 == the historical one-cycle charge).
    ack_ps = _lat(vp.inv_ack_cycles, p_core)

    dram_access_ps = vp.dram_latency_ps
    dram_service_ps = vp.dram_processing_ps
    flits_req = noc.num_flits(CTRL_BYTES, vp.net_memory.flit_width_bits)
    flits_data = noc.num_flits(params.line_size + CTRL_BYTES,
                               vp.net_memory.flit_width_bits)
    dense_tables = T * H <= _DENSE_MAX_ELEMS
    slots_p = jnp.arange(max(P, 1), dtype=jnp.int32)[:, None]
    contended = noc_flight.contended(params.net_memory)

    # Blocking-semantics chain fast pass first: replays whole banked
    # chains sequentially inside ONE engine round (fori over chain
    # slots), serving every element up to its chain's first cross-tile
    # line conflict / traffic-needing transition; the round loop below
    # serves the leftovers one element per round with the full FCFS
    # machinery.  The serialization-floor table is threaded through so
    # leftovers observe fast-served lines' availability times.
    ftbl0 = jnp.stack([jnp.full((H,), -1, dtype=jnp.int64),
                       jnp.zeros((H,), dtype=jnp.int64)])
    if P > 0 and params.core.model == "simple" \
            and params.directory.directory_type == "full_map" \
            and not contended:
        state, ftbl0 = chain_fast_pass(params, vp, state, H, ftbl0)

    def _parked(st):
        k = st.pend_kind
        return ((k == PEND_SH_REQ) | (k == PEND_EX_REQ)
                | (k == PEND_IFETCH))

    def round_body(carry):
        # ftbl is the carried per-line serialization-floor hash table,
        # stacked [2, H]: row 0 = line id (-1 empty), row 1 = the
        # winner's data-availability time.  One stacked scatter/gather
        # pair serves both fields (they always read/write together).
        _i, state, ftbl = carry
        # Requester-cache fill stamp for this conflict round (monotone
        # across local rounds and conflict rounds; see core.STAMP_STRIDE).
        rstamp = state.round_ctr * STAMP_STRIDE + STAMP_STRIDE - 1

        # ---- active request per tile: the miss-chain head (P > 0 —
        # memory misses always bank, never park) or the parked one-shot
        # request (P == 0, the round-3 engine).  Chain heads advance as
        # rounds serve them, so every request-derived quantity is
        # computed per round.
        if P > 0:
            has_chain = state.mq_head < state.mq_count
            head_oh = slots_p == state.mq_head[None, :]        # [P, T]

            def hsel(arr):
                return jnp.sum(jnp.where(head_oh, arr, 0), axis=0)

            req = hsel(state.mq_req)
            cdelta = hsel(state.mq_delta)
            # Element 0's delta is its absolute issue time; later elements
            # chain off the previous element's continuation point.
            issue = jnp.where(state.mq_head == 0, cdelta,
                              state.chain_base + cdelta)
            kind = (req & 7).astype(jnp.int32)
            line = req >> 8
            extra = hsel(state.mq_extra)
            aux = ((req >> 3) & 1).astype(jnp.int32)
            unres = has_chain
        else:
            has_chain = jnp.zeros(T, dtype=bool)
            kind = state.pend_kind
            line = state.pend_addr >> line_bits
            issue = state.pend_issue
            extra = state.pend_extra
            aux = state.pend_aux
            unres = _parked(state)
        is_ex = unres & (kind == PEND_EX_REQ)
        is_if = unres & (kind == PEND_IFETCH)
        home = home_of_line(params, line)
        dset = dir_set_of_line(params, line)
        fidx = (home * ndsets + dset).astype(jnp.int32)
        packed = _fcfs_keys(unres, issue)
        # Election-table slot: a full 64-bit mix before the modulo — plain
        # ``line % H`` collapses power-of-two-strided per-tile buffers
        # (which park in near-lockstep) onto a handful of slots,
        # serializing requests that share nothing.
        hidx = (dense.fmix64(line) % jnp.uint64(H)).astype(jnp.int32)
        oh_hidx = _oh(hidx, H) if dense_tables else None
        p_net_home = p_net[home]
        p_dir_home = p_dir[home]
        net_req = noc.unicast_ps(params.net_memory, rows, home, CTRL_BYTES,
                                 p_net, params.mesh_width,
                                 vnet=vp.net_memory)
        reply_ps = noc.unicast_ps(params.net_memory, home, rows,
                                  params.line_size + CTRL_BYTES, p_net_home,
                                  params.mesh_width, vnet=vp.net_memory)
        dir_ps = _lat(vp.dir_access_cycles, p_dir_home)
        # Per-line serialization floor from the carried (line, time) hash
        # table (a stored-line check makes collisions inert).
        ftbl_g = ftbl[:, hidx]                     # [2, T] one gather
        line_floor = jnp.where(ftbl_g[0] == line, ftbl_g[1], 0)

        # ---- earliest-per-line election (the directory FSM serialization)
        if dense_tables:
            tbl = jnp.min(jnp.where(oh_hidx & unres[:, None],
                                    packed[:, None], _BIG), axis=0)
            win = unres & (_sel(oh_hidx, tbl) == packed)
        else:
            win = _elect(unres, packed, hidx, H)

        # ---- directory-cache probe at (home, dset), via the flat
        # (home*ndsets + dset) index — ONE gather for the whole entry
        drow = dir_gather(state.dir_word, fidx, A)           # [T, A]
        dstate = dword_state(drow)
        dstamp = dword_stamp(drow)
        match = (dword_tag(drow) == line[:, None].astype(jnp.int32)) \
            & (dstate != I)
        hit = match.any(axis=1)
        hway = jnp.argmax(match, axis=1).astype(jnp.int32)
        invalid = dstate == I

        # ---- victim-way assignment for allocating (miss) winners.  The
        # home directory serves same-set requests in FCFS order, each
        # evicting the then-best victim — so the k-th miss winner of a
        # (home, dset) group this round takes the way with the k-th best
        # replacement priority (invalid ways first, then min-stamp LRU),
        # with ways touched by a hit winner excluded.  Distinct ways per
        # group mean the winners' directory installs never collide.
        # grank comes from a lexsort over (set, FCFS key) and hit-held
        # ways from a hash table — both O(T log T), replacing the old
        # dense [T, T](, A) comparison blocks.
        hitwin = win & hit
        misswin = win & ~hit
        grank = _grouped_rank(fidx, packed, misswin)
        fhash = (dense.fmix64(fidx.astype(jnp.int64))
                 % jnp.uint64(H)).astype(jnp.int32)
        used_tbl = jnp.zeros((H, A), dtype=bool).at[
            jnp.where(hitwin, fhash, H), hway].set(True, mode="drop")
        hway_used = used_tbl[fhash]                           # [T, A]
        # Victim order key: hit-held ways never; invalid ways first, then
        # oldest stamp, ties to the lowest way.  (A hash collision can
        # only mark extra ways used — the loser defers a round, as with
        # the line election.)
        NEVER = jnp.int32(2**31 - 1)
        vkey = jnp.where(hway_used, NEVER,
                         jnp.where(invalid, -1, dstamp))
        eligible = ~hway_used
        arA0 = jnp.arange(A, dtype=jnp.int32)
        pos = jnp.sum(
            (eligible[:, None, :]
             & ((vkey[:, None, :] < vkey[:, :, None])
                | ((vkey[:, None, :] == vkey[:, :, None])
                   & (arA0[None, None, :] < arA0[None, :, None])))),
            axis=2).astype(jnp.int32)          # [T, A] ascending victim pos
        n_elig = jnp.sum(eligible, axis=1).astype(jnp.int32)
        miss_way = jnp.argmax(eligible & (pos == grank[:, None]),
                              axis=1).astype(jnp.int32)
        can_alloc = misswin & (grank < n_elig)
        way = jnp.where(hit, hway, miss_way)

        # ---- way-slot election safety net: hash collisions in the line
        # election can still hand two winners the same (home, dset, way);
        # the later one defers a round rather than corrupt the entry.
        # The flat slot id is fmix64-mixed before the modulo: unmixed,
        # ndsets*A is a multiple of H and the home tile cancels out of the
        # hash, colliding every same-(dset, way) request across homes.
        am = (home.astype(jnp.int64) * ndsets + dset) * A + way
        aidx = (dense.fmix64(am) % jnp.uint64(H)).astype(jnp.int32)
        alloc_defer = win & ((misswin & ~can_alloc)
                             | ~_elect(win, packed, aidx, H))
        win = win & ~alloc_defer
        misswin = misswin & ~alloc_defer

        # The selected way's whole entry in one gather of the packed word.
        way_word = jnp.take_along_axis(drow, way[:, None], axis=1)[:, 0]
        way_state = dword_state(way_word)
        way_owner = dword_owner(way_word)
        evicting = misswin & (way_state != I)

        way_sharers = sharers_gather(state.dir_sharers, fidx, A, W,
                                     way)                     # [T, W]
        entry_state = jnp.where(hit, way_state, I)
        entry_owner = jnp.where(hit, way_owner, -1)
        entry_sharers = jnp.where(hit[:, None], way_sharers,
                                  jnp.zeros((T, W), dtype=jnp.uint64))

        # Victim directory entry being replaced (reference invalidates all
        # of the victim's sharers/owner on directory-cache replacement —
        # dram_directory_cntlr replacement path; leaving them cached would
        # let a later request grant M while stale copies still hit).
        vtag = dword_tag(way_word).astype(jnp.int64)
        vstate = jnp.where(evicting, way_state, I)
        vowner = way_owner
        vsharers = way_sharers
        # Owner-flush victims: M always; E too under shared-L2 MESI (the
        # exclusive owner may have silently upgraded, so its flush is
        # conservatively priced and written back like a dirty one).
        if params.protocol_kind == "sh_l2_mesi":
            evict_m = evicting & ((vstate == M) | (vstate == E)) \
                & (vowner >= 0)
        else:
            evict_m = evicting & (vstate == M) & (vowner >= 0)
        # Empty-S entries (every sharer already dropped the line silently)
        # need no invalidation traffic — don't burn a fan-out slot on them.
        # O-state victims (MOSI) carry their owner in the sharer bitmap, so
        # the same multicast invalidates owner + sharers; the owner's dirty
        # data additionally reaches DRAM (occupancy + latency max below).
        evict_s = evicting & ((vstate == S) | (vstate == O)) \
            & (vsharers != jnp.uint64(0)).any(axis=1)

        act = dirmod.transition(params.protocol_kind, is_ex, rows,
                                entry_state, entry_owner, entry_sharers, W,
                                is_ifetch=is_if)

        # ---- limited directory schemes (reference: directory_schemes/
        # directory_entry_{limited_broadcast,limited_no_broadcast,ackwise,
        # limitless}.cc).  The engine stores the exact full bitmap; each
        # scheme contributes its BEHAVIORAL delta on top:
        #   limited_no_broadcast — an add past max_hw_sharers first
        #     invalidates a victim sharer (pointer eviction), so tracked
        #     sharers never exceed the cap;
        #   limitless — an access to an entry past the hardware pointer
        #     budget traps to software (software_trap_penalty directory
        #     cycles); sharer knowledge stays exact (software keeps it);
        #   limited_broadcast — an overflowed entry's invalidation must
        #     broadcast: latency spans ALL tiles and T-1 INV packets go
        #     out (every tile acks);
        #   ackwise — broadcast sends (T-1 packets) but acks are counted
        #     from the true sharers, so latency matches full_map.
        scheme = params.directory.directory_type
        k_hw = params.directory.max_hw_sharers
        scheme_dir_ps = jnp.int64(0)
        bcast_lat = bcast_traffic = None
        if scheme != "full_map":
            # Pointer pressure excludes the requester's own already-set
            # bit: a tracked sharer re-requesting consumes no new pointer
            # (no victim eviction, no software trap).
            req_bits = dirmod.make_tile_bit(rows, W)
            others = entry_sharers & ~req_bits
            n_sh = dirmod.popcount(others)
            if scheme == "limitless":
                scheme_dir_ps = jnp.where(
                    hit & (n_sh >= k_hw),
                    _lat(vp.limitless_trap_cycles, p_dir_home), 0)
            elif scheme == "limited_no_broadcast":
                cand = others
                overflow_add = ~is_ex & (n_sh >= k_hw) \
                    & (cand != jnp.uint64(0)).any(axis=1)
                vbit = dirmod.lowest_bit(cand)
                act = act._replace(
                    inv_targets=jnp.where(overflow_add[:, None],
                                          act.inv_targets | vbit,
                                          act.inv_targets),
                    new_sharers=jnp.where(overflow_add[:, None],
                                          act.new_sharers & ~vbit,
                                          act.new_sharers))
            elif scheme in ("limited_broadcast", "ackwise"):
                # Overflow is about TOTAL tracked pointers (the
                # requester's own bit occupies one too).
                overflowed = dirmod.popcount(entry_sharers) > k_hw
                bcast_traffic = overflowed
                if scheme == "limited_broadcast":
                    bcast_lat = overflowed

        has_inv = win & (act.inv_targets != jnp.uint64(0)).any(axis=1)
        owner = act.owner_tile
        vown_c = jnp.maximum(vowner, 0)

        # ---- fan-out budget: at most K multicast deliveries per round,
        # granted in FCFS key order (not tile order) so a hot-spot round
        # never systematically favors low tile ids.
        need_fan = has_inv | evict_s
        # K earliest FCFS keys win the budget — dense rank (top_k lowers
        # to a serialized loop on TPU, same story as _grouped_rank).
        fan_rank = jnp.sum(
            (packed[None, :] < packed[:, None]) & need_fan[None, :]
            & need_fan[:, None], axis=1, dtype=jnp.int32)
        sel0 = need_fan & (fan_rank < K)
        fan_defer = need_fan & ~sel0
        win1 = win & ~fan_defer

        # ---- owner-side delivery slots: at most J_OWN point-to-point
        # flush/downgrade deliveries per TARGET tile per round (owner legs
        # of current entries + victim-owner flushes — several requesters
        # can name the same owner); rows past a target's budget defer
        # their whole request a round, in FCFS key order (tile order above
        # the dense-rank size cap).
        owner_leg1 = act.owner_leg & win1
        evict_m1 = evict_m & ~fan_defer
        tgt2 = jnp.concatenate([owner, vown_c])
        val2 = jnp.concatenate([owner_leg1, evict_m1])
        key2 = jnp.concatenate([packed, packed])
        # FCFS rank of each delivery within its target tile's budget
        # (sort-based — the old dense [2T, 2T] compare was O(T^2)).
        posr = _grouped_rank(tgt2, key2, val2)            # [2T]
        over2 = val2 & (posr >= J_OWN)
        ow_defer = over2[:T] | over2[T:]
        win = win1 & ~ow_defer
        has_inv = has_inv & ~fan_defer & ~ow_defer
        evict_m = evict_m1 & ~ow_defer
        evict_s = evict_s & ~fan_defer & ~ow_defer
        evicting = evicting & ~fan_defer & ~ow_defer
        evict_o = evicting & (vstate == O)
        owner_leg = owner_leg1 & ~ow_defer
        val2 = jnp.concatenate([owner_leg, evict_m])

        # Per-target owner-delivery line lists [T, J_OWN], scatter-built —
        # surviving rows keep their unique slot rank < J_OWN.
        lines2 = jnp.concatenate([line, vtag])
        down2 = jnp.concatenate(
            [act.owner_downgrade_to, jnp.full(T, I, dtype=jnp.int32)])
        put = val2 & (posr < J_OWN)
        tgt2_m = jnp.where(put, tgt2, T).astype(jnp.int32)
        slot2 = jnp.minimum(posr, J_OWN - 1)
        own_lines = jnp.zeros((T, J_OWN), dtype=lines2.dtype).at[
            tgt2_m, slot2].set(lines2, mode="drop")
        own_valid = jnp.zeros((T, J_OWN), dtype=bool).at[
            tgt2_m, slot2].set(True, mode="drop")
        own_tgt = jnp.zeros((T, J_OWN), dtype=jnp.int32).at[
            tgt2_m, slot2].set(down2, mode="drop")

        # ---- shared-read combining.  The per-line FCFS election exists to
        # serialize CONFLICTING transactions; concurrent SH_REQs against an
        # I/S entry don't conflict (each independently adds its sharer bit
        # and reads DRAM — the reference's directory serves them back to
        # back with no inter-request blocking), so serializing them one
        # winner per round turned every shared-code i-fetch or read-mostly
        # line into a T-round convoy (1024 tiles x one line = 1024 rounds).
        # When EVERY unresolved request for a line is SH and the entry is
        # I/S, all of them win alongside the elected rep: identical
        # tag/meta/stamp writes collide harmlessly, sharer bits land as a
        # guarded disjoint scatter-add after the rep's full-row write, and
        # the DRAM queue still prices each read.  full_map only (limited
        # schemes take per-add pointer actions that must serialize); the
        # MESI slice E-grant on I stays a sole winner.
        combinable = jnp.zeros_like(win)
        req_word = (rows // 64).astype(jnp.int32)
        req_bit1 = jnp.uint64(1) << (rows % 64).astype(jnp.uint64)
        if params.directory.directory_type == "full_map":
            sh_entry_ok = (entry_state == I) | (entry_state == S)
            if params.shared_l2:
                # A combined read of an UNCACHED slice line would charge
                # one DRAM fill per reader; the reference's slice fills
                # once and serves later readers from the slice (and the
                # MESI E-grant needs a sole first reader anyway) — so
                # shared-L2 combining applies to S entries only.
                sh_entry_ok = sh_entry_ok & (entry_state != I)
            ex_unres = unres & is_ex
            rep_sh = win & ~is_ex & sh_entry_ok
            if dense_tables:
                any_ex = jnp.any(oh_hidx & ex_unres[:, None], axis=0)
                rline = jnp.max(jnp.where(oh_hidx & rep_sh[:, None],
                                          line[:, None], -1), axis=0)
                rway = jnp.max(jnp.where(oh_hidx & rep_sh[:, None],
                                         way[:, None], -1), axis=0)
                m_any_ex = _sel(oh_hidx, any_ex.astype(jnp.int32)) > 0
                m_rline = _sel(oh_hidx, rline)
                m_rway = _sel(oh_hidx, rway).astype(jnp.int32)
            else:
                # Three per-field tables over ONE shared index vector,
                # stacked into a single scatter-max (set == max here:
                # rep_sh has at most one winner per slot and the ex flag
                # is monotone; masked rows write the max identity).  One
                # stacked gather reads all three back — 6 sequential
                # dispatches become 2 (PROFILE.md lever 3).
                cmb = dense.stacked_max_table(
                    hidx, jnp.stack([
                        jnp.where(ex_unres, 1, -1).astype(jnp.int64),
                        jnp.where(rep_sh, line, jnp.int64(-1)),
                        jnp.where(rep_sh, way.astype(jnp.int64), -1)]),
                    H, jnp.int64(-1))
                g_cmb = cmb[:, hidx]
                m_any_ex = g_cmb[0] > 0
                m_rline = g_cmb[1]
                m_rway = g_cmb[2].astype(jnp.int32)
            combinable = unres & ~win & ~is_ex & sh_entry_ok \
                & ~m_any_ex & (m_rline == line)
            win = win | combinable
            way = jnp.where(combinable, m_rway, way)
        own_word = jnp.take_along_axis(entry_sharers, req_word[:, None],
                                       axis=1)[:, 0]
        sharer_add = combinable & ((own_word & req_bit1) == jnp.uint64(0))

        sel = sel0 & ~ow_defer
        rank = queue_models._cumsum_doubling(sel.astype(jnp.int32)) - 1
        # Selected fan-out rows, as a dense [K, T] slot-assignment mask
        # (oh_sr[k, t] <=> requester t owns fan-out slot k this round).
        oh_sr = sel[None, :] & (
            jnp.arange(K, dtype=jnp.int32)[:, None] == rank[None, :])

        def sr_sel(vals):     # [T] -> [K] values of each slot's requester
            return jnp.sum(jnp.where(oh_sr, vals[None, :], 0), axis=1,
                           dtype=vals.dtype)

        inv_words = jnp.sum(
            jnp.where((oh_sr & has_inv[None, :])[:, :, None],
                      act.inv_targets[None, :, :], jnp.uint64(0)),
            axis=1, dtype=jnp.uint64)                    # [K, W]
        vic_words = jnp.sum(
            jnp.where((oh_sr & evict_s[None, :])[:, :, None],
                      vsharers[None, :, :], jnp.uint64(0)),
            axis=1, dtype=jnp.uint64)
        inv_bool = dirmod.bitmap_to_bool(inv_words, T)   # [K, T]
        vic_bool = dirmod.bitmap_to_bool(vic_words, T)   # [K, T]
        if bcast_lat is not None:
            # limited_broadcast overflow: the INV broadcast's completion
            # waits on acks from EVERY tile, not just the true sharers.
            bl_k = jnp.any(oh_sr & (bcast_lat & has_inv)[None, :], axis=1)
            inv_bool = inv_bool | bl_k[:, None]

        home_sr = sr_sel(home)
        pnh_sr = sr_sel(p_net_home.astype(jnp.int64)).astype(jnp.int32)
        ack_sr = sr_sel(ack_ps)

        # Invalidation round-trip latencies, mapped back per requester
        # (ack-combining cycles on top of the max-hop round trip).
        inv_ps_k = 2 * noc.max_hop_to_mask_ps(
            params.net_memory, home_sr, inv_bool, CTRL_BYTES,
            pnh_sr, params.mesh_width, vnet=vp.net_memory) + ack_sr
        vic_ps_k = 2 * noc.max_hop_to_mask_ps(
            params.net_memory, home_sr, vic_bool, CTRL_BYTES,
            pnh_sr, params.mesh_width, vnet=vp.net_memory) + ack_sr
        inv_ps = jnp.where(has_inv, jnp.sum(
            jnp.where(oh_sr, inv_ps_k[:, None], 0), axis=0), 0)
        evict_ps = jnp.where(evict_s, jnp.sum(
            jnp.where(oh_sr, vic_ps_k[:, None], 0), axis=0), 0)
        # M-state victim: single-owner flush round trip.
        p_net_vown = p_net[vown_c]
        # Owner-side lookup cost for flush/downgrade legs: the owner holds
        # the line in its private L2 — or only in its L1D under shared L2
        # (there is no private L2 there).
        if params.shared_l2:
            l2_vown_ps = _lat(vp.l1d_access_cycles,
                              _period(state, DVFSModule.L1_DCACHE)[vown_c])
        else:
            l2_vown_ps = _lat(vp.l2_access_cycles, p_l2[vown_c])

        # ---- latency assembly (SURVEY.md 3.3's round trips).  Unicast
        # legs are either zero-load closed forms (magic/emesh_hop_counter)
        # or, under emesh_hop_by_hop, flights priced by the configured
        # link queue model (engine/noc_flight.py), threading the link
        # queues through every leg in dependency order: request -> victim
        # flush -> owner leg -> reply.  Invalidation multicasts stay
        # zero-load (the reference's broadcast-tree option likewise
        # bypasses per-hop unicast queues).  Contention requires both the
        # hop-by-hop model AND its queue model (noc_flight.contended).
        link_wait = jnp.zeros(T, dtype=jnp.int64)
        lr = state.link_mem
        rows32 = rows.astype(jnp.int32)
        if contended:
            fr = noc_flight.flight(
                params.net_memory, params.mesh_width, params.mesh_height,
                rows32, home, issue, flits_req, win, lr, p_net,
                vnet=vp.net_memory)
            lr = fr.rings
            link_wait = link_wait + fr.wait_ps
            arrive = jnp.maximum(fr.arrival, line_floor)
        else:
            arrive = jnp.maximum(issue + net_req, line_floor)

        # Victim flush round trips: M victims always (L1/L2 owner flush);
        # O victims only under MOSI (the private owner holds the dirty
        # data) — under shared L2 the slice itself holds O data, so its
        # eviction writes DRAM without visiting any other tile.
        ev_rt = (evict_m | evict_o) if params.protocol_kind == "mosi" \
            else evict_m
        if contended:
            dep_ev = arrive + dir_ps
            e1 = noc_flight.flight(
                params.net_memory, params.mesh_width, params.mesh_height,
                home, vown_c, dep_ev, flits_req, ev_rt, lr, p_net_home,
                vnet=vp.net_memory)
            e2 = noc_flight.flight(
                params.net_memory, params.mesh_width, params.mesh_height,
                vown_c, home, e1.arrival + l2_vown_ps, flits_data, ev_rt,
                e1.rings, p_net_vown, vnet=vp.net_memory)
            lr = e2.rings
            link_wait = link_wait + e1.wait_ps + e2.wait_ps
            evict_m_ps = jnp.where(ev_rt, e2.arrival - dep_ev, 0)
        else:
            evict_m_ps = noc.unicast_ps(
                params.net_memory, home, vown_c, CTRL_BYTES,
                p_net_home, params.mesh_width, vnet=vp.net_memory) \
                + l2_vown_ps \
                + noc.unicast_ps(
                    params.net_memory, vown_c, home,
                    params.line_size + CTRL_BYTES,
                    p_net_vown, params.mesh_width, vnet=vp.net_memory)
        evict_ps = jnp.where(evict_m, evict_m_ps, evict_ps)
        if params.protocol_kind == "mosi":
            # O-state victim: sharer-invalidation multicast AND the
            # owner's dirty-data flush leg — whichever completes later.
            evict_ps = jnp.where(evict_o, jnp.maximum(evict_ps, evict_m_ps),
                                 evict_ps)

        # Replacement of a live victim entry completes before the new
        # request is served.  scheme_dir_ps adds the limitless software
        # trap where the entry overflowed its hardware pointers.
        t_dir = arrive + dir_ps + scheme_dir_ps \
            + jnp.where(evicting, evict_ps, 0)

        p_net_own = p_net[owner]
        if params.shared_l2:
            l2_own_ps = _lat(vp.l1d_access_cycles,
                             _period(state, DVFSModule.L1_DCACHE)[owner])
        else:
            l2_own_ps = _lat(vp.l2_access_cycles, p_l2[owner])
        if contended:
            g1 = noc_flight.flight(
                params.net_memory, params.mesh_width, params.mesh_height,
                home, owner, t_dir, flits_req, owner_leg, lr, p_net_home,
                vnet=vp.net_memory)
            g2 = noc_flight.flight(
                params.net_memory, params.mesh_width, params.mesh_height,
                owner, home, g1.arrival + l2_own_ps, flits_data, owner_leg,
                g1.rings, p_net_own, vnet=vp.net_memory)
            lr = g2.rings
            link_wait = link_wait + g1.wait_ps + g2.wait_ps
            owner_ps = jnp.where(owner_leg, g2.arrival - t_dir, 0)
        else:
            leg_ps = noc.unicast_ps(params.net_memory, home, owner,
                                    CTRL_BYTES, p_net_home,
                                    params.mesh_width, vnet=vp.net_memory) \
                + l2_own_ps \
                + noc.unicast_ps(params.net_memory, owner, home,
                                 params.line_size + CTRL_BYTES, p_net_own,
                                 params.mesh_width, vnet=vp.net_memory)
            owner_ps = jnp.where(owner_leg, leg_ps, 0)

        need_read = win & act.dram_read
        if params.shared_l2:
            # The slice home and the memory controller can differ: a slice
            # miss adds slice->controller request + data-return legs
            # (zero-load; reference pr_l1_sh_l2 dram_cntlr placement).
            dsite = dram_site_of_line(params, line)
            local_ctl = home == dsite
            to_dram_ps = jnp.where(local_ctl, 0, noc.unicast_ps(
                params.net_memory, home, dsite, CTRL_BYTES, p_net_home,
                params.mesh_width, vnet=vp.net_memory))
            from_dram_ps = jnp.where(local_ctl, 0, noc.unicast_ps(
                params.net_memory, dsite, home,
                params.line_size + CTRL_BYTES, p_net[dsite],
                params.mesh_width, vnet=vp.net_memory))
        else:
            dsite = home
            to_dram_ps = from_dram_ps = jnp.int64(0)
        dram_arrival = t_dir + owner_ps + to_dram_ps
        # Writebacks (owner-leg flushes that reach DRAM) occupy the
        # controller off the critical path (write buffer): occupancy-only
        # rows in the interval queue.  MOSI owner forwards and shared-L2
        # transitions skip DRAM entirely (act.dram_write False); dirty
        # victim evictions insert their own intervals in the fills
        # section below.
        dram_wb = (act.dram_write & win) | evict_m | evict_o
        if params.dram.queue_model_enabled:
            q_start, _, _, rs_, re_, rp_, mg1_ = queue_models.probe(
                params.dram.queue_model_type,
                dsite, dram_arrival, jnp.full(T, dram_service_ps),
                need_read, state.dram_ring_start, state.dram_ring_end,
                state.dram_ring_ptr, state.dram_qacc,
                occ_res=dsite, occ_arr=dram_arrival,
                occ_svc=jnp.full(T, dram_service_ps), occ_valid=dram_wb,
                ma_window=params.dram.basic_ma_window)
            state = state._replace(dram_ring_start=rs_, dram_ring_end=re_,
                                   dram_ring_ptr=rp_, dram_qacc=mg1_)
            dram_start = jnp.where(need_read, q_start, 0)
        else:
            # [dram/queue_model] enabled=false: no queueing delay, no
            # occupancy tracking (reference DramPerfModel without a
            # queue model).
            dram_start = jnp.where(need_read, dram_arrival, 0)
        dram_ready = dram_start + dram_access_ps + dram_service_ps \
            + from_dram_ps

        t_data = t_dir + owner_ps
        t_data = jnp.maximum(t_data, jnp.where(need_read, dram_ready, 0))
        t_data = jnp.maximum(t_data, t_dir + inv_ps)

        if contended:
            rr = noc_flight.flight(
                params.net_memory, params.mesh_width, params.mesh_height,
                home, rows32, t_data, flits_data, win, lr, p_net_home,
                vnet=vp.net_memory)
            link_wait = link_wait + rr.wait_ps
            reply_done = rr.arrival
            state = state._replace(link_mem=rr.rings)
        else:
            reply_done = t_data + reply_ps

        l1_fill_ps = jnp.where(
            is_if, _lat(vp.l1i_access_cycles,
                        _period(state, DVFSModule.L1_ICACHE)),
            _lat(vp.l1d_access_cycles, p_l1))
        if params.shared_l2:
            # No private L2 to fill through on the requester side.
            completion = reply_done + l1_fill_ps + extra
        else:
            l2_fill_ps = _lat(vp.l2_access_cycles, p_l2)
            completion = reply_done + l2_fill_ps + l1_fill_ps \
                + extra

        # ---- apply directory entry updates: single-way scatters.  The
        # way-slot election guarantees winners hold distinct
        # (home, dset, way) slots this round, so no two scatters collide;
        # replacement recency is the scattered round stamp (timestamp LRU,
        # like engine/cache.py — the old code maintained rank permutations
        # with dense [T, T, A] merges).
        # (Combined SH winners of one line write identical packed words,
        # so their colliding scatters are safe; the sharer bitmap is the
        # one per-winner-distinct field — the line's rep rewrites the row,
        # then the other combined winners add their disjoint bits on top.)
        state = state._replace(dir_word=dir_set(
            state.dir_word, A, win, fidx, way,
            dword_pack(line, state.round_ctr, act.new_state,
                       act.new_owner)))
        # Sharer-bitmap rewrite as modular delta-adds: the slot's current
        # row is known (the hit entry's words, or the victim's for a fresh
        # alloc), so adding (new - old) per u32 word lands the new row
        # exactly, and the combined winners' bits ride the same
        # scatter-add — one 1-D scatter into the flat store, so no
        # relayout of it (see SimState.dir_sharers).
        old_row = jnp.where(hit[:, None], entry_sharers, vsharers)
        state = state._replace(dir_sharers=sharers_add(
            state.dir_sharers, A, W,
            rows=(win & ~combinable, fidx, way, old_row, act.new_sharers),
            bits=(sharer_add, fidx, way, rows)))

        # ---- coherence-driven cache-state changes, one single-pass sweep
        # per cache level over per-target line lists: owner downgrades
        # (current-entry M), victim-owner flushes, budgeted sharer
        # invalidations, and victim-entry sharer invalidations.
        line_sr = sr_sel(line)
        vtag_sr = sr_sel(vtag)
        dlv_lines = jnp.concatenate([
            own_lines,
            jnp.broadcast_to(line_sr[None, :], (T, K)),
            jnp.broadcast_to(vtag_sr[None, :], (T, K))], axis=1)
        dlv_valid = jnp.concatenate(
            [own_valid, inv_bool.T, vic_bool.T], axis=1)
        dlv_tgt = jnp.concatenate(
            [own_tgt, jnp.full((T, 2 * K), I, dtype=jnp.int32)], axis=1)
        state = state._replace(
            l2=cachemod.invalidate_by_value(
                state.l2, dlv_lines, dlv_valid, dlv_tgt),
            l1d=cachemod.invalidate_by_value(
                state.l1d, dlv_lines, dlv_valid, dlv_tgt))

        # ---- miss-type classification ([cache]/track_miss_types;
        # reference cache.h:45-49): every served miss is sharing (the
        # line was coherence-invalidated from this tile), capacity (seen
        # before, evicted since), or cold (first touch).  Filters are
        # direct-mapped per-tile line tables — a hash collision can
        # misclassify one miss, never mistime anything.
        if params.track_miss_types:
            HF = state.seen_filter.shape[1]
            fslot = (dense.fmix64(line) % jnp.uint64(HF)).astype(jnp.int32)
            key32 = (line + 1).astype(jnp.int32)
            seen_hit = jnp.take_along_axis(
                state.seen_filter, fslot[:, None], axis=1)[:, 0] == key32
            inv_hit = jnp.take_along_axis(
                state.inv_filter, fslot[:, None], axis=1)[:, 0] == key32
            m_shar = win & inv_hit
            m_cap = win & ~inv_hit & seen_hit
            m_cold = win & ~inv_hit & ~seen_hit
            c2 = state.counters
            state = state._replace(counters=c2._replace(
                l2_miss_cold=c2.l2_miss_cold
                + m_cold.astype(jnp.int64),
                l2_miss_capacity=c2.l2_miss_capacity
                + m_cap.astype(jnp.int64),
                l2_miss_sharing=c2.l2_miss_sharing
                + m_shar.astype(jnp.int64)))
            rows_w = jnp.where(win, rows, T).astype(jnp.int32)
            # The fill marks the line seen and consumes any inv mark.
            state = state._replace(
                seen_filter=state.seen_filter.at[rows_w, fslot].set(
                    key32, mode="drop"),
                inv_filter=state.inv_filter.at[
                    jnp.where(m_shar, rows, T), fslot].set(
                    0, mode="drop"))
            # Record coherence take-aways: INV deliveries (down to I) mark
            # the target's filter slot for the delivered line.
            inv_dlv = dlv_valid & (dlv_tgt == I)
            dlv_line_i = dlv_lines.astype(jnp.int64)
            dslot = (dense.fmix64(dlv_line_i)
                     % jnp.uint64(HF)).astype(jnp.int32)
            tgt_rows = jnp.where(
                inv_dlv, jnp.arange(T, dtype=jnp.int32)[:, None], T)
            state = state._replace(
                inv_filter=state.inv_filter.at[tgt_rows, dslot].set(
                    (dlv_line_i + 1).astype(jnp.int32), mode="drop"))

        # ---- requester-side fills / victims: EVERY winner — a P == 0
        # parked request or a P > 0 chain head — installs its line at
        # SERVE time (blocking semantics: nothing was installed at bank
        # time), choosing its victim against the post-serve cache state;
        # the fill's victim feeds the directory notify + DRAM writeback
        # occupancy below.
        granted_e = win & ~is_ex & (act.new_state == E)
        if params.shared_l2:
            # MESI first-reader grant: fill the L1 line in E so a later
            # local store silently upgrades it (core.py mesi_local path).
            l1_state = jnp.where(is_ex, M,
                                 jnp.where(granted_e, E, S)).astype(
                                     jnp.int32)
            fd = cachemod.fill(state.l1d, line, l1_state,
                               win & ~is_if,
                               params.l1d.num_sets, params.l1d.replacement,
                               rstamp)
            state = state._replace(l1d=fd.cache)
            fi = cachemod.fill(state.l1i, line,
                               jnp.full(T, S, dtype=jnp.int32),
                               win & is_if, params.l1i.num_sets,
                               params.l1i.replacement, rstamp)
            state = state._replace(l1i=fi.cache)
            # i-fetch L1I victims notify separately below via vt_i.
            vt1 = fd.victim_tag
            vs1 = jnp.where(win & ~is_if, fd.victim_state, I)
            vt_i, vs_i = fi.victim_tag, fi.victim_state
        else:
            f2 = cachemod.fill(state.l2, line,
                               jnp.where(is_ex, M, S).astype(jnp.int32),
                               win, params.l2.num_sets,
                               params.l2.replacement, rstamp)
            state = state._replace(l2=f2.cache)
            vt1, vs1 = f2.victim_tag, f2.victim_state
            # An evicted-from-L2 line also leaves L1 (inclusive hierarchy,
            # reference l2_cache_cntlr invalidation of L1 on eviction).
            state = state._replace(l1d=cachemod.invalidate_by_value(
                state.l1d, f2.victim_tag[:, None],
                (win & (f2.victim_state != I))[:, None],
                jnp.full((T, 1), I, dtype=jnp.int32)))
            fd = cachemod.fill(state.l1d, line,
                               jnp.where(is_ex, M, S).astype(jnp.int32),
                               win & ~is_if, params.l1d.num_sets,
                               params.l1d.replacement, rstamp)
            state = state._replace(l1d=fd.cache)
            fi = cachemod.fill(state.l1i, line,
                               jnp.full(T, S, dtype=jnp.int32),
                               win & is_if, params.l1i.num_sets,
                               params.l1i.replacement, rstamp)
            state = state._replace(l1i=fi.cache)

        if params.shared_l2:
            # L1 victims report back to their slice: dirty ones flush
            # data into the slice (entry -> O), clean drops clear sharer
            # bits.  The dirty flush is a line-size WB data packet on the
            # memory network (counted below via victim_dirty; off the
            # critical path, so no latency/link-contention charge) — it
            # lands in the slice, not DRAM.
            vlive1 = win & (vs1 != I)
            victim_dirty = vlive1 & (vs1 == M)
            state = _sh_l1_evict_notify(params, state, rows, vt1, vs1,
                                        vlive1)
            state = _sh_l1_evict_notify(
                params, state, rows, vt_i, vs_i,
                win & is_if & (vs_i != I))
        else:
            victim_dirty = win & ((vs1 == M) | (vs1 == O))
            victim_live = win & (vs1 != I)
            victim_home = dram_site_of_line(params, vt1)
            if params.dram.queue_model_enabled:
                r3 = queue_models.occupy(
                    params.dram.queue_model_type,
                    state.dram_ring_start, state.dram_ring_end,
                    state.dram_ring_ptr, state.dram_qacc,
                    victim_home, t_dir, dram_service_ps, victim_dirty,
                    ma_window=params.dram.basic_ma_window)
                state = state._replace(dram_ring_start=r3[0],
                                       dram_ring_end=r3[1],
                                       dram_ring_ptr=r3[2], dram_qacc=r3[3])
            # Notify the victim line's home directory (reference sends
            # eviction writebacks that downgrade the entry; silently
            # dropping them left stale owners/sharer bits that charge
            # phantom coherence legs).  Off the requester's critical path.
            state = _dir_evict_notify(params, state, rows, vt1, vs1,
                                      victim_live)

        # ---- counters (all home-binned tallies via dense one-hot sums)
        kcnt_inv = jnp.sum(inv_bool, axis=1).astype(jnp.int64)  # [K]
        kcnt_inv_flits = kcnt_inv
        if bcast_traffic is not None:
            # Broadcast schemes put T-1 INV messages on the wire for an
            # overflowed entry regardless of the true sharer count.
            # With a broadcast tree ([network/emesh_hop_by_hop]
            # broadcast_tree_enabled, carbon_sim.cfg:299-313) the source
            # INJECTS one packet and the routers replicate it down the
            # tree — still ~T-1 link traversals carrying the flits
            # (energy/traffic are per-traversal, reference charges every
            # tree link), so flit accounting keeps the T-1 factor and
            # only the packet count drops to 1.  Without the tree the
            # sender unicasts T-1 packets (network.cc:215- fan-out).
            # Latency is the max-hop bound either way.
            bt_k = jnp.any(oh_sr & (bcast_traffic & has_inv)[None, :],
                           axis=1)
            bcast_pkts = 1 if params.net_memory.broadcast_tree_enabled \
                else T - 1
            kcnt_inv = jnp.where(bt_k, bcast_pkts, kcnt_inv)
            kcnt_inv_flits = jnp.where(bt_k, T - 1, kcnt_inv_flits)
        kcnt_vic = jnp.sum(vic_bool, axis=1).astype(jnp.int64)
        kcnt = kcnt_inv + kcnt_vic
        kcnt_fl = kcnt_inv_flits + kcnt_vic
        inv_count = jnp.sum(jnp.where(oh_sr, kcnt[:, None], 0), axis=0)
        inv_flits = jnp.sum(jnp.where(oh_sr, kcnt_fl[:, None], 0), axis=0)
        c = state.counters
        # Home-binned tallies ride ONE scatter-add of a stacked [T, 9+]
        # delta matrix (the old per-counter dense [T, T] one-hot sums were
        # O(T^2) each); rows with no work contribute zero deltas, so no
        # mask is needed.
        b = lambda m: m.astype(jnp.int64)
        home_cols = [
            b(win & ~is_ex),                          # dir_sh_req
            b(win & is_ex),                           # dir_ex_req
            inv_flits,                                # dir_invalidations
            #   (logical INV deliveries — a tree broadcast still
            #   invalidates T-1 caches even when injected as 1 packet)
            b(owner_leg | evict_m | evict_o),         # dir_writebacks
            b(owner_leg & ~act.dram_write),           # dir_forwards
            b(evicting),                              # dir_evictions
            b(win) + inv_count,                       # net_mem_pkts @home
            jnp.where(win, flits_data, 0)
            + inv_flits * flits_req,                  # net_mem_flits @home
            b(alloc_defer | fan_defer | ow_defer),    # dir_deferrals
        ]
        if params.shared_l2:
            # Slice accesses/misses are accounted at the home tile here
            # (the local kernel never sees an L2).
            home_cols += [b(win), b(win & ~hit)]      # l2_access, l2_miss
            # Slice home != controller: the DRAM-site tallies need their
            # own index vector.
            dstack = jnp.stack([b(need_read), b(dram_wb)], axis=1)
            db = jnp.zeros((T, 2), dtype=jnp.int64).at[dsite].add(dstack)
            # A dirty L1 victim flushes into the SLICE (its WB packet is
            # counted below), not DRAM.
            vic_wr = 0
        else:
            # Private-L2 protocols: dsite == home, so the DRAM-site
            # columns ride the SAME home-indexed scatter-add as the
            # directory/network tallies — one dispatch instead of two.
            home_cols += [b(need_read), b(dram_wb)]   # dram_reads/writes
            vic_wr = jnp.zeros(T, dtype=jnp.int64).at[
                victim_home].add(b(victim_dirty))
        hstack = jnp.stack(home_cols, axis=1)
        hb = jnp.zeros((T, hstack.shape[1]), dtype=jnp.int64).at[
            home].add(hstack)
        if not params.shared_l2:
            db = hb[:, 9:11]
        c = c._replace(
            dir_sh_req=c.dir_sh_req + hb[:, 0],
            dir_ex_req=c.dir_ex_req + hb[:, 1],
            dir_invalidations=c.dir_invalidations + hb[:, 2],
            dir_writebacks=c.dir_writebacks + hb[:, 3],
            dir_forwards=c.dir_forwards + hb[:, 4],
            dir_evictions=c.dir_evictions + hb[:, 5],
            dram_reads=c.dram_reads + db[:, 0],
            dram_writes=c.dram_writes + db[:, 1] + vic_wr,
            l2_access=c.l2_access + (hb[:, 9] if params.shared_l2 else 0),
            l2_miss=c.l2_miss + (hb[:, 10] if params.shared_l2 else 0),
            net_mem_pkts=c.net_mem_pkts
            + jnp.where(win, 1, 0)                    # request
            + jnp.where(victim_dirty, 1, 0)           # victim WB data
            # reply + INV_REQ traffic accounted at the home tile
            + hb[:, 6],
            net_mem_flits=c.net_mem_flits
            + jnp.where(win, flits_req, 0)
            + jnp.where(victim_dirty, flits_data, 0)
            + hb[:, 7],
            net_link_wait_ps=c.net_link_wait_ps + link_wait,
            # Deferral events this round: way-slot collisions + fan-out
            # budget overflow + owner-delivery budget overflow (a request
            # deferred in N rounds counts N times; end-of-pass saturation
            # is counted separately below).
            dir_deferrals=c.dir_deferrals + hb[:, 8],
        )
        state = state._replace(counters=c)

        # ---- core-model unblock semantics.  simple: the in-order core
        # stalls until the data arrives (SimpleCoreModel).  iocoom: plain
        # load/store misses only hold the core until issue + 1 cycle (the
        # LQ/SQ entry tracks the priced completion; drain points in
        # local_advance wait for it), floored at the reused ring slot's
        # previous completion — a full queue backpressures.  Atomics and
        # i-fetches always wait in full.  (Reference:
        # iocoom_core_model.cc:78- load queue / store buffer.)
        if params.core.model == "iocoom":
            # aux bit 0 = atomic flag; bits 8-12 = scoreboard dest
            # register + 1 (core.py pend_aux packing).
            is_atomic = (aux & 0xFF) != 0
            is_load = win & (kind == PEND_SH_REQ) & ~is_atomic
            is_store = win & (kind == PEND_EX_REQ) & ~is_atomic
            if params.core.mixed:
                # Heterogeneous model_list: simple tiles stall until the
                # data arrives (unpark = completion below); only iocoom
                # tiles release at issue+1 via their LQ/SQ entries.
                iot = jnp.asarray(params.core.iocoom_mask)
                is_load = is_load & iot
                is_store = is_store & iot
            LQE = state.lq_ready.shape[0]
            SQE = state.sq_ready.shape[0]
            lq_oh = dense.onehot(state.lq_next % LQE, LQE).T \
                & is_load[None, :]                           # [LQE, T]
            sq_oh = dense.onehot(state.sq_next % SQE, SQE).T \
                & is_store[None, :]
            lq_floor = jnp.sum(jnp.where(lq_oh, state.lq_ready, 0), axis=0)
            sq_floor = jnp.sum(jnp.where(sq_oh, state.sq_ready, 0), axis=0)
            if not params.core.multiple_outstanding_rfos:
                # One outstanding RFO: a store miss waits for every prior
                # store's completion before issuing its own.
                sq_floor = jnp.maximum(
                    sq_floor, jnp.max(state.sq_ready, axis=0))
            unpark = jnp.where(
                is_load, jnp.maximum(issue + cycle_ps, lq_floor),
                jnp.where(is_store,
                          jnp.maximum(issue + cycle_ps, sq_floor),
                          completion))
            state = state._replace(
                lq_ready=jnp.where(lq_oh, completion[None, :],
                                   state.lq_ready),
                sq_ready=jnp.where(sq_oh, completion[None, :],
                                   state.sq_ready),
                lq_next=state.lq_next + is_load,
                sq_next=state.sq_next + is_store)
            # Scoreboarded remote load: land the priced completion in the
            # destination register's ready slot (reference executeLoad ->
            # _register_scoreboard[reg] = write_operands_ready,
            # iocoom_core_model.cc:188-199).
            NREG = state.reg_ready.shape[0]
            dreg = (aux >> 8) & 31
            state = state._replace(reg_ready=state.reg_ready.at[
                jnp.where(is_load & (dreg > 0), dreg - 1, NREG),
                jnp.arange(T)].max(completion, mode="drop"))
        else:
            unpark = completion

        # Parked winners unblock (cursor advance + stall accounting;
        # P > 0 has no memory parks — the complex slot banks instead).
        if P == 0:
            state = _unblock(state, win, unpark, sync=False)
        # Chain winners advance their chain: the continuation point
        # becomes the base for the next element's issue; a fully drained
        # chain restores the absolute clock (base + accumulated local
        # time) and frees the bank for the next window.
        if P > 0:
            c4 = state.counters
            new_head = state.mq_head + win.astype(jnp.int32)
            drained = win & (new_head >= state.mq_count)
            state = state._replace(
                mq_head=jnp.where(drained, 0, new_head),
                mq_count=jnp.where(drained, 0, state.mq_count),
                chain_base=jnp.where(win, unpark, state.chain_base),
                clock=jnp.where(drained, unpark + state.chain_rel,
                                state.clock),
                chain_rel=jnp.where(drained, 0, state.chain_rel),
                counters=c4._replace(
                    mem_stall_ps=c4.mem_stall_ps
                    + jnp.where(win, unpark - issue, 0)))

        # ---- serialization floor for still-pending same-line requests:
        # per-line winner's data-availability time, into the carried
        # (line, time) hash table (collisions inert via the line check).
        t_free = t_data
        if dense_tables:
            win_oh = oh_hidx & win[:, None]
            new_line = jnp.max(
                jnp.where(win_oh, line[:, None], jnp.int64(-1)), axis=0)
            new_t = jnp.max(jnp.where(win_oh, t_free[:, None], 0), axis=0)
            wrote = win_oh.any(axis=0)
            ftbl = jnp.where(wrote[None, :],
                             jnp.stack([new_line, new_t]), ftbl)
        else:
            # Both fields land in ONE stacked scatter.  Elected winners
            # are unique per slot; COMBINED SH winners of one line do
            # collide here with per-member availability times (the
            # dense path above takes the group max) — a pre-existing
            # backend-ordering wart left as-is because this path is part
            # of the miss_chain = 0 bit-identity surface (it engages
            # only above the dense-table cap, T > 512, where combined
            # same-line floors differ by sub-cycle NoC skew).
            ftbl = dense.stacked_set_table(
                hidx, win, jnp.stack([line, t_free]), ftbl)
        state = state._replace(round_ctr=state.round_ctr + 1,
                               ctr_conflict=state.ctr_conflict + 1)
        return _i + 1, state, ftbl

    # Early-exit conflict rounds: a round only runs while unresolved
    # requests remain (parked requests clear their pend kind on service;
    # chain heads advance to their counts).
    def _more(st):
        if P > 0:
            return (st.mq_head < st.mq_count).any()
        return _parked(st).any()

    cap = params.max_resolve_rounds if P > 0 \
        else params.directory_conflict_rounds

    def round_cond(carry):
        i, st, _ft = carry
        return (i < cap) & _more(st)

    state = state._replace(ctr_resolve=state.ctr_resolve + 1)
    carry = (jnp.int32(0), state, ftbl0)
    _, state, _ = jax.lax.while_loop(round_cond, round_body, carry)
    # Saturation visibility (VERDICT weak #5): requests still pending after
    # a full resolve pass slipped past the round cap and will be retried
    # next sub-round (binned at the requester tile).
    saturated = (state.mq_head < state.mq_count) if P > 0 \
        else _parked(state)
    c = state.counters
    state = state._replace(counters=c._replace(
        dir_deferrals=c.dir_deferrals + saturated.astype(jnp.int64)))
    return state


class _VictimProbe:
    """Directory/slice entry located for a batch of dropped lines — the
    shared plumbing of the eviction-notify paths: per-row home/set, tag
    match, way select, entry metadata, and the dropping tile's sharer-bit
    geometry (word index, bit mask, presence)."""

    def __init__(self, params: SimParams, state: SimState, tiles, vtag,
                 valid):
        T = params.num_tiles
        A = params.directory.associativity
        W = sharer_words(T)
        self.assoc, self.words, self.tiles = A, W, tiles
        ndsets = params.directory.num_sets
        self.vhome = home_of_line(params, vtag)
        self.vdset = dir_set_of_line(params, vtag)
        self.vfidx = (self.vhome * ndsets + self.vdset).astype(jnp.int32)
        vfidx = self.vfidx
        drow = dir_gather(state.dir_word, vfidx, A)         # [T, A]
        dstate = dword_state(drow)
        match = (dword_tag(drow) == vtag[:, None].astype(jnp.int32)) \
            & (dstate != I) & valid[:, None]
        self.found = match.any(axis=1)
        self.way = jnp.argmax(match, axis=1).astype(jnp.int32)
        self.word_way = jnp.take_along_axis(
            drow, self.way[:, None], axis=1)[:, 0]
        self.est = dword_state(self.word_way)
        self.eowner = dword_owner(self.word_way)
        self.esharers = sharers_gather(state.dir_sharers, vfidx, A, W,
                                       self.way)              # [T, W]
        self.word = (tiles // 64).astype(jnp.int32)
        self.bit = jnp.uint64(1) << (tiles % 64).astype(jnp.uint64)
        self.woh = self.word[:, None] \
            == jnp.arange(W, dtype=jnp.int32)[None, :]
        cur = jnp.sum(jnp.where(self.woh, self.esharers, jnp.uint64(0)),
                      axis=1, dtype=jnp.uint64)
        self.has_bit = (cur & self.bit) != jnp.uint64(0)

    def set_meta(self, state: SimState, mask, new_state, new_owner):
        """Rewrite the matched entry's (state, owner) where ``mask``
        (tag + stamp preserved from the gathered word; callers pass
        disjoint masks, so each entry is written at most once)."""
        return state._replace(dir_word=dir_set(
            state.dir_word, self.assoc, mask, self.vfidx, self.way,
            dword_with_meta(self.word_way, new_state, new_owner)))

    def set_meta2(self, state: SimState, mask_a, state_a, owner_a,
                  mask_b, state_b, owner_b):
        """Two DISJOINT-mask (state, owner) rewrites fused into ONE
        scatter — the eviction-notify paths always write exactly two
        complementary entry classes, and each scatter into dir_word is a
        sequential dispatch on TPU (see dense.py's stacking rationale)."""
        new = jnp.where(mask_a,
                        dword_with_meta(self.word_way, state_a, owner_a),
                        dword_with_meta(self.word_way, state_b, owner_b))
        return state._replace(dir_word=dir_set(
            state.dir_word, self.assoc, mask_a | mask_b, self.vfidx,
            self.way, new))

    def clear_bit(self, state: SimState, mask):
        """Clear the dropping tile's sharer bit where ``mask`` (guarded
        commutative subtract — distinct sharers of one entry may clear in
        the same batch)."""
        return state._replace(dir_sharers=sharers_add(
            state.dir_sharers, self.assoc, self.words,
            bits=(mask & self.has_bit, self.vfidx, self.way, self.tiles),
            clear_bits=True))


def _dir_evict_notify(params: SimParams, state: SimState, tiles, vtag,
                      vstate, valid) -> SimState:
    """Tell the home directory a tile dropped ``vtag`` from its L2.

    M-owner entries become I (the dirty data went to DRAM); an O owner's
    drop (MOSI) clears the owner and leaves the remaining sharers in S (or
    I when none remain) — its dirty data also went to DRAM; a plain
    sharer's bit clears via a commutative subtract so concurrent drops of
    different sharers of the same line all land.  (Reference: eviction
    writeback messages into dram_directory_cntlr.)
    """
    p = _VictimProbe(params, state, tiles, vtag, valid)

    # Owner dropped its M line: entry -> I.
    drop_m = p.found & (p.est == M) & (p.eowner == tiles)
    # Owner dropped its O line (MOSI): owner cleared, sharers remain in S.
    drop_o = p.found & (p.est == O) & (p.eowner == tiles)
    # Sharer dropped its S copy (incl. a non-owner sharer of an O entry).
    drop_s = p.found & p.has_bit \
        & ((p.est == S) | ((p.est == O) & (p.eowner != tiles)))
    # Last sharer gone -> entry I, so later evictions of the entry don't
    # burn fan-out budget on an empty bitmap.  (Concurrent same-entry drops
    # of one entry in this batch each still see the pre-batch bitmap, so a
    # transient empty-S entry can remain; the evict_s gate tolerates that.)
    left = p.esharers & ~jnp.where(p.woh, p.bit[:, None], jnp.uint64(0))
    empty = (left == jnp.uint64(0)).all(axis=1)

    state = p.set_meta2(state,
                        drop_m | ((drop_s | drop_o) & empty), I, -1,
                        drop_o & ~empty, S, -1)
    # M drop wipes the whole bitmap row (the owner was the only holder) by
    # modular subtract of the known contents; S/O drops clear one bit —
    # one scatter-add.
    return state._replace(dir_sharers=sharers_add(
        state.dir_sharers, p.assoc, p.words,
        rows=(drop_m, p.vfidx, p.way, p.esharers,
              jnp.zeros_like(p.esharers)),
        bits=((drop_s | drop_o) & p.has_bit, p.vfidx, p.way, tiles),
        clear_bits=True))


def _sh_l1_evict_notify(params: SimParams, state: SimState, tiles, vtag,
                        vstate, valid) -> SimState:
    """Report an L1 victim back to its home L2 slice (shared-L2 protocols).

    A dirty (M) L1 victim flushes its data into the slice — the entry
    drops its owner and becomes O (slice-dirty); a clean exclusive (E)
    victim releases ownership (entry -> S); a plain S victim just clears
    its sharer bit.  The slice line itself stays resident — unlike the
    private-protocol notify, entries never drop to I here (reference:
    pr_l1_sh_l2_msi l1 writeback into l2_cache_cntlr).
    """
    p = _VictimProbe(params, state, tiles, vtag, valid)
    own_drop = p.found & (p.eowner == tiles) & ((p.est == M) | (p.est == E))
    # Dirty flush -> slice-dirty O; clean exclusive release -> S.
    state = p.set_meta2(state, own_drop & (vstate == M), O, -1,
                        own_drop & (vstate != M), S, -1)
    # The tile no longer holds the line in any case.
    return p.clear_bit(state, p.found)


# ====================================================================== sync

def resolve_recv(params: SimParams, vp: VariantParams,
                 state: SimState) -> SimState:
    T = params.num_tiles
    rows = jnp.arange(T)
    D = state.ch_time.shape[0]
    is_recv = state.pend_kind == PEND_RECV
    src = jnp.clip(state.pend_aux, 0, T - 1)
    sent = state.ch_sent[src, rows]
    recvd = state.ch_recvd[src, rows]
    avail = sent > recvd
    slot = recvd % D
    arr = state.ch_time[slot, src, rows]
    ok = is_recv & avail
    cycle_ps = _lat(1, _period(state, DVFSModule.CORE))
    completion = jnp.maximum(state.pend_issue, arr) + cycle_ps
    src_eff = jnp.where(ok, src, T)
    state = state._replace(
        ch_recvd=state.ch_recvd.at[src_eff, rows].add(1, mode="drop"),
        # Overwrite the consumed ring slot with the recv's completion time:
        # the slot's next writer (a send reusing it after a wrap) reads it
        # back as the slot-freed floor, so back-pressured sends can never
        # stamp arrivals that predate the recv that made room.
        ch_time=state.ch_time.at[slot, src_eff, rows].set(
            completion, mode="drop"),
        counters=state.counters._replace(
            recvs=state.counters.recvs + jnp.where(
                ok & state.models_enabled, 1, 0)))
    return _unblock(state, ok, completion, sync=True)


def resolve_send(params: SimParams, vp: VariantParams,
                 state: SimState) -> SimState:
    """Complete sends that were back-pressured by a full channel ring."""
    T = params.num_tiles
    rows = jnp.arange(T)
    D = state.ch_time.shape[0]
    is_send = state.pend_kind == PEND_SEND
    dst = jnp.clip(state.pend_aux, 0, T - 1)
    space = (state.ch_sent[rows, dst] - state.ch_recvd[rows, dst]) < D
    ok = is_send & space
    p_nu = _period(state, DVFSModule.NETWORK_USER)
    cycle_ps = _lat(1, _period(state, DVFSModule.CORE))
    net_ps = noc.unicast_ps(params.net_user, rows, dst, state.pend_addr,
                            p_nu, params.mesh_width, vnet=vp.net_user)
    slot = state.ch_sent[rows, dst] % D
    # Floor at the time the reused ring slot was actually freed (the
    # consuming recv's completion, stored into the slot by resolve_recv) —
    # a back-pressured send cannot complete before the recv that made room.
    freed = state.ch_time[slot, rows, dst]
    completion = jnp.maximum(state.pend_issue, freed) + cycle_ps
    arrival = completion + net_ps
    src_eff = jnp.where(ok, rows, T).astype(jnp.int32)
    state = state._replace(
        ch_time=state.ch_time.at[slot, src_eff, dst].set(arrival, mode="drop"),
        ch_sent=state.ch_sent.at[src_eff, dst].add(1, mode="drop"),
        counters=state.counters._replace(
            sends=state.counters.sends + jnp.where(
                ok & state.models_enabled, 1, 0),
            net_user_pkts=state.counters.net_user_pkts + jnp.where(
                ok & state.models_enabled, 1, 0),
            net_user_flits=state.counters.net_user_flits + jnp.where(
                ok & state.models_enabled,
                noc.num_flits(state.pend_addr,
                              vp.net_user.flit_width_bits), 0)))
    return _unblock(state, ok, completion, sync=True)


def resolve_barrier(params: SimParams, vp: VariantParams,
                    state: SimState) -> SimState:
    T = params.num_tiles
    rows = jnp.arange(T)
    NB = state.bar_count.shape[0]
    is_bar = state.pend_kind == PEND_BARRIER
    bid = jnp.clip(state.pend_addr, 0, NB - 1).astype(jnp.int32)
    parts = jnp.maximum(state.pend_aux, 1)
    reached = state.bar_count[bid] >= parts
    rel = is_bar & reached
    p_nu = _period(state, DVFSModule.NETWORK_USER)
    cycle_ps = _lat(1, _period(state, DVFSModule.CORE))
    back_ps = noc.unicast_ps(params.net_user,
                             jnp.full(T, mcp_tile(params)), rows, CTRL_BYTES,
                             p_nu[mcp_tile(params)], params.mesh_width,
                             vnet=vp.net_user)
    completion = state.bar_time[bid] + back_ps + cycle_ps
    if state.sched_enabled:
        # Wake DESCHEDULED waiters of released barriers directly in the
        # stream store — the release edge resets bar_count below, so a
        # rotated-out parker would otherwise miss its generation
        # (ThreadScheduler; the reference's barrier server wakes every
        # registered waiter regardless of scheduling).  Their arrival
        # was already counted at park time.
        S = state.strm_cursor.shape[0]
        s_is = state.strm_pend_kind == PEND_BARRIER
        sbid = jnp.clip(state.strm_pend_addr, 0, NB - 1).astype(jnp.int32)
        sparts = jnp.maximum(state.strm_pend_aux, 1)
        s_rel = s_is & (state.bar_count[sbid] >= sparts) \
            & ~state.strm_done
        s_tile = (jnp.arange(S, dtype=jnp.int32) % T)
        s_comp = state.bar_time[sbid] + back_ps[s_tile] + cycle_ps[s_tile]
        state = state._replace(
            strm_pend_kind=jnp.where(s_rel, PEND_NONE,
                                     state.strm_pend_kind),
            strm_clock=jnp.where(s_rel, s_comp, state.strm_clock),
            strm_cursor=state.strm_cursor + jnp.where(s_rel, 1, 0))
    # reset released barriers for their next generation
    bid_eff = jnp.where(rel, bid, NB)
    state = state._replace(
        bar_count=state.bar_count.at[bid_eff].set(0, mode="drop"),
        bar_time=state.bar_time.at[bid_eff].set(0, mode="drop"))
    return _unblock(state, rel, completion, sync=True)


def resolve_mutex(params: SimParams, vp: VariantParams,
                  state: SimState) -> SimState:
    T = params.num_tiles
    rows = jnp.arange(T)
    NL = state.lock_holder.shape[0]
    is_mx = state.pend_kind == PEND_MUTEX
    lid = jnp.clip(state.pend_addr, 0, NL - 1).astype(jnp.int32)
    issue = state.pend_issue
    # FCFS: earliest waiter per free lock wins (SimMutex wakeup order,
    # sync_server.cc) — exact election: the lock id indexes the table
    # directly, so there are no hash collisions.
    first = _elect(is_mx, _fcfs_keys(is_mx, issue), lid, NL)
    free = state.lock_holder[lid] == 0
    win = first & free
    p_nu = _period(state, DVFSModule.NETWORK_USER)
    cycle_ps = _lat(1, _period(state, DVFSModule.CORE))
    mcp = mcp_tile(params)
    to_mcp = noc.unicast_ps(params.net_user, rows, jnp.full(T, mcp),
                            CTRL_BYTES, p_nu, params.mesh_width,
                            vnet=vp.net_user)
    from_mcp = noc.unicast_ps(params.net_user, jnp.full(T, mcp), rows,
                              CTRL_BYTES, p_nu[mcp], params.mesh_width,
                              vnet=vp.net_user)
    grant = jnp.maximum(issue + to_mcp, state.lock_free_at[lid])
    completion = grant + from_mcp + cycle_ps
    lid_eff = jnp.where(win, lid, NL)
    state = state._replace(
        lock_holder=state.lock_holder.at[lid_eff].set(
            (rows + 1).astype(jnp.int32), mode="drop"),
        counters=state.counters._replace(
            mutex_acquires=state.counters.mutex_acquires
            + jnp.where(win & state.models_enabled, 1, 0)))
    return _unblock(state, win, completion, sync=True)


def resolve_cond(params: SimParams, vp: VariantParams,
                 state: SimState) -> SimState:
    """Match parked cond waiters with parked signal/broadcast tokens.

    Semantics (reference SimCond, sync_server.cc:67-119): the POSTER of a
    signal/broadcast parks as the token itself (PEND_CSIG/PEND_CBC with
    its exact MCP-arrival timestamp).  Each pass processes, per cond, the
    single EARLIEST pending token — so interleaved signals and broadcasts
    act in exact time order:

      * signal: wakes the earliest waiter with ``park <= t_sig`` (i.e.
        already parked at the signal's server time, pthread lost-signal
        semantics); if none exists it stays pending until no still-live
        tile could park with an earlier timestamp (clock skew within a
        quantum allows late-arriving earlier parks), then it is LOST.
      * broadcast: wakes every waiter with ``park <= t_bc``; it is
        consumed under the same no-earlier-parks-possible rule so skewed
        waiters are never missed.

    Posters unblock when their token resolves, with timestamp-based
    completions (MCP ack round trip) — the extra engine passes a pending
    token waits cost wall time only, never simulated time.  A woken
    waiter transforms into PEND_MUTEX to re-acquire its mutex through the
    regular FCFS machinery (SimCond::wait re-locks on wake).
    """
    from graphite_tpu.engine.state import NUM_CONDS as NC
    T = params.num_tiles
    rows = jnp.arange(T)
    kind = state.pend_kind
    is_cw = kind == PEND_COND
    is_sig = kind == PEND_CSIG
    is_bc = kind == PEND_CBC
    is_tok = is_sig | is_bc
    cid = jnp.clip(state.pend_addr, 0, NC - 1).astype(jnp.int32)
    t = state.pend_issue                       # MCP-arrival timestamps
    oh_c = dense.onehot(cid, NC)

    # One earliest token per cond this pass (FCFS by time then tile).
    tok_win = _elect(is_tok, _fcfs_keys(is_tok, t), cid, NC)
    tok_time_nc = dense.binmax(oh_c, tok_win, t, 0)          # [NC]
    tok_bc_nc = dense.binsum(oh_c, tok_win & is_bc, 1) > 0   # [NC]
    has_tok_nc = dense.binsum(oh_c, tok_win, 1) > 0

    # Waiter eligibility against its cond's elected token.  Strict mode
    # enforces pthread lost-signal semantics in simulated time (a waiter
    # must have parked at or before the token); replay mode (captured
    # traces) accepts any parked waiter — the native run already proved
    # the pairing — waking at max(park, token time).
    wt = _sel(oh_c, tok_time_nc)
    w_has = _sel(oh_c, has_tok_nc.astype(jnp.int32)) > 0
    w_bc = _sel(oh_c, tok_bc_nc.astype(jnp.int32)) > 0
    if params.cond_replay:
        elig = is_cw & w_has
        wake_at = jnp.maximum(t, wt)
    else:
        elig = is_cw & w_has & (t <= wt)
        wake_at = wt
    first = _elect(elig, _fcfs_keys(elig, t), cid, NC)
    wake = jnp.where(w_bc, elig, first)
    if params.cond_replay:
        # Orphaned recorded waits: simulated retiming can push a captured
        # COND_WAIT past the signal that natively woke it (the token was
        # rightly lost before the waiter arrived).  The native run proves
        # the waiter WAS woken, so once the system is sync-quiesced (every
        # live tile parked on a pure-sync kind — memory/send parks
        # self-resolve) and no token exists for its cond, the waiter
        # wakes spuriously at its own park time.
        # (PEND_SEND counts as sync here: a full channel only drains when
        # its receiver runs, so a sender parked behind the orphan must not
        # block the rescue.  A parked RECV/SEND that was about to
        # self-resolve can make the rescue fire one pass early — a timing
        # approximation, never a hang.)
        k = state.pend_kind
        pure_sync = ((k == PEND_COND) | (k == PEND_MUTEX)
                     | (k == PEND_BARRIER) | (k == PEND_RECV)
                     | (k == PEND_SEND) | (k == PEND_JOIN)
                     | (k == PEND_START) | (k == PEND_CSIG)
                     | (k == PEND_CBC))
        quiesce = ~jnp.any(~state.done & ~pure_sync)
        orphan = is_cw & ~w_has & quiesce
        wake = wake | orphan
        wake_at = jnp.where(orphan, t, wake_at)

    p_nu = _period(state, DVFSModule.NETWORK_USER)
    mcp = mcp_tile(params)
    to_mcp = noc.unicast_ps(params.net_user, rows,
                            jnp.full(T, mcp), CTRL_BYTES,
                            p_nu, params.mesh_width, vnet=vp.net_user)

    # Token resolution: a signal completes when it woke someone, or when
    # provably lost; a broadcast completes once no earlier park can still
    # arrive (its wakes repeat harmlessly until then — same waiters, same
    # times).  Each tile's future park timestamps are lower-bounded by:
    # its clock (runnable); STRICTLY past pend_issue when parked (every
    # resume completes at least a cycle after issue); for mutex waiters,
    # past issue + to_mcp (the grant can't precede the MCP arrival) —
    # this matters because cond-woken waiters carry a rewound pend_issue
    # of (wake - to_mcp) for the re-acquire math, which must not pin the
    # very token that woke them.  The token excludes ITSELF from the
    # bound via the two smallest.
    INF = jnp.int64(2**62)
    lb = jnp.where(
        state.done, INF,
        jnp.where(state.pend_kind == PEND_NONE, state.clock,
                  jnp.where(state.pend_kind == PEND_MUTEX,
                            state.pend_issue + to_mcp + 1,
                            state.pend_issue + 1)))
    if state.sched_enabled:
        # Descheduled streams can still park (or already hold a park)
        # with timestamps at or past their frozen clocks — a token must
        # not be declared lost/complete while such a stream could still
        # match it (ThreadScheduler; the store's parked COND waiters
        # match when reseated, since tokens are durable parked entries).
        lb_store = jnp.where(
            state.strm_done, INF,
            jnp.where(state.strm_pend_kind == PEND_NONE,
                      state.strm_clock, state.strm_pend_issue + 1))
        # Exclude currently-seated streams (their seat rows carry the
        # live values; the store copy is stale for them).
        seated = jnp.zeros(lb_store.shape[0], dtype=bool).at[
            state.seat_stream].set(True)
        store_min = jnp.min(jnp.where(seated, INF, lb_store))
    else:
        store_min = INF
    if lb.shape[0] >= 2:
        neg2 = jax.lax.top_k(-lb, 2)[0]
        m1, m2 = -neg2[0], -neg2[1]
        lb_excl = jnp.where(lb == m1, m2, m1)  # min over the OTHER tiles
    else:
        lb_excl = jnp.full_like(lb, INF)       # no other tiles exist
    lb_excl = jnp.minimum(lb_excl, store_min)
    # ---- store-side wakes (ThreadScheduler): descheduled waiters must
    # be woken directly — a waiter and its signaler placed on the same
    # tile alternate one seat and may NEVER be co-seated, so seat-only
    # matching would hang them (the store_min bound above stops the
    # token from being falsely lost, but cannot deliver the wake).
    # Broadcasts wake every eligible stored waiter; a signal falls back
    # to the earliest stored waiter only when no seated one matched.
    woke_seat_nc = dense.binsum(oh_c, wake & ~w_bc, 1) > 0
    if state.sched_enabled:
        S = state.strm_cursor.shape[0]
        s_tile = (jnp.arange(S, dtype=jnp.int32) % T)
        seated_s = jnp.zeros(S, dtype=bool).at[state.seat_stream].set(True)
        s_is_cw = (state.strm_pend_kind == PEND_COND) & ~seated_s \
            & ~state.strm_done
        s_cid = jnp.clip(state.strm_pend_addr, 0, NC - 1).astype(jnp.int32)
        s_t = state.strm_pend_issue
        s_wt = tok_time_nc[s_cid]
        s_has = has_tok_nc[s_cid]
        s_bc = tok_bc_nc[s_cid]
        if params.cond_replay:
            s_elig = s_is_cw & s_has
            s_wake_at = jnp.maximum(s_t, s_wt)
        else:
            s_elig = s_is_cw & s_has & (s_t <= s_wt)
            s_wake_at = s_wt
        # Signal fallback: earliest eligible stored waiter per cond,
        # only for conds whose signal woke no seated waiter.
        sBIG = jnp.int64(2**62)
        skey = jnp.clip(s_t, 0, jnp.int64(2**40)) * S \
            + jnp.arange(S, dtype=jnp.int64)
        stbl = jnp.full((NC,), sBIG, jnp.int64).at[
            jnp.where(s_elig, s_cid, NC)].min(skey, mode="drop")
        s_first = s_elig & (stbl[s_cid] == skey)
        s_wake = jnp.where(s_bc, s_elig,
                           s_first & ~woke_seat_nc[s_cid])
        to_mcp_s = to_mcp[s_tile]
        state = state._replace(
            strm_pend_kind=jnp.where(s_wake, PEND_MUTEX,
                                     state.strm_pend_kind),
            strm_pend_addr=jnp.where(
                s_wake, state.strm_pend_aux.astype(jnp.int64),
                state.strm_pend_addr),
            strm_pend_issue=jnp.where(s_wake, s_wake_at - to_mcp_s,
                                      state.strm_pend_issue))
        woke_store_nc = jnp.zeros((NC,), dtype=bool).at[
            jnp.where(s_wake & ~s_bc, s_cid, NC)].set(True, mode="drop")
        woke_nc = woke_seat_nc | woke_store_nc
    else:
        woke_nc = woke_seat_nc
    woke_mine = _sel(oh_c, woke_nc.astype(jnp.int32)) > 0
    if params.cond_replay:
        # A token is lost only when no waiter for its cond is parked AND
        # no tile is runnable (nothing can still reach its COND_WAIT) —
        # sound for traces whose native run completed.
        any_runnable = (~state.done
                        & (state.pend_kind == PEND_NONE)).any()
        waiter_nc = dense.binsum(oh_c, is_cw, 1) > 0
        no_waiter = ~(_sel(oh_c, waiter_nc.astype(jnp.int32)) > 0)
        tok_done = tok_win & ((is_sig & woke_mine)
                              | (~any_runnable & no_waiter))
    else:
        tok_done = tok_win & ((t < lb_excl) | (is_sig & woke_mine))

    cycle_ps = _lat(1, _period(state, DVFSModule.CORE))
    from_mcp = noc.unicast_ps(params.net_user, jnp.full(T, mcp), rows,
                              CTRL_BYTES, p_nu[mcp], params.mesh_width,
                              vnet=vp.net_user)

    # Wake waiters: transform into mutex re-acquires; pend_issue is set so
    # resolve_mutex's (issue + to_mcp) lands exactly at the token time.
    c = state.counters
    state = state._replace(
        pend_kind=jnp.where(wake, PEND_MUTEX, state.pend_kind),
        pend_addr=jnp.where(wake, state.pend_aux.astype(jnp.int64),
                            state.pend_addr),
        pend_issue=jnp.where(wake, wake_at - to_mcp, state.pend_issue),
        counters=c._replace(
            # Stall charged here covers [park, handoff-to-mutex); the
            # mutex _unblock then adds [wake_at - to_mcp, completion) —
            # the to_mcp subtraction avoids double-counting that overlap.
            sync_stall_ps=c.sync_stall_ps + jnp.where(
                wake, jnp.maximum(wake_at - to_mcp - t, 0), 0)))
    # Ack the resolved posters.
    return _unblock(state, tok_done, t + from_mcp + cycle_ps, sync=True)


def resolve_join(params: SimParams, vp: VariantParams,
                 state: SimState) -> SimState:
    """Release joiners whose child stream has reached DONE (reference:
    ThreadManager join protocol via the MCP, thread_manager.cc)."""
    T = params.num_tiles
    rows = jnp.arange(T)
    is_j = state.pend_kind == PEND_JOIN
    # ``child`` is a STREAM id ([S] done_at; == tile when the scheduler
    # is off).  A seated child's done flag lives in the seat, not the
    # store — merge before the lookup.
    S_ids = state.done_at.shape[0]
    child = jnp.clip(state.pend_aux, 0, S_ids - 1)
    if state.sched_enabled:
        sdone = state.strm_done.at[state.seat_stream].set(state.done)
        child_done = sdone[child]
    else:
        child_done = state.done[child]
    child_done_at = state.done_at[child]
    ok = is_j & child_done
    p_nu = _period(state, DVFSModule.NETWORK_USER)
    cycle_ps = _lat(1, _period(state, DVFSModule.CORE))
    mcp = mcp_tile(params)
    to_mcp = noc.unicast_ps(params.net_user, rows, jnp.full(T, mcp),
                            CTRL_BYTES, p_nu, params.mesh_width,
                            vnet=vp.net_user)
    from_mcp = noc.unicast_ps(params.net_user, jnp.full(T, mcp), rows,
                              CTRL_BYTES, p_nu[mcp], params.mesh_width,
                              vnet=vp.net_user)
    exit_at_mcp = child_done_at + to_mcp[child % T]
    completion = jnp.maximum(state.pend_issue + to_mcp, exit_at_mcp) \
        + from_mcp + cycle_ps
    state = state._replace(counters=state.counters._replace(
        joins=state.counters.joins + jnp.where(
            ok & state.models_enabled, 1, 0)))
    return _unblock(state, ok, completion, sync=True)


def resolve_start(params: SimParams, vp: VariantParams,
                  state: SimState) -> SimState:
    """Release THREAD_START gates whose stream has been SPAWNed
    (spawned_at is stream-indexed; the seat's stream id maps it)."""
    is_s = state.pend_kind == PEND_START
    if state.sched_enabled:
        seat_spawned = state.spawned_at[state.seat_stream]
    else:
        seat_spawned = state.spawned_at
    ok = is_s & (seat_spawned >= 0)
    cycle_ps = _lat(1, _period(state, DVFSModule.CORE))
    completion = jnp.maximum(state.pend_issue, seat_spawned) + cycle_ps
    return _unblock(state, ok, completion, sync=True)


def _when_pending(kind: int, fn, params: SimParams, vp: VariantParams,
                  state: SimState) -> SimState:
    """Run a resolver only if some tile is parked on its pend kind —
    `lax.cond` skips the resolver's gathers/scatters entirely otherwise
    (a resolver sees only masked no-ops when nothing matches, so this is
    result-identical)."""
    return jax.lax.cond(
        (state.pend_kind == kind).any(),
        lambda s: fn(params, vp, s), lambda s: s, state)


def resolve(params: SimParams, state: SimState,
            vp: VariantParams = None) -> SimState:
    """One full cross-tile resolution pass.  resolve_cond runs before
    resolve_mutex so a freshly-woken waiter competes for its mutex
    re-acquire in the same pass.

    Two conditionals only — memory and one combined sync gate.  Each
    ``lax.cond`` costs pass-through buffer copies of the whole state on
    TPU, so per-kind gating (round 2's shape) paid ~7 state copies per
    sub-round; the per-kind resolvers are no-ops on empty masks anyway.

    ``vp`` threads the VARIANT timing operands (engine/vparams.py);
    omitted, it derives from ``params`` and traces as constants.
    """
    if vp is None:
        vp = variant_params(params)
    if params.miss_chain > 0:
        any_mem = (state.mq_count > 0).any()
    else:
        any_mem = ((state.pend_kind == PEND_SH_REQ)
                   | (state.pend_kind == PEND_EX_REQ)
                   | (state.pend_kind == PEND_IFETCH)).any()

    def mem_pass(s: SimState) -> SimState:
        return jax.lax.cond(
            any_mem, lambda x: resolve_memory(params, vp, x),
            lambda x: x, s)

    @jax.named_scope(scopes.RESOLVE_SYNC)
    def sync_pass(s: SimState) -> SimState:
        if s.has_capi:
            # Traces with no CAPI traffic carry zero-size channel arrays
            # (see make_state) — these resolvers would index them, and no
            # tile can park on RECV/SEND without CAPI events in the trace.
            s = _when_pending(PEND_RECV, resolve_recv, params, vp, s)
            s = _when_pending(PEND_SEND, resolve_send, params, vp, s)
        s = _when_pending(PEND_BARRIER, resolve_barrier, params, vp, s)
        # Cond resolution runs whenever waiters OR tokens are parked (a
        # lost signal must still expire and ack its poster with no waiter
        # around).
        s = jax.lax.cond(
            ((s.pend_kind == PEND_COND) | (s.pend_kind == PEND_CSIG)
             | (s.pend_kind == PEND_CBC)).any(),
            lambda x: resolve_cond(params, vp, x), lambda x: x, s)
        s = _when_pending(PEND_MUTEX, resolve_mutex, params, vp, s)
        s = _when_pending(PEND_JOIN, resolve_join, params, vp, s)
        s = _when_pending(PEND_START, resolve_start, params, vp, s)
        return s

    # ``any_sync`` may be read BEFORE the memory pass: resolve_memory
    # clears memory parks and serves chains but never creates or clears
    # a sync-kind park (all >= PEND_RECV), so the mask is identical on
    # either side of it.
    any_sync = (state.pend_kind >= PEND_RECV).any()   # every non-memory kind
    if params.fast_forward > 0:
        # Round-12 skip-when-empty guard: fast-forwarded sub-rounds
        # retire hit/compute spans that park NOTHING, so whole resolve
        # calls go empty on miss-free stretches — fold both passes under
        # one outer cond (inner conds preserved, result-identical) and
        # skip the state pass-through entirely.
        def both(s: SimState) -> SimState:
            return jax.lax.cond(any_sync, sync_pass, lambda x: x,
                                mem_pass(s))

        return jax.lax.cond(any_mem | any_sync, both, lambda s: s, state)
    state = mem_pass(state)
    return jax.lax.cond(any_sync, sync_pass, lambda s: s, state)
