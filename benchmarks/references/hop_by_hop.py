"""Plain reference of Graphite's contended hop-by-hop electrical mesh.

``harness/reference.py`` prices every unicast of the memory network by
hop count, as ``[network/emesh_hop_counter]`` does.  Under
``[network/emesh_hop_by_hop]`` with its queue model on, Graphite routes
each packet XY one hop at a time and queues it at every link it crosses
(``network_model_emesh_hop_by_hop.cc``; per-link queue models,
``queue_model_history_list.cc`` / ``_history_tree.cc`` / ``_basic.cc``).
This module models that for the engine's exact schedule, and nothing
else: every other part of the model is ``reference.ReferenceSim``'s.

What it models, from the configuration's ``model.noc``:

  ``queue_model``  ``history_list`` or ``history_tree``: each directed
                   link keeps a ring of ``ring_slots`` busy intervals
                   (Graphite's max_list_size).
                   A packet reaching a link starts at its arrival, or at
                   the end of a recorded interval that its flits would
                   overlap (chased three times); same-link packets of one
                   hop of one batch serialize FCFS by arrival, ties by
                   row; each link records one merged interval [first
                   start, last end] per hop of a batch, in the slot after
                   its last, the oldest overwritten.
                   ``basic``: one horizon per link; a packet starts no
                   earlier than it, and the horizon moves to the latest
                   end.  Absent or null: no contention, and the class is
                   the default reference.
  ``broadcast_tree``  must be true: invalidation multicasts stay
                   zero-load (the default reference's ``max_hop``).

A packet's head crosses a link at its start and takes router + link
cycles per hop; the link is busy for the packet's flits; the tail's
flits - 1 cycles are charged once at the destination.  The legs of one
conflict round fly as separate batches, in the engine's order: request,
victim flush (out and back), owner leg (out and back), reply.  The wait
each packet spends queued is summed per requester in
``net_link_wait_ps``.

Each hop is vectorised over the packets in flight: one sort by (link,
arrival, row), a segmented running max for the serialization, and one
write per link into the rings.  It imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

import reference
from reference import (BIG, CTRL_BYTES, DIR_OWNER_FIELD, DIR_STAMP_FIELD, I,
                       M, O, PEND_EX, PEND_IF, PEND_NONE, S, bitmap_to_bool,
                       elect, fmix64, grouped_rank, tile_bits)

HISTORY = ("history_list", "history_tree")
QUEUE_MODELS = HISTORY + ("basic",)
CHASES = 3                  # interval ends chased per probe
NUM_DIRS = 4                # outgoing links per tile: E, W, N, S


class ReferenceSim(reference.ReferenceSim):
    """``reference.ReferenceSim`` on the contended mesh."""

    def __init__(self, model: dict, events: dict):
        super().__init__(model, events)
        n = model["noc"]
        self.qtype = n.get("queue_model")
        if self.qtype is None:
            return
        if self.qtype not in QUEUE_MODELS:
            raise ValueError(f"link queue model {self.qtype!r} is not "
                             f"modelled (one of {QUEUE_MODELS})")
        if not n.get("broadcast_tree"):
            raise ValueError("only the broadcast tree is modelled: "
                             "multicasts go zero-load")
        L = NUM_DIRS * self.T
        R = n["ring_slots"] if self.qtype in HISTORY else 1
        self.link_s = np.zeros((R, L), np.int64)
        self.link_e = np.zeros((R, L), np.int64)
        self.link_p = np.zeros(L, np.int64)
        self.ctr["net_link_wait_ps"] = np.zeros(self.T, np.int64)
        # What the bounded ring leaves out, for the record only: the
        # latest end each link's overwritten intervals had, and per probe
        # (packet at a link) whether it reached the link before that end
        # (Graphite would price it by its M/G/1 fallback), and whether a
        # further chase would have moved its start.
        self.link_forgot = np.zeros(L, np.int64)
        self.link_diag = {"probes": 0, "before_forgotten": 0,
                          "chase_short": 0}

    # ------------------------------------------------------------ network

    def fly(self, src, dst, depart, payload, active):
        """One batch of packets src -> dst, hop by hop over the queued
        links; returns (tail arrival, time spent queued), 0 where not
        active."""
        T, w, P = self.T, self.mesh_w, self.P
        flits = self.flits(payload)
        svc = flits * P
        pos = np.asarray(src, np.int64).copy()
        dst = np.asarray(dst, np.int64)
        t = np.where(active, depart, 0)
        wait = np.zeros(T, np.int64)
        rows = np.flatnonzero(active & (pos != dst))
        while rows.size:
            p, d = pos[rows], dst[rows]
            px, py, dx, dy = p % w, p // w, d % w, d // w
            east, west = px < dx, px > dx
            north = ~east & ~west & (py < dy)
            south = ~east & ~west & ~north
            dirn = np.select([east, west, north], [0, 1, 2], 3)
            step = np.select([east, west, north, south],
                             [1, -1, w, -w])
            start = self.link_probe(dirn * T + p, t[rows], svc, rows)
            wait[rows] += start - t[rows]
            t[rows] = start + self.hop_cycles * P
            pos[rows] = p + step
            rows = rows[pos[rows] != d]
        arrival = np.where(active, t + max(flits - 1, 0) * P, 0)
        return arrival, wait

    def link_probe(self, link, arr, svc, rows):
        """Start times of packets reaching ``link`` at ``arr`` (one hop of
        one batch; ``rows`` break ties), recorded into the link queues."""
        if self.qtype in HISTORY:
            rs, re = self.link_s[:, link], self.link_e[:, link]
            base = arr
            for _ in range(CHASES):
                over = (rs < base + svc) & (base < re)
                base = np.where(over, re, base).max(axis=0)
            over = (rs < base + svc) & (base < re)
            d = self.link_diag
            d["probes"] += link.size
            d["before_forgotten"] += int((arr < self.link_forgot[link]).sum())
            d["chase_short"] += int(over.any(axis=0).sum())
        else:
            base = np.maximum(arr, self.link_e[0, link])
        order = np.lexsort((rows, arr, link))
        ls, bs = link[order], base[order]
        first = np.r_[True, ls[1:] != ls[:-1]]
        seg = np.cumsum(first) - 1
        head = np.flatnonzero(first)
        rank = np.arange(ls.size) - head[seg]
        cand = bs - rank * svc
        lo = int(cand.min())
        span = int(cand.max()) - lo + 1
        if int(seg[-1]) * span >= 2 ** 62:
            raise OverflowError("arrival spread too wide for one hop")
        key = seg * span + (cand - lo)
        start_s = np.maximum.accumulate(key) - seg * span + lo \
            + rank * svc
        tail = np.r_[head[1:], ls.size] - 1
        links = ls[head]
        if self.qtype in HISTORY:
            slot = self.link_p[links] % self.link_s.shape[0]
            self.link_forgot[links] = np.maximum(
                self.link_forgot[links], self.link_e[slot, links])
            self.link_s[slot, links] = start_s[head]
            self.link_e[slot, links] = start_s[tail] + svc
            self.link_p[links] += 1
        else:
            self.link_e[0, links] = np.maximum(self.link_e[0, links],
                                               start_s[tail] + svc)
        start = np.empty_like(start_s)
        start[order] = start_s
        return start

    # ------------------------------------------------------------ resolve

    def _conflict_round(self, ftbl):
        """``reference.ReferenceSim._conflict_round``, its unicast legs
        flown over the contended mesh (``fly``) instead of priced by hop
        count; every other line as there."""
        if self.qtype is None:
            return super()._conflict_round(ftbl)
        T, A, W, H = self.T, self.A, self.W, self.H
        rows = self.rows
        c = self.ctr
        rstamp = self.round_ctr * self.stride_st + self.stride_st - 1
        kind = self.pend
        line = self.pend_addr >> self.line_bits
        issue = self.pend_issue
        extra = self.pend_extra
        unres = self._parked()
        is_ex = unres & (kind == PEND_EX)
        is_if = unres & (kind == PEND_IF)
        home = self.home(line)
        dset = self.dir_set(line)
        fidx = home * self.ndsets + dset
        issue0 = np.where(unres, issue, BIG).min()
        packed = np.clip(issue - issue0, 0, 2 ** 40) * T + rows
        hidx = (fmix64(line) % np.uint64(H)).astype(np.int64)
        g = ftbl[:, hidx]
        line_floor = np.where(g[0] == line, g[1], 0)

        win = elect(unres, packed, hidx, H)

        drow = self.dir_word[:, fidx].T                      # [T, A]
        dstate = drow & 7
        dstamp = (drow >> 16) & DIR_STAMP_FIELD
        match = ((drow >> 33) == line[:, None]) & (dstate != I)
        hit = match.any(1)
        hway = match.argmax(1)
        invalid = dstate == I
        hitwin = win & hit
        misswin = win & ~hit
        grank = grouped_rank(fidx, packed, misswin)
        fhash = (fmix64(fidx) % np.uint64(H)).astype(np.int64)
        used = np.zeros((H, A), bool)
        used[fhash[hitwin], hway[hitwin]] = True
        hway_used = used[fhash]
        vkey = np.where(hway_used, 2 ** 31 - 1, np.where(invalid, -1, dstamp))
        eligible = ~hway_used
        arA = np.arange(A)
        pos = (eligible[:, None, :]
               & ((vkey[:, None, :] < vkey[:, :, None])
                  | ((vkey[:, None, :] == vkey[:, :, None])
                     & (arA[None, None, :] < arA[None, :, None])))).sum(2)
        n_elig = eligible.sum(1)
        miss_way = (eligible & (pos == grank[:, None])).argmax(1)
        can_alloc = misswin & (grank < n_elig)
        way = np.where(hit, hway, miss_way)
        am = (home * self.ndsets + dset) * A + way
        aidx = (fmix64(am) % np.uint64(H)).astype(np.int64)
        alloc_defer = win & ((misswin & ~can_alloc)
                             | ~elect(win, packed, aidx, H))
        win = win & ~alloc_defer
        misswin = misswin & ~alloc_defer

        way_word = drow[rows, way]
        way_state = way_word & 7
        way_owner = ((way_word >> 3) & DIR_OWNER_FIELD) - 1
        evicting = misswin & (way_state != I)
        dsh = self.dir_sharers[:, fidx].reshape(W, A, T).transpose(2, 1, 0)
        sel_sh = dsh[rows, way]                               # [T, W]
        entry_state = np.where(hit, way_state, I)
        entry_owner = np.where(hit, way_owner, -1)
        entry_sharers = np.where(hit[:, None], sel_sh, np.uint64(0))
        vtag = way_word >> 33
        vstate = np.where(evicting, way_state, I)
        vowner = way_owner
        vsharers = sel_sh
        evict_m = evicting & (vstate == M) & (vowner >= 0)
        evict_s = evicting & ((vstate == S) | (vstate == O)) \
            & (vsharers != 0).any(1)

        # The MSI directory transition.
        req_bit = tile_bits(rows, W)
        owner_leg_a = (entry_state == M) & (entry_owner >= 0) \
            & (entry_owner != rows)
        sh_sharers = np.where((entry_state == M)[:, None],
                              tile_bits(np.maximum(entry_owner, 0), W)
                              | req_bit, entry_sharers | req_bit)
        new_state = np.where(is_ex, M, S)
        new_owner = np.where(is_ex, rows, -1)
        new_sharers = np.where(is_ex[:, None], req_bit, sh_sharers)
        inv_targets = np.where((is_ex & (entry_state == S))[:, None],
                               entry_sharers & ~req_bit, np.uint64(0))
        owner = np.maximum(entry_owner, 0)
        owner_down = np.where(is_ex, I, S)
        dram_read_a = ~owner_leg_a
        dram_write_a = owner_leg_a

        has_inv = win & (inv_targets != 0).any(1)
        vown = np.maximum(vowner, 0)
        need_fan = has_inv | evict_s
        fan_rank = grouped_rank(np.zeros(T, np.int64), packed, need_fan)
        sel0 = need_fan & (fan_rank < self.fan_k)
        fan_defer = need_fan & ~sel0
        win1 = win & ~fan_defer
        owner_leg1 = owner_leg_a & win1
        evict_m1 = evict_m & ~fan_defer
        tgt2 = np.concatenate([owner, vown])
        val2 = np.concatenate([owner_leg1, evict_m1])
        key2 = np.concatenate([packed, packed])
        posr = grouped_rank(tgt2, key2, val2)
        over2 = val2 & (posr >= self.j_own)
        ow_defer = over2[:T] | over2[T:]
        win = win1 & ~ow_defer
        has_inv = has_inv & ~fan_defer & ~ow_defer
        evict_m = evict_m1 & ~ow_defer
        evict_s = evict_s & ~fan_defer & ~ow_defer
        evicting = evicting & ~fan_defer & ~ow_defer
        evict_o = evicting & (vstate == O)
        owner_leg = owner_leg1 & ~ow_defer
        val2 = np.concatenate([owner_leg, evict_m])

        J = self.j_own
        lines2 = np.concatenate([line, vtag])
        down2 = np.concatenate([owner_down, np.full(T, I)])
        put = val2 & (posr < J)
        slot2 = np.minimum(posr, J - 1)
        own_lines = np.zeros((T, J), np.int64)
        own_valid = np.zeros((T, J), bool)
        own_tgt = np.zeros((T, J), np.int64)
        own_lines[tgt2[put], slot2[put]] = lines2[put]
        own_valid[tgt2[put], slot2[put]] = True
        own_tgt[tgt2[put], slot2[put]] = down2[put]

        # Concurrent shared reads of one I/S entry all win beside the
        # elected one, when no exclusive request for the line is pending.
        sh_ok = (entry_state == I) | (entry_state == S)
        ex_unres = unres & is_ex
        rep_sh = win & ~is_ex & sh_ok
        any_ex = np.zeros(H, bool)
        any_ex[hidx[ex_unres]] = True
        rline = np.full(H, -1, np.int64)
        np.maximum.at(rline, hidx[rep_sh], line[rep_sh])
        rway = np.full(H, -1, np.int64)
        np.maximum.at(rway, hidx[rep_sh], way[rep_sh])
        combinable = unres & ~win & ~is_ex & sh_ok & ~any_ex[hidx] \
            & (rline[hidx] == line)
        win = win | combinable
        way = np.where(combinable, rway[hidx], way)
        req_word = rows // 64
        req_bit1 = np.uint64(1) << (rows % 64).astype(np.uint64)
        own_word = entry_sharers[rows, req_word]
        sharer_add = combinable & ((own_word & req_bit1) == 0)

        # Fan-out slots: the selected requests, in FCFS order.
        sel = sel0 & ~ow_defer
        rank = np.cumsum(sel) - 1
        Kf = self.fan_k
        oh = sel[None, :] & (np.arange(Kf)[:, None] == rank[None, :])
        has_slot = oh.any(1)
        slot_t = oh.argmax(1)

        def sr(vals):
            return np.where(has_slot, vals[slot_t], 0)

        inv_words = np.where((has_slot & has_inv[slot_t])[:, None],
                             inv_targets[slot_t], np.uint64(0))
        vic_words = np.where((has_slot & evict_s[slot_t])[:, None],
                             vsharers[slot_t], np.uint64(0))
        inv_bool = bitmap_to_bool(inv_words, T)
        vic_bool = bitmap_to_bool(vic_words, T)
        home_sr = sr(home)
        ack_sr = np.where(has_slot, self.ack_ps, 0)
        inv_k = 2 * self.max_hop(home_sr, inv_bool, CTRL_BYTES) + ack_sr
        vic_k = 2 * self.max_hop(home_sr, vic_bool, CTRL_BYTES) + ack_sr
        slot_of = np.where(sel, rank, 0)
        inv_ps = np.where(has_inv, inv_k[slot_of], 0)
        evict_ps = np.where(evict_s, vic_k[slot_of], 0)

        # Latency of each served request: every unicast leg flies the
        # contended mesh, in the engine's order (request, victim flush,
        # owner leg, reply); the invalidation multicasts above stay
        # zero-load under the broadcast tree.
        data = self.line_size + CTRL_BYTES
        fly_req, wait = self.fly(rows, home, issue, CTRL_BYTES, win)
        arrive = np.maximum(fly_req, line_floor)
        dep_ev = arrive + self.dir_ps
        e1, w1 = self.fly(home, vown, dep_ev, CTRL_BYTES, evict_m)
        e2, w2 = self.fly(vown, home, e1 + self.l2_ps, data, evict_m)
        evict_m_ps = np.where(evict_m, e2 - dep_ev, 0)
        evict_ps = np.where(evict_m, evict_m_ps, evict_ps)
        t_dir = arrive + self.dir_ps + np.where(evicting, evict_ps, 0)
        g1, w3 = self.fly(home, owner, t_dir, CTRL_BYTES, owner_leg)
        g2, w4 = self.fly(owner, home, g1 + self.l2_ps, data, owner_leg)
        owner_ps = np.where(owner_leg, g2 - t_dir, 0)
        need_read = win & dram_read_a
        dram_arr = t_dir + owner_ps
        dram_wb = (dram_write_a & win) | evict_m | evict_o
        q_start = self._dram_probe(home, dram_arr, need_read, dram_wb)
        dram_start = np.where(need_read, q_start, 0)
        dram_ready = dram_start + self.dram_lat + self.dram_svc
        t_data = t_dir + owner_ps
        t_data = np.maximum(t_data, np.where(need_read, dram_ready, 0))
        t_data = np.maximum(t_data, t_dir + inv_ps)
        reply_done, w5 = self.fly(home, rows, t_data, data, win)
        c["net_link_wait_ps"] += wait + w1 + w2 + w3 + w4 + w5
        l1_fill = np.where(is_if, self.l1i_ps, self.l1d_ps)
        completion = reply_done + self.l2_ps + l1_fill + extra

        # Directory entry writes.
        dword = (line << 33) | ((self.round_ctr & DIR_STAMP_FIELD) << 16) \
            | ((new_owner + 1) << 3) | new_state
        self.dir_word[way[win], fidx[win]] = dword[win]
        old_row = np.where(hit[:, None], entry_sharers, vsharers)
        delta = new_sharers - old_row
        rep = win & ~combinable
        planes = np.arange(W)[:, None] * A + way[None, :]           # [W, T]
        np.add.at(self.dir_sharers,
                  (planes[:, rep].reshape(-1),
                   np.broadcast_to(fidx[rep][None, :],
                                   (W, int(rep.sum()))).reshape(-1)),
                  delta[rep].T.reshape(-1))
        np.add.at(self.dir_sharers,
                  ((req_word * A + way)[sharer_add], fidx[sharer_add]),
                  req_bit1[sharer_add])

        # Coherence deliveries into the other tiles' L2 and L1-D.
        line_sr = sr(line)
        vtag_sr = sr(vtag)
        dl = np.concatenate([own_lines,
                             np.broadcast_to(line_sr[None, :], (T, Kf)),
                             np.broadcast_to(vtag_sr[None, :], (T, Kf))], 1)
        dv = np.concatenate([own_valid, inv_bool.T, vic_bool.T], 1)
        dtg = np.concatenate([own_tgt, np.full((T, 2 * Kf), I)], 1)
        self.l2.deliver(dl, dv, dtg)
        self.l1d.deliver(dl, dv, dtg)

        # Requester fills (inclusive L2: an L2 victim leaves L1-D too).
        vt1, vs1 = self.l2.fill(line, np.where(is_ex, M, S), win, rstamp)
        self.l1d.deliver(vt1[:, None], (win & (vs1 != I))[:, None],
                         np.full((T, 1), I))
        self.l1d.fill(line, np.where(is_ex, M, S), win & ~is_if, rstamp)
        self.l1i.fill(line, np.full(T, S), win & is_if, rstamp)
        victim_dirty = win & ((vs1 == M) | (vs1 == O))
        victim_live = win & (vs1 != I)
        victim_home = self.home(vt1)
        self._record(victim_home, victim_dirty, t_dir, t_dir + self.dram_svc)
        self._evict_notify(vt1, victim_live)

        # Counters, binned at the home tile.
        kcnt = inv_bool.sum(1) + vic_bool.sum(1)
        inv_count = np.where(sel, kcnt[slot_of], 0)
        flits_req = self.flits(CTRL_BYTES)
        flits_data = self.flits(self.line_size + CTRL_BYTES)

        def at_home(name, vals):
            np.add.at(c[name], home, np.asarray(vals).astype(np.int64))

        at_home("dir_sh_req", win & ~is_ex)
        at_home("dir_ex_req", win & is_ex)
        at_home("dir_invalidations", inv_count)
        at_home("dir_writebacks", owner_leg | evict_m | evict_o)
        at_home("dir_forwards", owner_leg & ~dram_write_a)
        at_home("dir_evictions", evicting)
        at_home("net_mem_pkts", win + inv_count)
        at_home("net_mem_flits", np.where(win, flits_data, 0)
                + inv_count * flits_req)
        at_home("dir_deferrals", alloc_defer | fan_defer | ow_defer)
        at_home("dram_reads", need_read)
        at_home("dram_writes", dram_wb)
        np.add.at(c["dram_writes"], victim_home, victim_dirty.astype(np.int64))
        c["net_mem_pkts"] += win.astype(np.int64) + victim_dirty
        c["net_mem_flits"] += np.where(win, flits_req, 0) \
            + np.where(victim_dirty, flits_data, 0)

        # Served requests unpark at their completion.
        c["mem_stall_ps"] += np.where(win, completion - self.pend_issue, 0)
        self.clock = np.where(win, completion, self.clock)
        self.cursor = self.cursor + win
        self.pend = np.where(win, PEND_NONE, self.pend)

        # Per-line availability floor for the requests still pending.
        if self.dense:
            new_line = np.full(H, -1, np.int64)
            np.maximum.at(new_line, hidx[win], line[win])
            new_t = np.zeros(H, np.int64)
            np.maximum.at(new_t, hidx[win], t_data[win])
            wrote = np.zeros(H, bool)
            wrote[hidx[win]] = True
            ftbl = np.where(wrote[None, :], np.stack([new_line, new_t]),
                            ftbl)
        else:
            ftbl = ftbl.copy()
            ftbl[0, hidx[win]] = line[win]
            ftbl[1, hidx[win]] = t_data[win]
        self.round_ctr += 1
        self.rounds["ctr_conflict"] += 1
        return ftbl
