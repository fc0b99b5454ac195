"""Plain reference of the exact timing model, in numpy.

A second, independent implementation of what graphite_tpu's exact engine
(``tpu/miss_chain = 0``, ``tpu/fast_forward = 0``) computes for the
targets and traffic of this benchmark: a tiled many-core with a simple
in-order core, private L1-I / L1-D / L2 caches, a full-map MSI DRAM
directory, an electrical mesh priced by hop count, a history-list DRAM
queue, and lax-barrier quanta.  It imports nothing of the program and
takes nothing the program made: it reads the event arrays the benchmark's
own generator produced and the model numbers in the configuration file.

The engine's results depend on its schedule, not only on the model: a
quantum runs at most ``rounds_per_quantum`` sub-rounds of (local advance;
resolve), local advance at most ``max_events_per_quantum`` rounds of
(block windows of ``block_events`` events; one general slot), and the
directory at most ``directory_conflict_rounds`` FCFS conflict rounds per
resolve pass, with hashed same-line elections and fan-out budgets.  That
schedule is part of the exact program's definition, so it is spelled out
here too, step for step, on plain arrays.

Only the event kinds the benchmark's traffic emits are modelled:
COMPUTE, MEM_READ, MEM_WRITE, BARRIER_WAIT, DONE (and NOP padding).
"""

from __future__ import annotations

import numpy as np

# Event opcodes of the trace format (graphite's per-tile event schema).
NOP, COMPUTE, MEM_READ, MEM_WRITE, DONE, BARRIER = 0, 1, 2, 3, 12, 13
SUPPORTED_OPS = (NOP, COMPUTE, MEM_READ, MEM_WRITE, DONE, BARRIER)

# Cache-line and directory states.
I, S, O, E, M = 0, 1, 2, 3, 4

# Why a tile is parked.
PEND_NONE, PEND_SH, PEND_EX, PEND_IF, PEND_BAR = 0, 1, 2, 3, 5

BIG = 2 ** 62
STAMP_FIELD = (1 << 29) - 1           # cache LRU stamp bits
STAMP_MASK = STAMP_FIELD << 3
DIR_STAMP_FIELD = (1 << 17) - 1       # directory LRU stamp bits
DIR_OWNER_FIELD = (1 << 13) - 1
CTRL_BYTES = 8                        # coherence control payload
HEADER_BYTES = 8                      # packet header on the wire
FMIX_C = np.uint64(0xFF51AFD7ED558CCD)

COUNTERS = (
    "icount", "l1i_access", "l1i_miss", "l1d_read", "l1d_read_miss",
    "l1d_write", "l1d_write_miss", "l2_access", "l2_miss", "dir_sh_req",
    "dir_ex_req", "dir_invalidations", "dir_writebacks", "dir_forwards",
    "dir_evictions", "dir_deferrals", "dram_reads", "dram_writes",
    "net_mem_pkts", "net_mem_flits", "barriers", "mem_stall_ps",
    "sync_stall_ps",
)


def fmix64(x):
    x = np.asarray(x).astype(np.uint64)
    x = x ^ (x >> np.uint64(33))
    x = x * FMIX_C
    return x ^ (x >> np.uint64(33))


def fold(line, n):
    """Line -> slot in [0, n), the bits above the slot index XOR-folded in
    first."""
    bits = max(n.bit_length() - 1, 1)
    x = line ^ (line >> bits) ^ (line >> (2 * bits)) ^ (line >> (3 * bits))
    return x % n


def grouped_rank(group, key, active):
    """Rank of each active row among the active rows of its group, ordered
    by (key, row); 0 for inactive rows."""
    out = np.zeros(key.shape[0], np.int64)
    a = np.flatnonzero(active)
    if a.size == 0:
        return out
    o = a[np.lexsort((a, key[a], group[a]))]
    g = group[o]
    first = np.r_[True, g[1:] != g[:-1]]
    start = np.maximum.accumulate(np.where(first, np.arange(o.size), 0))
    out[o] = np.arange(o.size) - start
    return out


def elect(active, key, idx, size):
    """The smallest key among the active rows of each idx slot wins."""
    tbl = np.full(size, BIG, np.int64)
    np.minimum.at(tbl, idx[active], key[active])
    return active & (tbl[idx] == key)


def tile_bits(tiles, W):
    out = np.zeros((tiles.shape[0], W), np.uint64)
    out[np.arange(tiles.shape[0]), tiles // 64] = \
        np.uint64(1) << (tiles % 64).astype(np.uint64)
    return out


def bitmap_to_bool(words, T):
    K, W = words.shape
    bits = (words[:, :, None] >> np.arange(64, dtype=np.uint64)) \
        & np.uint64(1)
    return bits.reshape(K, W * 64)[:, :T].astype(bool)


class Cache:
    """One cache level of every tile: [ways, tiles, sets] packed words
    (tag << 32 | stamp << 3 | state); LRU by stamp."""

    def __init__(self, ways, tiles, sets):
        self.w = np.zeros((ways, tiles, sets), np.int64)
        self.sets = sets

    def probe(self, line):
        sidx = line % self.sets
        rows = np.arange(line.shape[0])
        if line.ndim == 2:
            rows = rows[:, None]
        row = self.w[:, rows, sidx]
        st = row & 7
        match = ((row >> 32) == line) & (st != I)
        hit = match.any(0)
        way = match.argmax(0)
        state = np.where(hit, np.where(match, st, 0).sum(0), I)
        return hit, way, state, sidx, row

    def touch(self, sidx, way, active, word, stamp):
        rows = np.broadcast_to(
            np.arange(sidx.shape[0]).reshape((-1,) + (1,) * (sidx.ndim - 1)),
            sidx.shape)
        stamp = np.broadcast_to(stamp, sidx.shape)
        new = (word & ~STAMP_MASK) | ((stamp & STAMP_FIELD) << 3)
        np.maximum.at(self.w, (way[active], rows[active], sidx[active]),
                      new[active])

    def fill(self, line, new_state, active, stamp):
        """Install one line per tile where active; returns the victim's
        (tag, state), state I where nothing live was evicted."""
        T = line.shape[0]
        rows = np.arange(T)
        sidx = line % self.sets
        row = self.w[:, rows, sidx]
        st = row & 7
        resident = ((row >> 32) == line) & (st != I)
        has_res = resident.any(0)
        invalid = st == I
        way = np.where(has_res, resident.argmax(0),
                       np.where(invalid.any(0), invalid.argmax(0),
                                ((row >> 3) & STAMP_FIELD).argmin(0)))
        vic = row[way, rows]
        vtag = vic >> 32
        vstate = np.where(active & ~has_res, vic & 7, I)
        eff = np.where(has_res, np.maximum(new_state, vic & 7), new_state)
        new = (line << 32) | ((np.int64(stamp) & STAMP_FIELD) << 3) | eff
        self.w[way[active], rows[active], sidx[active]] = new[active]
        return vtag, vstate

    def deliver(self, lines, valid, down):
        """Coherence deliveries: [T, J] lines addressed to each tile; a
        matching line drops to ``down`` (never raised; the lowest of
        several deliveries to one line wins)."""
        T, J = lines.shape
        tiles = np.repeat(np.arange(T), J)
        lines, valid, down = lines.reshape(-1), valid.reshape(-1), \
            down.reshape(-1)
        sidx = lines % self.sets
        row = self.w[:, tiles, sidx]
        st = row & 7
        match = ((row >> 32) == lines) & (st != I) & valid
        hit = match.any(0)
        way = match.argmax(0)
        cur = row[way, np.arange(lines.size)]
        new = (cur & ~np.int64(7)) | np.minimum(cur & 7, down)
        np.minimum.at(self.w, (way[hit], tiles[hit], sidx[hit]), new[hit])


class ReferenceSim:
    """The exact model, quantum by quantum.  ``events`` is the dict of
    [T, N] arrays (ops, addr, arg, arg2); ``model`` the configuration
    file's ``model`` block."""

    def __init__(self, model: dict, events: dict):
        m = model
        self.ops = np.asarray(events["ops"], np.int64)
        self.addr = np.asarray(events["addr"], np.int64)
        self.arg = np.asarray(events["arg"], np.int64)
        self.arg2 = np.asarray(events["arg2"], np.int64)
        bad = ~np.isin(self.ops, SUPPORTED_OPS)
        if bad.any():
            raise ValueError(f"reference models ops {SUPPORTED_OPS}; trace "
                             f"has {sorted(set(self.ops[bad].tolist()))}")
        T, N = self.ops.shape
        self.T, self.N = T, N
        self.rows = np.arange(T)
        self.mesh_w = int(np.floor(np.sqrt(T)))
        P = self.P = m["period_ps"]                 # every clock domain
        self.q = m["quantum_ps"]
        self.line_size = m["line_size"]
        self.line_bits = self.line_size.bit_length() - 1
        c1i, c1d, c2 = m["l1i"], m["l1d"], m["l2"]
        self.l1i = Cache(c1i["ways"], T, c1i["sets"])
        self.l1d = Cache(c1d["ways"], T, c1d["sets"])
        self.l2 = Cache(c2["ways"], T, c2["sets"])
        self.l1i_ps = c1i["access_cycles"] * P
        self.l1d_ps = c1d["access_cycles"] * P
        self.l2_ps = c2["access_cycles"] * P
        self.l2_tag_ps = c2["tags_cycles"] * P
        d = m["directory"]
        self.A = d["ways"]
        self.ndsets = d["sets"]
        self.dir_ps = d["access_cycles"] * P
        self.ack_ps = d["inv_ack_cycles"] * P
        self.W = (T + 63) // 64
        self.dir_word = np.zeros((self.A, T * self.ndsets), np.int64)
        self.dir_sharers = np.zeros((self.W * self.A, T * self.ndsets),
                                    np.uint64)
        dr = m["dram"]
        self.nctl = T if dr["controllers"] == "all" else dr["controllers"]
        self.stride = max(1, T // self.nctl)
        self.dram_lat = dr["latency_ps"]
        self.dram_svc = dr["service_ps"]
        R = dr["ring_slots"]
        self.ring_s = np.zeros((R, T), np.int64)
        self.ring_e = np.zeros((R, T), np.int64)
        self.ring_p = np.zeros(T, np.int64)
        n = m["noc"]
        self.hop_cycles = n["router_cycles"] + n["link_cycles"]
        self.flit_bits = n["flit_bits"]
        e = m["engine"]
        self.K = e["block_events"]
        self.max_ev = e["max_events_per_quantum"]
        self.max_sub = e["rounds_per_quantum"]
        self.max_conf = e["directory_conflict_rounds"]
        self.fan_k = min(e["max_inv_fanout_per_round"], T)
        self.j_own = e["owner_deliveries_per_round"]
        self.stride_st = e["stamp_stride"]
        NB = e["barriers"]
        self.H = max(1024, 16 * T)
        self.dense = T * self.H <= (1 << 22)

        self.clock = np.zeros(T, np.int64)
        self.cursor = np.zeros(T, np.int64)
        self.done = np.zeros(T, bool)
        self.boundary = self.q
        self.pend = np.zeros(T, np.int64)
        self.pend_addr = np.zeros(T, np.int64)
        self.pend_issue = np.zeros(T, np.int64)
        self.pend_aux = np.zeros(T, np.int64)
        self.pend_extra = np.zeros(T, np.int64)
        self.bar_count = np.zeros(NB, np.int64)
        self.bar_time = np.zeros(NB, np.int64)
        self.ctr = {k: np.zeros(T, np.int64) for k in COUNTERS}
        self.round_ctr = 0
        self.rounds = {"ctr_quantum": 0, "ctr_window": 0, "ctr_complex": 0,
                       "ctr_conflict": 0, "ctr_resolve": 0}

    # ------------------------------------------------------------ network

    def flits(self, payload):
        return ((payload + HEADER_BYTES) * 8 + self.flit_bits - 1) \
            // self.flit_bits

    def hops(self, src, dst):
        w = self.mesh_w
        return np.abs(src % w - dst % w) + np.abs(src // w - dst // w)

    def unicast(self, src, dst, payload):
        return (self.hops(src, dst) * self.hop_cycles
                + max(self.flits(payload) - 1, 0)) * self.P

    def max_hop(self, src, mask, payload):
        h = self.hops(src[:, None], self.rows[None, :])
        mh = np.where(mask, h, 0).max(axis=1)
        cyc = mh * self.hop_cycles + max(self.flits(payload) - 1, 0)
        return np.where(mask.any(axis=1), cyc, 0) * self.P

    # ------------------------------------------------------------ quantum

    def all_done(self):
        return bool(self.done.all())

    def step_quantum(self):
        runnable = ~self.done & (self.pend < 4)
        if runnable.any():
            mn = int(np.where(runnable, self.clock, BIG).min())
            self.boundary = (mn // self.q + 1) * self.q
        else:
            self.boundary = self.boundary + self.q
        self.rounds["ctr_quantum"] += 1
        i, prev, cur = 0, -1, self._progress()
        while i < self.max_sub and (i == 0 or cur > prev):
            self.local_advance()
            self.resolve()
            i, prev, cur = i + 1, cur, self._progress()

    def _progress(self):
        return int(self.cursor.sum()) + int(self.clock.sum())

    def run(self, stop_quanta=None):
        """Advance until every tile is done, or until ``stop_quanta``
        quanta have run in all; returns the digest."""
        while not self.all_done():
            if stop_quanta is not None \
                    and self.rounds["ctr_quantum"] >= stop_quanta:
                break
            self.step_quantum()
        return self.digest()

    def digest(self):
        return {
            "ctr_quantum": int(self.rounds["ctr_quantum"]),
            "completion_time_ps": int(self.clock.max()),
            "clock": self.clock.copy(),
            "cursor": self.cursor.copy(),
            "done": self.done.copy(),
            "counters": {k: v.copy() for k, v in self.ctr.items()},
            "round_ctrs": dict(self.rounds, round_ctr=self.round_ctr),
        }

    # ------------------------------------------------------ local advance

    def local_advance(self):
        def csum():
            return int(self.cursor.sum())

        i, prev, cur = 0, -1, csum()
        while i < self.max_ev and (i == 0 or cur > prev):
            if self.K > 0:
                j, pv, cv = 0, -1, cur
                while j < self.max_ev and (j == 0 or cv > pv):
                    self.block_retire()
                    j, pv, cv = j + 1, cv, csum()
            self.complex_slot()
            i, prev, cur = i + 1, cur, csum()

    def _active(self):
        return ~self.done & (self.pend == PEND_NONE) \
            & (self.clock < self.boundary) & (self.cursor < self.N)

    def block_retire(self):
        """One window round: every active tile retires the leading run of
        its next K events that are compute or cache hits (or single fills
        from its own L2), stopping at a miss, a barrier, the quantum
        boundary, or a same-set fill hazard inside the window."""
        K, T, N, P = self.K, self.T, self.N, self.P
        active = self._active()
        self.rounds["ctr_window"] += 1
        if not active.any():            # a round that retires nothing
            self.round_ctr += 1
            return
        ar = np.arange(K)
        pos = self.cursor[:, None] + ar[None, :]
        idx = np.minimum(pos, N - 1)
        r = self.rows[:, None]
        valid = (pos < N) & active[:, None]
        op = np.where(valid, self.ops[r, idx], NOP)
        addr = self.addr[r, idx]
        arg = self.arg[r, idx]
        arg2 = self.arg2[r, idx]
        line = addr >> self.line_bits
        is_comp = op == COMPUTE
        is_rd = op == MEM_READ
        is_wr = op == MEM_WRITE
        is_mem = is_rd | is_wr
        hI, wI, _, setI, rowI = self.l1i.probe(line)
        hD, wD, sD, setD, rowD = self.l1d.probe(line)
        h2, w2, s2, set2, row2 = self.l2.probe(line)
        l1_ok = hD & (is_rd | (sD >= M))
        mem_l2 = is_mem & ~l1_ok & h2 & (is_rd | (s2 == M))
        comp_l2 = is_comp & ~hI & h2
        simple = (is_mem & (l1_ok | mem_l2)) | (is_comp & (hI | comp_l2))
        earlier = ar[None, :, None] > ar[None, None, :]

        def hazard(fills, acc, sset):
            same = sset[:, :, None] == sset[:, None, :]
            return acc & (earlier & same & fills[:, None, :]).any(2)

        touch_d = is_mem & l1_ok
        touch_i = is_comp & hI
        haz = hazard(mem_l2, is_mem, setD) \
            | hazard(touch_d | mem_l2, mem_l2, setD) \
            | hazard(comp_l2, is_comp, setI) \
            | hazard(touch_i | comp_l2, comp_l2, setI)
        ok = simple & valid & ~haz

        icount = np.maximum(arg2 & 0xFFFFF, 0)
        n_lines = np.maximum((icount * 4 + self.line_size - 1)
                             // self.line_size, 1)
        dt = np.where(is_comp,
                      np.maximum(arg, 0) * P + icount * self.l1i_ps
                      + np.where(comp_l2, n_lines * self.l2_ps, 0),
                      np.where(is_mem,
                               np.where(mem_l2, self.l1d_ps + self.l2_ps,
                                        self.l1d_ps), 0))
        clk = self.clock.copy()
        n_ret = np.zeros(T, np.int64)
        run = active.copy()
        for j in range(K):
            can = run & ok[:, j] & (clk < self.boundary)
            clk = np.where(can, clk + dt[:, j], clk)
            n_ret += can
            run = can
        retired = ar[None, :] < n_ret[:, None]

        stamp = (self.round_ctr * self.stride_st + ar)[None, :]

        def word_at(row, way):
            return np.take_along_axis(row, way[None], 0)[0]

        self.l1i.touch(setI, wI, touch_i & retired, word_at(rowI, wI), stamp)
        self.l1d.touch(setD, wD, touch_d & retired, word_at(rowD, wD), stamp)
        self.l2.touch(set2, w2, (mem_l2 | comp_l2) & retired,
                      word_at(row2, w2), stamp)

        def fills(cache, fill, hit, way, sidx, row, state):
            act = fill & retired
            if not act.any():
                return
            st = row & 7
            invalid = st == I
            vic = np.where(invalid.any(0), invalid.argmax(0),
                           ((row >> 3) & STAMP_FIELD).argmin(0))
            fway = np.where(hit, way, vic)
            new = np.broadcast_to(
                (line << 32) | ((stamp & STAMP_FIELD) << 3) | state,
                act.shape)
            tiles = np.broadcast_to(r, act.shape)
            cache.w[fway[act], tiles[act], sidx[act]] = new[act]

        fills(self.l1d, mem_l2, hD, wD, setD, rowD, np.where(is_wr, M, S))
        fills(self.l1i, comp_l2, hI, wI, setI, rowI, S)

        def msum(mask, val=1):
            return np.where(mask & retired, val, 0).sum(axis=1)

        c = self.ctr
        c["icount"] += msum(is_comp, icount) \
            + msum(is_mem & ((arg2 & 0xFF) == 0))
        c["l1i_access"] += msum(is_comp, icount)
        c["l1i_miss"] += msum(is_comp & ~hI, n_lines)
        c["l1d_read"] += msum(is_rd)
        c["l1d_read_miss"] += msum(is_rd & ~l1_ok)
        c["l1d_write"] += msum(is_wr)
        c["l1d_write_miss"] += msum(is_wr & ~l1_ok)
        c["l2_access"] += msum(mem_l2 | comp_l2)
        self.clock = clk
        self.cursor = self.cursor + n_ret
        self.round_ctr += 1

    def complex_slot(self):
        """One event per active tile, of any kind: misses park the tile for
        the directory, barriers park it at the sync server."""
        T, N, P = self.T, self.N, self.P
        active = self._active()
        self.rounds["ctr_complex"] += 1
        if not active.any():            # a slot with nothing to do
            self.round_ctr += 1
            return
        cur = np.minimum(self.cursor, N - 1)
        r = self.rows
        op = np.where(active, self.ops[r, cur], NOP)
        addr = self.addr[r, cur]
        arg = self.arg[r, cur]
        arg2 = self.arg2[r, cur]
        clk = self.clock
        line = addr >> self.line_bits
        hI, wI, _, setI, rowI = self.l1i.probe(line)
        hD, wD, sD, setD, rowD = self.l1d.probe(line)
        h2, w2, s2, set2, row2 = self.l2.probe(line)
        stamp = self.round_ctr * self.stride_st + self.stride_st - 2

        is_comp = op == COMPUTE
        icount = np.maximum(arg2 & 0xFFFFF, 0)
        n_lines = np.maximum((icount * 4 + self.line_size - 1)
                             // self.line_size, 1)
        cost = np.maximum(arg, 0) * P
        fetch = icount * self.l1i_ps
        comp_l2 = is_comp & ~hI & h2
        comp_block = is_comp & ~hI & ~h2
        dt_comp = cost + fetch + np.where(~hI, n_lines * self.l2_ps, 0)
        comp_ok = is_comp & ~comp_block

        is_rd = op == MEM_READ
        is_wr = op == MEM_WRITE
        is_mem = is_rd | is_wr
        l1_ok = hD & (is_rd | (sD >= M))
        mem_l1 = is_mem & l1_ok
        l2_ok = h2 & (is_rd | (s2 == M))
        mem_l2 = is_mem & ~l1_ok & l2_ok
        mem_rem = is_mem & ~l1_ok & ~l2_ok

        is_bar = op == BARRIER
        to_mcp = self.unicast(r, T - 1, 8)
        NB = self.bar_count.shape[0]
        bid = np.clip(arg, 0, NB - 1)
        np.add.at(self.bar_count, bid[is_bar], 1)
        np.maximum.at(self.bar_time, bid[is_bar], (clk + to_mcp)[is_bar])
        is_done = op == DONE

        dt = np.where(comp_ok, dt_comp, 0)
        dt = np.where(mem_l1, self.l1d_ps, dt)
        dt = np.where(mem_l2, self.l1d_ps + self.l2_ps, dt)
        new_clock = clk + dt

        blocked = comp_block | mem_rem | is_bar
        kind = np.where(comp_block, PEND_IF, PEND_NONE)
        kind = np.where(mem_rem & is_rd, PEND_SH, kind)
        kind = np.where(mem_rem & is_wr, PEND_EX, kind)
        kind = np.where(is_bar, PEND_BAR, kind)
        self.pend = np.where(blocked, kind, self.pend)
        self.pend_addr = np.where(is_bar, arg,
                                  np.where(blocked, addr, self.pend_addr))
        issue = clk + np.where(comp_block, self.l1i_ps + self.l2_tag_ps,
                               np.where(mem_rem, self.l1d_ps + self.l2_tag_ps,
                                        P))
        self.pend_issue = np.where(blocked, issue, self.pend_issue)
        mdreg = np.where(is_rd, (arg2 >> 8) & 31, 0)
        self.pend_aux = np.where(blocked,
                                 np.where(mem_rem, mdreg << 8, arg2),
                                 self.pend_aux)
        extra = np.where(comp_block,
                         cost + fetch + (n_lines - 1) * self.l2_ps, 0)
        self.pend_extra = np.where(blocked, extra, self.pend_extra)

        self.l1i.touch(setI, wI, is_comp & hI, rowI[wI, r], stamp)
        self.l1i.fill(line, np.full(T, S), comp_l2, stamp)
        self.l2.touch(set2, w2, comp_l2 | mem_l2, row2[w2, r], stamp)
        self.l1d.touch(setD, wD, mem_l1, rowD[wD, r], stamp)
        self.l1d.fill(line, np.where(is_wr, M, S), mem_l2, stamp)

        c = self.ctr
        c["icount"] += np.where(is_comp, icount, 0) \
            + np.where(is_mem & ((arg2 & 0xFF) == 0), 1, 0)
        c["l1i_access"] += np.where(is_comp, icount, 0)
        c["l1i_miss"] += np.where(is_comp & ~hI & active, n_lines, 0)
        c["l1d_read"] += is_rd
        c["l1d_read_miss"] += is_rd & ~l1_ok
        c["l1d_write"] += is_wr
        c["l1d_write_miss"] += is_wr & ~l1_ok
        c["l2_access"] += mem_l2 | mem_rem | comp_l2 | comp_block
        c["l2_miss"] += mem_rem | comp_block
        c["barriers"] += is_bar
        self.done = self.done | is_done
        self.clock = new_clock
        self.cursor = self.cursor + (active & ~blocked)
        self.round_ctr += 1

    # ------------------------------------------------------------ resolve

    def resolve(self):
        any_mem = self._parked().any()
        any_sync = (self.pend >= 4).any()
        if any_mem:
            self.resolve_memory()
        if any_sync and (self.pend == PEND_BAR).any():
            self.resolve_barrier()

    def _parked(self):
        return (self.pend >= PEND_SH) & (self.pend <= PEND_IF)

    def resolve_barrier(self):
        T, P = self.T, self.P
        NB = self.bar_count.shape[0]
        is_bar = self.pend == PEND_BAR
        bid = np.clip(self.pend_addr, 0, NB - 1)
        parts = np.maximum(self.pend_aux, 1)
        rel = is_bar & (self.bar_count[bid] >= parts)
        back = self.unicast(np.full(T, T - 1), self.rows, CTRL_BYTES)
        completion = self.bar_time[bid] + back + P
        self.bar_count[bid[rel]] = 0
        self.bar_time[bid[rel]] = 0
        self.ctr["sync_stall_ps"] += np.where(
            rel, completion - self.pend_issue, 0)
        self.clock = np.where(rel, completion, self.clock)
        self.cursor = self.cursor + rel
        self.pend = np.where(rel, PEND_NONE, self.pend)

    def home(self, line):
        return fold(line, self.nctl) * self.stride

    def dir_set(self, line):
        x = line // self.nctl
        bits = self.ndsets.bit_length() - 1
        x = x ^ (x >> bits) ^ (x >> (2 * bits)) ^ (x >> (3 * bits))
        return x % self.ndsets

    def _dram_probe(self, res, arr, valid, occ_valid):
        """History-list FCFS DRAM queue: a request starts after any recorded
        busy interval it overlaps and after the earlier requests of its
        batch on the same controller; one merged busy interval is recorded
        per controller for the requests, one more for the writebacks."""
        svc0 = self.dram_svc
        C = self.ring_s.shape[1]
        K = res.shape[0]
        svc = np.where(valid, svc0, 0)
        res_eff = np.where(valid, res, C)
        res_g = np.minimum(res_eff, C - 1)
        base = arr.copy()
        for _ in range(3):
            rs = self.ring_s[:, res_g]
            re = self.ring_e[:, res_g]
            ov = (rs < base[None, :] + svc[None, :]) & (base[None, :] < re)
            base = np.where(ov, re, base[None, :]).max(axis=0)
        base = np.where(valid, base, arr)
        idx = np.arange(K)
        same = valid[None, :] & valid[:, None] \
            & (res_eff[None, :] == res_eff[:, None])
        earlier = same & ((arr[None, :] < arr[:, None])
                          | ((arr[None, :] == arr[:, None])
                             & (idx[None, :] < idx[:, None])))
        s_prev = np.where(earlier, svc[None, :], 0).sum(axis=1)
        cand = base - s_prev
        soe = earlier | (np.eye(K, dtype=bool) & valid[:, None])
        run = np.where(soe, cand[None, :], -BIG).max(axis=1)
        start = run + s_prev
        end = start + svc
        self._record(res_eff, valid, start, end)
        self._record(np.where(occ_valid, res, C), occ_valid, arr,
                     arr + svc0)
        return np.where(valid, start, 0)

    def _record(self, res, valid, lo_v, hi_v):
        Rs, C = self.ring_s.shape
        lo = np.full(C + 1, BIG, np.int64)
        hi = np.zeros(C + 1, np.int64)
        rr = np.where(valid, res, C)
        np.minimum.at(lo, rr, lo_v)
        np.maximum.at(hi, rr, hi_v)
        lo, hi = lo[:C], hi[:C]
        has = hi > 0
        slot = self.ring_p % Rs
        cols = np.arange(C)
        self.ring_s[slot[has], cols[has]] = lo[has]
        self.ring_e[slot[has], cols[has]] = hi[has]
        self.ring_p = self.ring_p + has

    def resolve_memory(self):
        self.rounds["ctr_resolve"] += 1
        ftbl = np.stack([np.full(self.H, -1, np.int64),
                         np.zeros(self.H, np.int64)])
        i = 0
        while i < self.max_conf and self._parked().any():
            ftbl = self._conflict_round(ftbl)
            i += 1
        self.ctr["dir_deferrals"] += self._parked()

    def _conflict_round(self, ftbl):
        """Serve the parked misses through their home directories: the
        earliest request per line wins (same-set winners take distinct
        victim ways), concurrent shared reads of an I/S entry combine,
        invalidation fan-outs and owner legs are budgeted per round, and
        the rest waits for the next round."""
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
        net_req = self.unicast(rows, home, CTRL_BYTES)
        reply = self.unicast(home, rows, self.line_size + CTRL_BYTES)
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

        # Latency of each served request.
        arrive = np.maximum(issue + net_req, line_floor)
        evict_m_ps = self.unicast(home, vown, CTRL_BYTES) + self.l2_ps \
            + self.unicast(vown, home, self.line_size + CTRL_BYTES)
        evict_ps = np.where(evict_m, evict_m_ps, evict_ps)
        t_dir = arrive + self.dir_ps + np.where(evicting, evict_ps, 0)
        leg = self.unicast(home, owner, CTRL_BYTES) + self.l2_ps \
            + self.unicast(owner, home, self.line_size + CTRL_BYTES)
        owner_ps = np.where(owner_leg, leg, 0)
        need_read = win & dram_read_a
        dram_arr = t_dir + owner_ps
        dram_wb = (dram_write_a & win) | evict_m | evict_o
        q_start = self._dram_probe(home, dram_arr, need_read, dram_wb)
        dram_start = np.where(need_read, q_start, 0)
        dram_ready = dram_start + self.dram_lat + self.dram_svc
        t_data = t_dir + owner_ps
        t_data = np.maximum(t_data, np.where(need_read, dram_ready, 0))
        t_data = np.maximum(t_data, t_dir + inv_ps)
        reply_done = t_data + reply
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

    def _evict_notify(self, vtag, valid):
        """An L2 victim tells its home directory: an owner's M drop clears
        the entry, a sharer's drop clears its bit (the entry goes to I
        when that bit was the last)."""
        T, A, W = self.T, self.A, self.W
        tiles = self.rows
        vfidx = self.home(vtag) * self.ndsets + self.dir_set(vtag)
        drow = self.dir_word[:, vfidx].T
        dstate = drow & 7
        match = ((drow >> 33) == vtag[:, None]) & (dstate != I) \
            & valid[:, None]
        found = match.any(1)
        way = match.argmax(1)
        ww = drow[tiles, way]
        est = ww & 7
        eowner = ((ww >> 3) & DIR_OWNER_FIELD) - 1
        esh = self.dir_sharers[:, vfidx].reshape(W, A, T) \
            .transpose(2, 1, 0)[tiles, way]                    # [T, W]
        word = tiles // 64
        bit = np.uint64(1) << (tiles % 64).astype(np.uint64)
        woh = word[:, None] == np.arange(W)[None, :]
        has_bit = (esh[tiles, word] & bit) != 0
        drop_m = found & (est == M) & (eowner == tiles)
        drop_s = found & has_bit & (est == S)
        left = esh & ~np.where(woh, bit[:, None], np.uint64(0))
        empty = (left == 0).all(1)
        to_i = drop_m | (drop_s & empty)
        new = (ww & ~np.int64((1 << 16) - 1)) | I
        self.dir_word[way[to_i], vfidx[to_i]] = new[to_i]
        planes = np.arange(W)[:, None] * A + way[None, :]
        n = int(drop_m.sum())
        np.add.at(self.dir_sharers,
                  (planes[:, drop_m].reshape(-1),
                   np.broadcast_to(vfidx[drop_m][None, :], (W, n))
                   .reshape(-1)),
                  (np.uint64(0) - esh[drop_m]).T.reshape(-1))
        clr = drop_s & has_bit
        np.add.at(self.dir_sharers, ((word * A + way)[clr], vfidx[clr]),
                  np.uint64(0) - bit[clr])
