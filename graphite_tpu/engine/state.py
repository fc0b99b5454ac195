"""Simulation state: one pytree of [num_tiles, ...] arrays.

This is the TPU build's replacement for the reference's object graph of
per-tile Tile/Core/MemoryManager/NetworkModel instances plus the MCP-side
server state (reference: common/tile/tile.cc:15-37 builds the per-tile
objects; common/system/sync_server.h holds mutex/cond/barrier state on the
MCP tile).  Everything mutable during simulation lives here; everything
static (geometry, latencies, model choices) lives in SimParams and is baked
into the compiled step.

The tile axis (leading dimension) is the sharding axis: under a device
mesh, arrays here are sharded over it, turning the reference's multi-process
socket distribution (common/transport/socktransport.cc) into XLA collectives.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from graphite_tpu.engine import cache as cachemod
from graphite_tpu.engine import noc_flight
from graphite_tpu.events.schema import Trace
from graphite_tpu.isa import DVFSModule, EventOp
from graphite_tpu.params import SimParams

# Pending-request kinds (a tile blocks on at most one at a time — in-order
# cores block inside Core::initiateMemoryAccess, reference core.cc:139).
PEND_NONE = 0
PEND_SH_REQ = 1     # read miss -> directory SH_REQ (shmem_msg.h:14-28)
PEND_EX_REQ = 2     # write/atomic miss or S-upgrade -> EX_REQ
PEND_IFETCH = 3     # instruction-fetch L2 miss (read-only SH_REQ)
PEND_RECV = 4       # blocking user-network receive (CAPI)
PEND_BARRIER = 5    # SimBarrier wait
PEND_MUTEX = 6      # SimMutex acquire
PEND_SEND = 7       # user-network send waiting for channel-buffer space
#   (models the finite receive-side buffering the reference gets from its
#   per-tile net queues; CAPI sends block in Network::netSend when the
#   transport back-pressures)
PEND_COND = 8       # SimCond wait (mutex released; wakes on signal, then
#   transforms into PEND_MUTEX for the re-acquire)
PEND_JOIN = 9       # blocked until the named tile's stream is DONE
PEND_START = 10     # stream gated on being SPAWNed
PEND_CSIG = 11      # posted signal TOKEN: the signaler parks until its
#   signal is consumed by a waiter or provably lost — the parked entry IS
#   the token (exact per-token timestamp, no collapsing), and the
#   signaler's ack completion is timestamp-based so the extra engine
#   passes cost no simulated time
PEND_CBC = 12       # posted broadcast token (same mechanism)

NUM_DVFS_MODULES = len(DVFSModule)


class Counters(NamedTuple):
    """Per-tile event counters ([T] int64 each) — the data behind the
    end-of-run summary (reference: each component's outputSummary(),
    e.g. cache counters in cache.cc, network_model.h:73)."""

    icount: jnp.ndarray
    l1i_access: jnp.ndarray
    l1i_miss: jnp.ndarray
    l1d_read: jnp.ndarray
    l1d_read_miss: jnp.ndarray
    l1d_write: jnp.ndarray
    l1d_write_miss: jnp.ndarray
    l2_access: jnp.ndarray
    l2_miss: jnp.ndarray
    branches: jnp.ndarray
    mispredicts: jnp.ndarray
    dir_sh_req: jnp.ndarray      # SH_REQ served at this tile's directory slice
    dir_ex_req: jnp.ndarray
    dir_invalidations: jnp.ndarray   # INV_REQ messages sent from this slice
    dir_writebacks: jnp.ndarray      # WB/FLUSH data returns to this slice
    dir_forwards: jnp.ndarray        # owner cache-to-cache forwards that
    #   skipped DRAM (MOSI O-state forwards; always 0 under MSI)
    dir_evictions: jnp.ndarray       # directory-cache entry evictions
    dir_deferrals: jnp.ndarray       # deferral events: one per round a
    #   request is pushed back by the way-slot election or the fan-out
    #   budget, plus one per request still unresolved after a full resolve
    #   pass (visibility into hot-line saturation)
    dram_reads: jnp.ndarray          # at this tile's memory controller
    dram_writes: jnp.ndarray
    net_mem_pkts: jnp.ndarray        # memory-network packets this tile sent
    net_mem_flits: jnp.ndarray
    net_link_wait_ps: jnp.ndarray    # per-link queueing delay this tile's
    #   requests accumulated en route (emesh_hop_by_hop contention only)
    net_user_pkts: jnp.ndarray
    net_user_flits: jnp.ndarray
    sends: jnp.ndarray
    recvs: jnp.ndarray
    barriers: jnp.ndarray
    mutex_acquires: jnp.ndarray
    cond_waits: jnp.ndarray          # COND_WAIT parks
    cond_signals: jnp.ndarray        # signals + broadcasts posted
    spawns: jnp.ndarray              # SPAWN events issued by this tile
    joins: jnp.ndarray               # completed JOINs
    syscalls: jnp.ndarray            # SYSCALL events served via the MCP
    syscall_ps: jnp.ndarray          # time spent in syscall round trips
    l2_miss_cold: jnp.ndarray        # miss-type classification (cache.h:
    l2_miss_capacity: jnp.ndarray    #   45-49): first-touch / evicted /
    l2_miss_sharing: jnp.ndarray     #   coherence-invalidated
    mem_stall_ps: jnp.ndarray        # time blocked on remote memory
    sync_stall_ps: jnp.ndarray       # time blocked on sync/recv
    chain_fanout_served: jnp.ndarray  # invalidation fan-out heads served
    #   INSIDE the chain replay (round 9's batched INV leg; 0 with
    #   tpu/fanout_replay off or miss_chain 0)
    chain_fallback: jnp.ndarray      # chain heads that hard-stopped out
    #   of the replay into the one-element-per-round fallback — the
    #   fallback-occupancy counter PROFILE.md's round-9 table reads


def make_counters(num_tiles: int) -> Counters:
    z = lambda: jnp.zeros(num_tiles, dtype=jnp.int64)
    return Counters(**{f: z() for f in Counters._fields})


class TraceArrays(NamedTuple):
    """Device-resident trace (see events/schema.py for field semantics).

    The int32 event fields are stacked into one [3, T, N] array
    (op, arg, arg2) beside the int64 address array, so the per-slot fetch
    is two gathers per tile instead of four — gathers on this hardware
    cost per *operation*, not per element.  The field axis LEADS (TPU pads
    the minor two dims to (8, 128) tiles; a trailing length-3 axis would
    pad the resident trace ~42x).
    """

    addr: jnp.ndarray  # [T, N] int64 byte address
    meta: jnp.ndarray  # [3, T, N] int32: (op, arg, arg2)
    # Streaming segmented ingest (engine/ingest.py): when ``base`` is
    # set, addr/meta hold only a [*, C]-column RESIDENT SEGMENT of a
    # longer trace — per-row, columns [base[r], base[r] + C) of the full
    # [*, n_total] event stream.  ``base`` is the per-row global column
    # of resident column 0 and ``n_total`` the full trace length; engine
    # reads stay in GLOBAL event coordinates and rebase through
    # ``local_cols`` at the gather.  Both stay None for the whole-trace
    # program (None pytree leaves vanish, so the compiled structure —
    # and the arithmetic, local_cols being the identity — is bit-for-bit
    # today's).
    base: Optional[jnp.ndarray] = None    # [rows] int32 global col of col 0
    n_total: Optional[int] = None         # full trace event count

    @property
    def num_events(self):
        """Global event count per row — the full stream length when this
        is a resident segment of a streamed trace."""
        if self.n_total is not None:
            return self.n_total
        return self.addr.shape[1]

    def local_cols(self, idx, rows=None):
        """Rebase GLOBAL event indices into resident-segment columns.

        Identity for a whole-trace ``TraceArrays``.  For a segment,
        subtracts each row's ``base`` (broadcast across trailing axes of
        ``idx``) and clips into the resident span — out-of-segment
        indices read junk columns exactly like the trace-end clamp reads
        junk events, and the streamed megarun (engine/ingest.py) rolls
        back any quantum whose speculative cursors could have taken such
        a read, so committed steps only ever see in-segment values.
        ``rows`` maps each idx row to its trace row (the seated-stream
        indirection) before the base lookup."""
        if self.base is None:
            return idx
        b = self.base if rows is None else self.base[rows]
        while b.ndim < jnp.ndim(idx):
            b = b[..., None]
        return jnp.clip(idx - b, 0, self.addr.shape[1] - 1)

    @classmethod
    def from_trace(cls, trace: Trace) -> "TraceArrays":
        import numpy as np
        addr = np.asarray(trace.addr, dtype=np.int64)
        if addr.max(initial=0) >= (1 << 37):
            raise ValueError(
                "trace addresses must be < 2^37 (int32 line-id layout)")
        meta = np.stack([
            np.asarray(trace.ops, dtype=np.int32),
            np.asarray(trace.arg, dtype=np.int32),
            np.asarray(trace.arg2, dtype=np.int32),
        ], axis=0)
        return cls(addr=jnp.asarray(addr), meta=jnp.asarray(meta))


_DIR_OWNER_BITS = 13   # owner+1, supports up to 8191 tiles
_DIR_OWNER_SHIFT = 3

# Packed directory-entry word (int64), ONE array instead of the round-3
# tags/meta/stamp triple — a directory probe is one gather and an entry
# write one scatter (gather/scatter ops on this hardware cost per
# *operation*, so collapsing 3 arrays into 1 cuts the conflict-round cost
# by the same factor):
#
#     bits  0..2    entry state (I/S/O/E/M — directory_state.h roles)
#     bits  3..15   owner tile + 1 (0 = none)
#     bits 16..32   replacement stamp (17-bit wrapping round counter;
#                   a wrap only perturbs LRU victim choice, never
#                   correctness — same argument as cache.py STAMP_BITS)
#     bits 33..63   tag (31-bit line id; frontend asserts addr < 2^37)
#
# SimState.dir_word stores each word as two uint32 halves (dir_gather,
# dir_set); dir_word_view gives the [A, F] int64 words back.
DIR_STAMP_BITS = 17
_DIR_STAMP_SHIFT = 16
_DIR_STAMP_FIELD = (1 << DIR_STAMP_BITS) - 1
_DIR_TAG_SHIFT = _DIR_STAMP_SHIFT + DIR_STAMP_BITS  # 33
_DIR_META_MASK = (1 << _DIR_STAMP_SHIFT) - 1


def dword_pack(tag, stamp, state, owner):
    """(tag, stamp, state, owner) -> packed int64 directory word."""
    return (jnp.asarray(tag, jnp.int64) << _DIR_TAG_SHIFT) \
        | ((jnp.asarray(stamp, jnp.int64) & _DIR_STAMP_FIELD)
           << _DIR_STAMP_SHIFT) \
        | ((jnp.asarray(owner, jnp.int64) + 1) << _DIR_OWNER_SHIFT) \
        | jnp.asarray(state, jnp.int64)


def dword_state(word):
    return (word & 7).astype(jnp.int32)


def dword_owner(word):
    return (((word >> _DIR_OWNER_SHIFT)
             & ((1 << _DIR_OWNER_BITS) - 1)) - 1).astype(jnp.int32)


def dword_stamp(word):
    return ((word >> _DIR_STAMP_SHIFT) & _DIR_STAMP_FIELD).astype(jnp.int32)


def dword_tag(word):
    return (word >> _DIR_TAG_SHIFT).astype(jnp.int32)


def dword_with_meta(word, state, owner):
    """Replace the (state, owner) fields, keeping tag + stamp."""
    return (word & ~jnp.int64(_DIR_META_MASK)) \
        | ((jnp.asarray(owner, jnp.int64) + 1) << _DIR_OWNER_SHIFT) \
        | jnp.asarray(state, jnp.int64)


class SimState(NamedTuple):
    """All mutable simulation state (a single jit-traversable pytree)."""

    # -- core (reference: CoreModel per-tile time/queues, core_model.h:19-146)
    clock: jnp.ndarray        # [T] int64 ps — the per-tile target clock
    cursor: jnp.ndarray       # [T] int32 — next trace event index
    done: jnp.ndarray         # [T] bool
    boundary: jnp.ndarray     # [] int64 ps — current lax-barrier quantum end

    # -- pending remote operation (at most one per tile)
    pend_kind: jnp.ndarray    # [T] int32 PEND_*
    pend_addr: jnp.ndarray    # [T] int64 byte address / object id
    pend_issue: jnp.ndarray   # [T] int64 ps when the request left the tile
    pend_aux: jnp.ndarray     # [T] int32 (recv src / barrier participants)
    pend_extra: jnp.ndarray   # [T] int64 ps of local cost to add on top of
    #   the resolved remote latency (e.g. a blocked COMPUTE block's own
    #   cost + fetch time, an atomic's RMW cycle)

    # -- cached block-window trace slice (tpu/window_cache; engine/core.py
    # _block_retire).  The window phase used to re-gather its [T, K] event
    # slice from the full device trace EVERY round; miss-dominated traces
    # retire ~1.4 events/tile/round, so ~90% of that HBM traffic re-read
    # bytes fetched the round before (PROFILE.md lever 2).  Instead a
    # [T, WC] slice (WC = 4K; 2K before round 9's boundary-spanning
    # windows raised per-round consumption) is gathered once and advances with the
    # cursor: rounds read from this small resident cache, and a full
    # re-gather happens only when some ACTIVE tile's next-K events fall
    # outside its cached span (or its seat rotated) — a guarded lax.cond,
    # so cache-hit rounds never touch the trace.  Values are identical to
    # a direct gather by construction (same clamped indices), so timing,
    # counters, and round counts are bit-identical (tests/
    # test_block_equivalence.py round-identity case).  Zero-width when
    # the cache or the window phase is disabled.
    win_meta: jnp.ndarray     # [3, T, WC] int32 (op, arg, arg2)
    win_addr: jnp.ndarray     # [T, WC] int64
    win_base: jnp.ndarray     # [T] int32 cursor at gather time (large
    #   negative = invalid, forces the first refresh)
    win_seat: jnp.ndarray     # [T] int32 seat_stream at gather time
    #   (seat rotation invalidates a tile's cached rows; -1 when the
    #   scheduler is off)

    # -- branch predictor (reference: one_bit_branch_predictor.cc)
    bp_table: jnp.ndarray     # [T, bp_size] bool — last outcome per slot

    # -- caches (private L1I/L1D/L2 per tile)
    l1i: cachemod.CacheArrays
    l1d: cachemod.CacheArrays
    l2: cachemod.CacheArrays

    # -- DVFS module clock periods (reference: dvfs_manager.h:19-88 keeps
    # per-module frequencies; the engine stores the derived integer period
    # so the hot loops never touch floating point — float64 is emulated on
    # TPU and was the single largest per-slot cost)
    period_ps: jnp.ndarray    # [T, NUM_DVFS_MODULES] int32 ps per cycle

    # -- directory slices (home-tile-indexed; reference: directory_cache.cc)
    # The whole entry (tag | stamp | owner | state) is packed into ONE
    # int64 word (see dword_pack): a probe is one gather, a write one
    # scatter.  The (tile, set) axes are stored PRE-FLATTENED — every
    # access indexes by the flat home*ndsets + dset id, and a
    # [.., T, dsets] layout forced XLA to materialize a full-array reshape
    # copy per conflict round (profiled at ~4.5 ms per round on the 512 MB
    # 1024-tile sharer bitmap).
    dir_word: jnp.ndarray     # [T*dsets * dassoc * 2] uint32 packed
    #   entries, flat and entry-major: entry (fidx, way) lives at
    #   (fidx*dassoc + way)*2, word 0 the low 32 bits of its dword_pack
    #   word, word 1 the high.  Flat and u32 for the reasons dir_sharers
    #   gives below (as a [dassoc, F] int64 array each conflict round
    #   relaid out all of it, 128 MiB at 1024 tiles, per u32 half);
    #   entry-major, so an entry's ways are one run of 2*dassoc words.
    #   Read and written only through dir_gather/dir_set;
    #   dir_word_view gives the [A, F] int64 view.
    dir_sharers: jnp.ndarray  # [T*dsets * dassoc * 2W] uint32 full-map
    #   sharer bitmaps, flat and entry-major: word j of entry (fidx, way)
    #   lives at (fidx*dassoc + way)*2W + j and holds bitmap bits
    #   32j..32j+31 (W = sharer_words(T) u64 words per row).  Flat, because
    #   XLA:TPU lowers a two-index scatter into any 2-D layout as a scatter
    #   into a flat buffer plus a relayout of the WHOLE array back into the
    #   tiled layout (2 GiB per conflict round at 1024 tiles); u32, because
    #   an int64 array is split into two u32 halves with a whole-array
    #   temporary.  Entry-major, so an entry's rows are one contiguous
    #   block and an even split of the flat axis is an even split over
    #   tiles.  Read and written only through sharers_gather/sharers_add;
    #   dir_sharers_view gives the [A, F, W] uint64 view.

    # -- banked miss chains (tpu/miss_chain > 0; engine/core.py window).
    # BLOCKING semantics (round 7): the block window executes past L2
    # misses on a relative clock, banking each request here WITHOUT
    # installing the line — the resolve pass replays the chain
    # sequentially (engine/resolve.chain_fast_pass), pricing element k+1
    # against the post-element-k directory state and installing each
    # line at serve time; stall-on-use hazards in the window keep later
    # events from observing a banked fill early.  Element k+1's issue =
    # element k's completion + its recorded local delta.  Packed fields:
    #   mq_req    int64: kind (PEND_SH/EX/IFETCH) bits 0-2 | atomic bit 3
    #             | line << 8
    #   mq_delta  int64 ps: element 0 — ABSOLUTE issue time; element k>0 —
    #             issue relative to element k-1's continuation point
    #   mq_extra  int64 ps: local cost folded into the completion
    # chain_rel is the local time accumulated since the last banked
    # element's (not yet known) continuation point; chain_base is the
    # continuation time of the last SERVED element (mq_head of them).
    mq_req: jnp.ndarray        # [P, T] int64
    mq_delta: jnp.ndarray      # [P, T] int64
    mq_extra: jnp.ndarray      # [P, T] int64
    mq_count: jnp.ndarray      # [T] int32 banked elements
    mq_head: jnp.ndarray       # [T] int32 served elements (< count: mid-chain)
    chain_base: jnp.ndarray    # [T] int64 ps
    chain_rel: jnp.ndarray     # [T] int64 ps

    # -- iocoom load/store queues (reference: iocoom_core_model.cc:78-;
    # completion-time rings — a load/store miss parks the tile only until
    # the resolve phase PRICES it; under iocoom the core then continues
    # from shortly after issue while the completion occupies a queue slot,
    # and drain points (atomics, sync ops, DONE, branches without
    # speculative loads) wait for the queues' max completion)
    lq_ready: jnp.ndarray      # [LQE, T] int64 completion times
    sq_ready: jnp.ndarray      # [SQE, T] int64
    lq_next: jnp.ndarray       # [T] int32 ring cursor
    sq_next: jnp.ndarray       # [T] int32
    # Register scoreboard (iocoom; reference iocoom_core_model.h:82,
    # .cc:119-136): per-register ready times.  Trace events carry
    # compressed 5-bit register annotations (events/schema.py
    # NUM_REGISTERS); reads floor the instruction's issue, writes land
    # completion times.  [0, T] when the core model is 'simple'.
    reg_ready: jnp.ndarray     # [NREG, T] int64

    # -- memory controllers (reference: dram_cntlr.h + dram_perf_model.h;
    # queueing per queue_model_history_list.cc — a bounded ring of busy
    # intervals per controller, so requests arriving in idle gaps insert
    # into the past instead of queueing behind a farther-future horizon)
    dram_ring_start: jnp.ndarray  # [R, T] int64 busy-interval starts
    dram_ring_end: jnp.ndarray    # [R, T] int64 busy-interval ends
    dram_ring_ptr: jnp.ndarray    # [T] int32 next ring slot
    # Queue-model accumulators per controller, [6, T] float64:
    # rows 0-3 = m_g_1 service moments (sum_s, sum_s_sq, n, newest
    # arrival — reference queue_model_m_g_1.h:14-20), rows 4-5 = the
    # basic model's moving-average state (ema mean, effective sample
    # count — reference queue_model_basic.cc + moving_average.h).  Only
    # the rows of the configured [dram/queue_model] type are consumed.
    dram_qacc: jnp.ndarray         # [6, T] float64

    # -- mesh link queues (emesh_hop_by_hop contention; reference:
    # per-link queue models in network_model_emesh_hop_by_hop.cc): per
    # directed link, the configured queue model's busy-interval ring, as
    # the DRAM queue's, of max_list_size slots (``basic``: one slot, its
    # horizon in the ends); zero-sized when the network is not contended
    # (noc_flight.contended).
    link_mem: noc_flight.LinkRings
    # The user network's (CAPI data traffic under network/user =
    # emesh_hop_by_hop).  MCP control trips stay zero-load: the reference
    # routes those over the SYSTEM network, which has its own (magic by
    # default) model.
    link_user: noc_flight.LinkRings

    # -- sync objects, global (reference: sync_server.h SimMutex/SimBarrier/
    # SimCond)
    lock_holder: jnp.ndarray   # [NL] int32 holder tile + 1, 0 = free
    lock_free_at: jnp.ndarray  # [NL] int64 time the lock was/will be released
    bar_count: jnp.ndarray     # [NB] int32 arrivals this generation
    bar_time: jnp.ndarray      # [NB] int64 max arrival time this generation
    # (cond-var signal/broadcast tokens live as parked PEND_CSIG/PEND_CBC
    # entries — pend_addr = cond id, pend_issue = MCP arrival — so no
    # dedicated arrays are needed and every token keeps its exact time)

    # -- thread lifecycle (reference: thread_manager.cc spawn/join tables).
    # STREAM-indexed ([S] where S = trace streams; S == T unless the
    # ThreadScheduler multiplexes several streams per tile).
    spawned_at: jnp.ndarray    # [S] int64 when this stream was spawned
    #   (-1 = not yet; THREAD_START gates on it)
    done_at: jnp.ndarray       # [S] int64 when the stream's DONE retired

    # -- ThreadScheduler seats (reference: thread_scheduler.h:30-56 +
    # round_robin_thread_scheduler.cc).  The engine's [T] context arrays
    # (clock/cursor/pend_*/done above) are SEATS — the running stream of
    # each tile; descheduled streams live in the strm_* store and rotate
    # in round-robin at quantum boundaries (engine/quantum.py
    # schedule_rotate).  All [0]-shaped when S == T (scheduler compiled
    # out; streams pin 1:1 to tiles exactly as before).
    seat_stream: jnp.ndarray   # [T] int32 stream seated on each tile
    seat_since: jnp.ndarray    # [T] int64 sim time the seat last rotated
    seat_yield: jnp.ndarray    # [T] bool YIELD retired since last rotate
    strm_cursor: jnp.ndarray   # [S] int32 (valid iff not seated)
    strm_clock: jnp.ndarray    # [S] int64
    strm_pend_kind: jnp.ndarray   # [S] int32
    strm_pend_addr: jnp.ndarray   # [S] int64
    strm_pend_issue: jnp.ndarray  # [S] int64
    strm_pend_aux: jnp.ndarray    # [S] int32
    strm_pend_extra: jnp.ndarray  # [S] int64
    strm_done: jnp.ndarray     # [S] bool (kept in sync for seated streams
    #   at every rotation; authoritative for completion)
    strm_key: jnp.ndarray      # [S] int64 round-robin queue key (unique;
    #   lowest key among a tile's waiting streams is seated next)

    # -- region of interest (reference: Simulator::enableModels +
    # PerformanceCounterManager broadcast) — one global flag; outside the
    # ROI compute/memory events fast-forward uncosted and uncounted
    models_enabled: jnp.ndarray   # [] bool

    # -- periodic sampling ring (reference: StatisticsManager's barrier-
    # clocked sampling + progress trace); fixed capacity, sampled at
    # quantum boundaries crossing the configured interval
    stat_filled: jnp.ndarray      # [] int32 samples taken
    stat_next: jnp.ndarray        # [] int64 next sample time
    stat_time: jnp.ndarray        # [S] int64 sample timestamps
    stat_scalars: jnp.ndarray     # [13, S] int64 aggregate series:
    #   (icount, net_mem_flits, net_user_flits, dram_reads, dram_writes,
    #    live_l2_or_slice_lines, sharer_bits [replication], link_wait_ps)
    stat_icount: jnp.ndarray      # [S, T] int64 per-tile icount snapshots
    #   (the progress trace; [1, T] dummy when disabled)

    # -- [telemetry] engine-health round metrics (graphite_tpu/obs):
    # sampled in the SAME _maybe_sample take as the rings above (shared
    # stat_filled/stat_time/stat_next bookkeeping).  Zero-size when
    # telemetry is off — the disabled path allocates nothing and the
    # compiled step is unchanged.
    tel_gauges: jnp.ndarray       # [len(TEL_SERIES), S] int64 gauge rows
    #   (row order: obs/metrics.TEL_SERIES)
    tel_cursor: jnp.ndarray       # [S, T] int32 per-tile trace-cursor
    #   snapshots (per-tile progress in events; SEAT-level — under the
    #   ThreadScheduler a tile's row shows whichever stream is seated)
    tel_pend: jnp.ndarray         # [S, T] int32 per-tile pend_kind
    #   snapshots (per-tile occupancy / stall attribution)

    # -- user-network channels (CAPI; reference: common/user/capi.cc)
    # [T, T]-shaped, so allocated only when the trace actually uses CAPI
    # (zero-size dummies otherwise — see make_state(has_capi); a 1024-tile
    # radix run must not carry O(T^2) channel state it never touches)
    ch_sent: jnp.ndarray       # [T, T] int32 messages sent src->dst
    ch_recvd: jnp.ndarray      # [T, T] int32 messages consumed
    ch_time: jnp.ndarray       # [D, T, T] int64 arrival-time ring buffer
    #   (slot axis leads — see the directory layout note)

    # -- engine round counter (stamp source for the timestamp-LRU caches;
    # bumped once per local round and per resolve conflict round)
    round_ctr: jnp.ndarray     # [] int32
    # Phase execution counters (device-work attribution for bench.py's
    # per-phase breakdown): window retirements, complex slots, resolve
    # conflict rounds, resolve calls, quantum steps.
    ctr_window: jnp.ndarray    # [] int64
    ctr_complex: jnp.ndarray   # [] int64
    ctr_conflict: jnp.ndarray  # [] int64
    ctr_resolve: jnp.ndarray   # [] int64
    ctr_quantum: jnp.ndarray   # [] int64
    # Round-12 fast-forward attribution: engaged fast-forward rounds
    # (spans actually committed), quanta that committed at least one
    # span, and total events priced analytically — the bench's
    # ff-quanta-fraction numerator/denominator ride on ctr_ffq vs
    # ctr_quantum.
    ctr_ff: jnp.ndarray        # [] int64
    ctr_ffq: jnp.ndarray       # [] int64
    ff_events: jnp.ndarray     # [] int64

    # -- VMManager accounting (reference: vm_manager.cc bump segments).
    # SYSCALL events carry the payload in the event's addr field
    # (mmap/munmap: length; brk: the requested data-segment size — the
    # delta over the program's initial break); the complex slot
    # folds them in and engine/vm.summarize renders the segment layout.
    vm_brk: jnp.ndarray          # [] int64 peak requested data-segment size
    vm_mmap_bytes: jnp.ndarray   # [] int64 total bytes mmap'd
    vm_munmap_bytes: jnp.ndarray  # [] int64 total bytes munmap'd

    # -- miss-type classification filters ([cache]/track_miss_types,
    # reference cache.h:45-49 cold/capacity/sharing counters).  Per-tile
    # direct-mapped line tables (fmix-hashed, last-writer-wins — a
    # collision can misclassify one miss, never mistime anything):
    # ``seen_filter`` records lines this tile has ever fetched,
    # ``inv_filter`` lines taken away by coherence.  [1, 1] dummies when
    # tracking is off.
    seen_filter: jnp.ndarray   # [T, HF] int32 line id + 1 (0 = empty)
    inv_filter: jnp.ndarray    # [T, HF] int32

    counters: Counters

    @property
    def has_capi(self) -> bool:
        """Static: were CAPI channel arrays allocated for this run?"""
        return self.ch_sent.size > 0

    @property
    def sched_enabled(self) -> bool:
        """Static: is the ThreadScheduler active (more streams than
        tiles)?"""
        return self.seat_stream.size > 0

    @property
    def num_streams(self) -> int:
        """Static: app-thread streams (== tiles unless the scheduler
        multiplexes)."""
        return self.strm_cursor.shape[0] if self.sched_enabled \
            else self.clock.shape[0]

    def all_done(self) -> jnp.ndarray:
        """Scalar bool: every STREAM is done (seats only cover the
        currently-scheduled subset when the scheduler is on)."""
        if self.sched_enabled:
            return jnp.all(self.strm_done.at[self.seat_stream]
                           .set(self.done))
        return self.done.all()


def sharer_words(num_tiles: int) -> int:
    """uint64 words per full-map sharer row."""
    return (num_tiles + 63) // 64


def _join_words(halves: jnp.ndarray) -> jnp.ndarray:
    """[..., 2W] uint32 (lo, hi interleaved) -> [..., W] uint64."""
    pairs = halves.reshape(halves.shape[:-1] + (-1, 2)).astype(jnp.uint64)
    return pairs[..., 0] | (pairs[..., 1] << jnp.uint64(32))


def _split_words(rows: jnp.ndarray) -> jnp.ndarray:
    """[..., W] uint64 -> [..., 2W] uint32 (lo, hi interleaved)."""
    halves = jnp.stack([rows.astype(jnp.uint32),
                        (rows >> jnp.uint64(32)).astype(jnp.uint32)], -1)
    return halves.reshape(rows.shape[:-1] + (-1,))


_ROW = 128    # u32 words per row of a flat store's [R, 128] gather view


def _gather_runs(store: jnp.ndarray, start: jnp.ndarray, n: int
                 ) -> jnp.ndarray:
    """[N] word offsets, each a multiple of ``n`` -> the [N, n] u32 runs
    of a flat store that start there.

    Where ``n`` divides 128 words or 128 divides ``n``, the store is read
    as [R, 128] rows (a bitcast of its tiled 1-D layout, no copy) by a
    gather of whole rows, one index per row, and each run is picked out
    of its row by a mask.  XLA:TPU pays a gather per index: for 1,024
    runs of 32 words, 16 us a call against 652 us for the element gather
    (one index per word) and 1.2 ms for the slice gather (a loop over
    runs), on a v5e.  Other run lengths take the element gather."""
    start = start.astype(jnp.int32)
    if store.shape[0] % _ROW == 0 and _ROW % n == 0:
        per = _ROW // n
        rows = store.reshape(-1, _ROW)[start // _ROW]              # [N, 128]
        pick = (start % _ROW // n)[:, None] \
            == jnp.arange(per, dtype=jnp.int32)
        return jnp.sum(jnp.where(pick[:, :, None],
                                 rows.reshape(-1, per, n), jnp.uint32(0)),
                       axis=1, dtype=jnp.uint32)
    if store.shape[0] % _ROW == 0 and n % _ROW == 0:
        r = start[:, None] // _ROW + jnp.arange(n // _ROW, dtype=jnp.int32)
        return store.reshape(-1, _ROW)[r].reshape(-1, n)
    return store[start[:, None] + jnp.arange(n, dtype=jnp.int32)]


def sharers_gather(sh: jnp.ndarray, fidx: jnp.ndarray, assoc: int,
                   words: int, way: Optional[jnp.ndarray] = None
                   ) -> jnp.ndarray:
    """[N] flat entry ids -> [N, assoc, words] uint64 sharer rows, or
    with ``way`` ([N]) the [N, words] row of that way alone: one run of
    contiguous words per entry either way (_gather_runs)."""
    w32 = 2 * words
    fidx = fidx.astype(jnp.int32)
    if way is None:
        block = _gather_runs(sh, fidx * (assoc * w32), assoc * w32)
        return _join_words(block.reshape(fidx.shape[0], assoc, w32))
    base = (fidx * assoc + way.astype(jnp.int32)) * w32
    return _join_words(_gather_runs(sh, base, w32))


def sharers_add(sh: jnp.ndarray, assoc: int, words: int, *, rows=None,
                bits=None, clear_bits: bool = False) -> jnp.ndarray:
    """Every sharer update of one step as ONE uint32 scatter-add.

    ``rows = (mask, fidx, way, old, new)`` rewrites the row of entry
    (fidx, way) from ``old`` to ``new`` ([N, words] uint64) where
    ``mask``: each u32 word adds its own modular ``new - old`` (a uint64
    delta split in halves would be wrong wherever the low half borrows).
    ``bits = (mask, fidx, way, tile)`` adds tile's bit to the row, or
    subtracts it with ``clear_bits`` (the bit must then be set, and for
    an add clear).  Masked-off updates index one past the end and
    drop."""
    w32 = 2 * words
    end = sh.shape[0]

    def base(fidx, way):
        return (fidx.astype(jnp.int32) * assoc + way.astype(jnp.int32)) * w32

    idx, vals = [], []
    if rows is not None:
        mask, fidx, way, old, new = rows
        i = base(fidx, way)[:, None] + jnp.arange(w32, dtype=jnp.int32)
        idx.append(jnp.where(mask[:, None], i, end).reshape(-1))
        vals.append((_split_words(new) - _split_words(old)).reshape(-1))
    if bits is not None:
        mask, fidx, way, tile = bits
        tile = tile.astype(jnp.int32)
        idx.append(jnp.where(mask, base(fidx, way) + tile // 32, end))
        bit = jnp.uint32(1) << (tile % 32).astype(jnp.uint32)
        vals.append(jnp.uint32(0) - bit if clear_bits else bit)
    return sh.at[jnp.concatenate(idx)].add(jnp.concatenate(vals),
                                           mode="drop")


def dir_sharers_view(state: "SimState", assoc: int) -> jnp.ndarray:
    """Flat sharer store -> [A, F, W] uint64 word-minor view (for tests
    and tools; the engine reads rows through sharers_gather)."""
    F = state.dir_word.shape[-1] // (2 * assoc)
    rows = _join_words(state.dir_sharers.reshape(F, assoc, -1))
    return jnp.moveaxis(rows, 1, 0)


def _as_int64(words: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(words, jnp.int64)


def dir_gather(dw: jnp.ndarray, fidx: jnp.ndarray, assoc: int
               ) -> jnp.ndarray:
    """[N] flat entry ids -> [N, assoc] int64 packed words of every way:
    each entry's run of 2*assoc u32 words (_gather_runs), whose halves
    are joined on the gathered rows alone."""
    w32 = 2 * assoc
    return _as_int64(_join_words(
        _gather_runs(dw, fidx.astype(jnp.int32) * w32, w32)))


def dir_set(dw: jnp.ndarray, assoc: int, mask, fidx, way, word
            ) -> jnp.ndarray:
    """Write the packed int64 ``word`` ([N]) into entry (fidx, way) where
    ``mask``: both u32 halves in ONE 1-D scatter.  Masked-off writes index
    one past the end and drop; writes that meet in one entry must carry
    the same word."""
    base = (fidx.astype(jnp.int32) * assoc + way.astype(jnp.int32)) * 2
    idx = jnp.where(mask[:, None],
                    base[:, None] + jnp.arange(2, dtype=jnp.int32),
                    dw.shape[0])
    halves = _split_words(jax.lax.bitcast_convert_type(
        jnp.asarray(word, jnp.int64), jnp.uint64)[:, None])
    return dw.at[idx.reshape(-1)].set(halves.reshape(-1), mode="drop")


def dir_live_entries(dw: jnp.ndarray) -> jnp.ndarray:
    """Entries not in I: the state bits (0..2) of each entry's low word,
    the even words of the store, without a relayout of it."""
    even = jnp.arange(dw.shape[0], dtype=jnp.int32) % 2 == 0
    return jnp.sum(((dw & 7) != 0) & even, dtype=jnp.int64)


def dir_word_view(dw: jnp.ndarray, assoc: int) -> jnp.ndarray:
    """Flat directory store -> [A, F] int64 packed words (for tests and
    tools; the engine reads entries through dir_gather)."""
    return _as_int64(_join_words(dw.reshape(-1, 2 * assoc))).T


def dir_word_from_2d(words: np.ndarray) -> np.ndarray:
    """The [..., A, F] int64 dir_word of checkpoint schemas <= 29 -> the
    flat [..., F*A*2] uint32 store (leading axes kept)."""
    *lead, _a, _f = words.shape
    rows = np.moveaxis(words, -2, -1).astype(np.uint64)     # [..., F, A]
    halves = np.stack([rows & np.uint64(0xFFFFFFFF), rows >> np.uint64(32)],
                      axis=-1).astype(np.uint32)
    return halves.reshape(*lead, -1)


def sharers_from_planes(planes: np.ndarray, assoc: int) -> np.ndarray:
    """The [..., W*A, F] uint64 plane layout of checkpoint schemas <= 27
    -> the flat [..., F*A*2W] uint32 store (leading axes kept)."""
    *lead, wa, f = planes.shape
    rows = np.moveaxis(planes.reshape(*lead, wa // assoc, assoc, f),
                       (-3, -1), (-1, -3))               # [..., F, A, W]
    halves = np.stack([rows & np.uint64(0xFFFFFFFF), rows >> np.uint64(32)],
                      axis=-1).astype(np.uint32)
    return halves.reshape(*lead, -1)


def init_periods(params: SimParams) -> np.ndarray:
    p = np.zeros((params.num_tiles, NUM_DVFS_MODULES), dtype=np.int32)
    for m in DVFSModule:
        p[:, int(m)] = int(round(1000.0 / params.module_freq_ghz(m)))
    return p


def _dummy_cache(num_tiles: int) -> cachemod.CacheArrays:
    """Placeholder private-L2 arrays for shared-L2 protocols (the slice
    lives in the directory arrays; a full-size private L2 would waste HBM
    at scale).  Never probed — core/resolve gate on params.shared_l2."""
    return cachemod.CacheArrays(
        word=jnp.zeros((1, num_tiles, 1), dtype=jnp.int64),
        rr_ptr=jnp.zeros((num_tiles, 1), dtype=jnp.int32))


def _num_tel_rows() -> int:
    from graphite_tpu.obs.metrics import TEL_SERIES
    return len(TEL_SERIES)


NUM_CONDS = 64      # cond-var id space (like max_mutexes; ids clip)
WIN_BASE_INVALID = -(1 << 30)   # win_base sentinel: forces a refresh


def _win_cache_width(params: SimParams) -> int:
    """Cached block-window width: 4x the [T, K] window (round 9; was 2x),
    so partial window occupancy carries across sub-rounds and quantum
    cuts — with boundary-spanning windows a tile retires up to K slots
    per round instead of ~7, and a 2K cache forced the guarded full-trace
    refresh nearly every round; at 4K a tile consumes its resident span
    over ~3 full windows before a refresh is due, whatever the boundary
    did to the rounds in between.  Values stay bit-identical to direct
    gathers by construction (same clamped indices), so the width is pure
    cache geometry (checkpoint schema v23 carries the wider arrays).
    0 disables (no cache arrays, per-round trace gathers — the pre-cache
    engine shape)."""
    if params.window_cache and params.block_events > 0:
        return 4 * params.block_events
    return 0
DRAM_RING_SLOTS = 8  # busy-interval history per memory controller
MISS_FILTER_SLOTS = 1 << 14   # per-tile miss-type filter entries (2x the
#                               T1 L2's 8192 lines: "seen" memory must
#                               outlast the cache for capacity vs cold)


def stats_ring_enabled(params: SimParams) -> bool:
    """Does anything consume the stat_scalars series ring (statistics /
    progress / power trace)?  Telemetry has its own tel_* arrays."""
    return (params.stats_enabled or params.progress_enabled
            or params.power_trace_enabled)


def sampling_enabled(params: SimParams) -> bool:
    """Any consumer of the quantum-boundary sample hook configured?"""
    return stats_ring_enabled(params) or params.telemetry_enabled


def _nsamp(params: SimParams) -> int:
    """Sample-ring capacity: 1-row dummy when no sampling is configured."""
    return params.max_stat_samples if sampling_enabled(params) else 1


def make_state(params: SimParams,
               max_mutexes: int = 64,
               max_barriers: int = 16,
               channel_depth: int = 0,
               has_capi: bool = True,
               num_streams: int = 0) -> SimState:
    T = params.num_tiles
    S = num_streams if num_streams > 0 else T
    if S < T:
        raise ValueError(
            f"trace has {S} streams but params expect {T} tiles; "
            f"fewer streams than tiles is not supported")
    if S > T * params.max_threads_per_core:
        raise ValueError(
            f"trace has {S} streams > {T} tiles x "
            f"{params.max_threads_per_core} general/max_threads_per_core "
            f"(the reference refuses the same overflow, "
            f"thread_scheduler.cc:577)")
    sched = S > T
    if T > (1 << _DIR_OWNER_BITS) - 2:
        raise ValueError(
            f"num_tiles {T} exceeds the packed directory owner field "
            f"({(1 << _DIR_OWNER_BITS) - 2} max); widen _DIR_OWNER_BITS")
    if channel_depth <= 0:
        channel_depth = params.channel_depth
    n_entry = params.directory.associativity * T * params.directory.num_sets
    n_sharer = n_entry * 2 * sharer_words(T)
    # dir_word's 2 words per entry are never more than the sharer store's
    # 2W, so this one check keeps both flat stores inside int32 indexing.
    per_device = n_sharer // (params.tile_shards
                              if params.shard_state == "resident" else 1)
    if per_device >= 2**31:
        raise ValueError(
            f"the full-map sharer store needs {per_device} uint32 words per "
            f"device, past the int32 index range (2**31); use fewer "
            f"directory sets or ways, or more resident tile shards")
    return SimState(
        clock=jnp.zeros(T, dtype=jnp.int64),
        cursor=jnp.zeros(T, dtype=jnp.int32),
        done=jnp.zeros(T, dtype=bool),
        boundary=jnp.asarray(params.quantum_ps, dtype=jnp.int64),
        pend_kind=jnp.zeros(T, dtype=jnp.int32),
        pend_addr=jnp.zeros(T, dtype=jnp.int64),
        pend_issue=jnp.zeros(T, dtype=jnp.int64),
        pend_aux=jnp.zeros(T, dtype=jnp.int32),
        pend_extra=jnp.zeros(T, dtype=jnp.int64),
        win_meta=jnp.zeros((3, T, _win_cache_width(params)),
                           dtype=jnp.int32),
        win_addr=jnp.zeros((T, _win_cache_width(params)), dtype=jnp.int64),
        # Invalid base: the first window round's validity check fails for
        # every active tile, forcing the initial gather.
        win_base=jnp.full(T, WIN_BASE_INVALID, dtype=jnp.int32),
        win_seat=jnp.full(T, -1, dtype=jnp.int32),
        bp_table=jnp.zeros((T, params.core.bp_size), dtype=bool),
        l1i=cachemod.make_cache(T, params.l1i),
        l1d=cachemod.make_cache(T, params.l1d),
        l2=(_dummy_cache(T) if params.shared_l2
            else cachemod.make_cache(T, params.l2)),
        period_ps=jnp.asarray(init_periods(params)),
        # I-state, owner -1, tag/stamp 0 packs to the all-zeros word.
        dir_word=jnp.zeros(n_entry * 2, dtype=jnp.uint32),
        dir_sharers=jnp.zeros(n_sharer, dtype=jnp.uint32),
        mq_req=jnp.zeros((params.miss_chain, T), dtype=jnp.int64),
        mq_delta=jnp.zeros((params.miss_chain, T), dtype=jnp.int64),
        mq_extra=jnp.zeros((params.miss_chain, T), dtype=jnp.int64),
        mq_count=jnp.zeros(T, dtype=jnp.int32),
        mq_head=jnp.zeros(T, dtype=jnp.int32),
        chain_base=jnp.zeros(T, dtype=jnp.int64),
        chain_rel=jnp.zeros(T, dtype=jnp.int64),
        lq_ready=jnp.zeros((params.core.load_queue_entries, T),
                           dtype=jnp.int64),
        sq_ready=jnp.zeros((params.core.store_queue_entries, T),
                           dtype=jnp.int64),
        lq_next=jnp.zeros(T, dtype=jnp.int32),
        sq_next=jnp.zeros(T, dtype=jnp.int32),
        reg_ready=jnp.zeros(
            (32 if params.core.model == "iocoom" else 0, T),
            dtype=jnp.int64),
        dram_ring_start=jnp.zeros((DRAM_RING_SLOTS, T), dtype=jnp.int64),
        dram_ring_end=jnp.zeros((DRAM_RING_SLOTS, T), dtype=jnp.int64),
        dram_ring_ptr=jnp.zeros(T, dtype=jnp.int32),
        dram_qacc=jnp.zeros((6, T), dtype=jnp.float64),
        link_mem=noc_flight.make_link_rings(params.net_memory, T),
        link_user=noc_flight.make_link_rings(params.net_user, T),
        lock_holder=jnp.zeros(max_mutexes, dtype=jnp.int32),
        lock_free_at=jnp.zeros(max_mutexes, dtype=jnp.int64),
        bar_count=jnp.zeros(max_barriers, dtype=jnp.int32),
        bar_time=jnp.zeros(max_barriers, dtype=jnp.int64),
        spawned_at=jnp.full(S, -1, dtype=jnp.int64),
        done_at=jnp.zeros(S, dtype=jnp.int64),
        # Scheduler seats: streams 0..T-1 start seated on their own tile
        # (round-robin placement strm_tile = s % T is static — see
        # quantum.schedule_rotate); all [0]-shaped when S == T.
        seat_stream=(jnp.arange(T, dtype=jnp.int32) if sched
                     else jnp.zeros(0, jnp.int32)),
        seat_since=jnp.zeros(T if sched else 0, dtype=jnp.int64),
        seat_yield=jnp.zeros(T if sched else 0, dtype=bool),
        strm_cursor=jnp.zeros(S if sched else 0, dtype=jnp.int32),
        strm_clock=jnp.zeros(S if sched else 0, dtype=jnp.int64),
        strm_pend_kind=jnp.zeros(S if sched else 0, dtype=jnp.int32),
        strm_pend_addr=jnp.zeros(S if sched else 0, dtype=jnp.int64),
        strm_pend_issue=jnp.zeros(S if sched else 0, dtype=jnp.int64),
        strm_pend_aux=jnp.zeros(S if sched else 0, dtype=jnp.int32),
        strm_pend_extra=jnp.zeros(S if sched else 0, dtype=jnp.int64),
        strm_done=jnp.zeros(S if sched else 0, dtype=bool),
        strm_key=(jnp.arange(S, dtype=jnp.int64) if sched
                  else jnp.zeros(0, jnp.int64)),
        models_enabled=jnp.asarray(params.models_enabled_at_start),
        stat_filled=jnp.int32(0),
        stat_next=jnp.asarray(params.stat_interval_ps, dtype=jnp.int64),
        stat_time=jnp.zeros(_nsamp(params), dtype=jnp.int64),
        # The series ring only exists for its consumers; a telemetry-only
        # run samples into tel_* and must not carry a dead 13 x S ring.
        stat_scalars=jnp.zeros(
            (13, _nsamp(params) if stats_ring_enabled(params) else 1),
            dtype=jnp.int64),
        stat_icount=jnp.zeros(
            (_nsamp(params) if params.progress_enabled else 1, T),
            dtype=jnp.int64),
        tel_gauges=jnp.zeros(
            (_num_tel_rows(), _nsamp(params))
            if params.telemetry_enabled else (0, 0), dtype=jnp.int64),
        tel_cursor=jnp.zeros(
            (_nsamp(params), T) if params.telemetry_enabled else (0, T),
            dtype=jnp.int32),
        tel_pend=jnp.zeros(
            (_nsamp(params), T) if params.telemetry_enabled else (0, T),
            dtype=jnp.int32),
        ch_sent=jnp.zeros((T, T) if has_capi else (0, 0), dtype=jnp.int32),
        ch_recvd=jnp.zeros((T, T) if has_capi else (0, 0), dtype=jnp.int32),
        ch_time=jnp.zeros((channel_depth, T, T) if has_capi else (0, 0, 0),
                          dtype=jnp.int64),
        round_ctr=jnp.int32(0),
        ctr_window=jnp.int64(0),
        ctr_complex=jnp.int64(0),
        ctr_conflict=jnp.int64(0),
        ctr_resolve=jnp.int64(0),
        ctr_quantum=jnp.int64(0),
        ctr_ff=jnp.int64(0),
        ctr_ffq=jnp.int64(0),
        ff_events=jnp.int64(0),
        vm_brk=jnp.int64(0),
        vm_mmap_bytes=jnp.int64(0),
        vm_munmap_bytes=jnp.int64(0),
        seen_filter=jnp.zeros(
            (T, MISS_FILTER_SLOTS) if params.track_miss_types else (1, 1),
            dtype=jnp.int32),
        inv_filter=jnp.zeros(
            (T, MISS_FILTER_SLOTS) if params.track_miss_types else (1, 1),
            dtype=jnp.int32),
        counters=make_counters(T),
    )
