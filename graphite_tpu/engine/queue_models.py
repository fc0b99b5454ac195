"""Contention queue models, vectorized.

The reference estimates per-resource queueing delay with pluggable models —
moving-average 'basic', exact interval bookkeeping 'history_list', interval
tree + M/G/1 'history_tree' (reference: common/shared_models/
queue_model{_basic,_history_list,_history_tree,_m_g_1}.{h,cc},
[queue_model/*] carbon_sim.cfg:376-392) — each a mutable C++ object probed
once per packet.

The TPU engine processes a whole batch of requests per step, so the native
formulation is a *segmented FCFS sweep*: requests serialize per resource in
(arrival, row) order, and the exact FCFS completion times have the
associative closed form

    end_i = S_i + max_{j<=i}(a_j - S_{j-1})        (S = prefix sum of service)

computed as one dense pairwise compare-reduce (``_serial_fcfs``) — no
sequential loop.  ``probe`` dispatches on the configured type: the history
types serve on top of a ring of busy intervals per resource
(``fcfs_ring``), ``basic`` on top of one carried horizon (``basic_ring``),
``m_g_1`` from carried moments (``mg1_delay``).  Divergence: no interval
*interleaving* of out-of-order arrivals within one batch — arrivals are
sorted first, which the reference's interleaving_enabled mode also
effectively permits.  Callers: the DRAM controllers (engine/resolve.py)
and the mesh links (engine/noc_flight.py).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp


def _cumsum_doubling(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum via doubling: log2(K) rounds of shift + add.
    Written with explicit shifts rather than ``lax.associative_scan``/
    ``jnp.cumsum`` because XLA:TPU lowers int64 scans to reduce-windows
    whose scoped-VMEM footprint blows past the 16 MB limit at K >= 256;
    the doubling form stays elementwise."""
    v = x
    d = 1
    K = x.shape[0]
    while d < K:
        pv = jnp.concatenate([jnp.zeros((d,), x.dtype), v[:-d]])
        v = v + pv
        d *= 2
    return v


class RingFcfsResult(NamedTuple):
    start: jnp.ndarray       # [K] int64 service start times
    end: jnp.ndarray         # [K] int64 completion times
    delay: jnp.ndarray       # [K] int64 queueing delay
    ring_start: jnp.ndarray  # [R, C] updated busy-interval ring
    ring_end: jnp.ndarray
    ring_ptr: jnp.ndarray    # [C] int32 next slot


def _containing_end(res, t, svc, ring_start, ring_end):
    """[K] earliest feasible start >= t of a service of length ``svc`` on
    resource res given the recorded busy intervals: any interval
    overlapping [t, t + svc) pushes the start to that interval's end —
    this covers both "t inside a busy interval" and "idle gap too small
    for the service" (the reference only schedules into a free interval
    when the service FITS, queue_model_history_list.cc:103-120)."""
    rs = ring_start[:, res]                   # [R, K]
    re = ring_end[:, res]
    overlap = (rs < t[None, :] + svc[None, :]) & (t[None, :] < re)
    return jnp.max(jnp.where(overlap, re, t[None, :]), axis=0)


_BIG = 2**62


def _merged(res, valid, start, end, C):
    """Per resource of C: the earliest start and the latest end of its
    valid rows, and whether it has any (lo BIG and hi -BIG where not).
    One scatter-max over both fields (the start negated): the TPU runs a
    scatter update by update, a two-field update as one."""
    idx = jnp.clip(res, 0, C - 1).astype(jnp.int32)
    vals = jnp.stack([jnp.where(valid, -start, -_BIG),
                      jnp.where(valid, end, -_BIG)]).astype(jnp.int64)
    tbl = jnp.full((2, C), -_BIG, jnp.int64).at[:, idx].max(vals)
    return -tbl[0], tbl[1], tbl[1] > 0


def _ring_record(ring_start, ring_end, ring_ptr, lo, hi, has):
    """Write [lo, hi) into each resource's next ring slot where ``has``
    (the oldest interval is overwritten).  Every resource column takes at
    most one write, so this is a dense select over [R, C], not a scatter:
    a column scatter runs update by update on the TPU."""
    R = ring_start.shape[0]
    put = has[None, :] & (jnp.arange(R, dtype=jnp.int32)[:, None]
                          == (ring_ptr % R)[None, :])
    return (jnp.where(put, lo[None, :], ring_start),
            jnp.where(put, hi[None, :], ring_end),
            ring_ptr + has.astype(jnp.int32))


def fcfs_ring(resource: jnp.ndarray, arrival: jnp.ndarray,
              service: jnp.ndarray, valid: jnp.ndarray,
              ring_start: jnp.ndarray, ring_end: jnp.ndarray,
              ring_ptr: jnp.ndarray,
              occ_res: jnp.ndarray = None, occ_arr: jnp.ndarray = None,
              occ_svc: jnp.ndarray = None,
              occ_valid: jnp.ndarray = None,
              record_split: int = 1) -> RingFcfsResult:
    """Exact-within-batch FCFS against a bounded busy-interval HISTORY —
    the reference's history_list semantics (queue_model_history_list.cc):
    a request arriving in an idle gap starts immediately (insertion into
    the past), one arriving inside a busy interval waits for that
    interval's end.  The single carried-horizon form over-delays any
    request processed in a later batch than a farther-future one (phantom
    convoys when batch partitioning mixes arrival times — the miss-chain
    engine does); interval history bounds that error to genuine overlaps.

    ring_*: [R, C] busy intervals per resource, unsorted ring (oldest
    overwritten).  One merged interval is recorded per (resource, batch)
    — within-batch gaps are conservatively marked busy (deliberate
    deviation from history_list, which keeps every gap: the merge bounds
    ring size at one slot per batch; the error is over-delay only, and
    only for requests arriving inside a previous batch's span).

    occ_*: optional occupancy-only rows (writebacks): they insert busy
    intervals but take no delay and return no times.
    """
    C = ring_start.shape[1]
    res_eff = jnp.where(valid, resource, C).astype(jnp.int32)
    res_g = jnp.minimum(res_eff, C - 1)
    svc = jnp.where(valid, service, 0)

    # Interval-resolved base: chase overlapping-interval ends a few times
    # (adjacent intervals chain; 3 hops covers R=8 rings in practice).
    # Each hop also rejects idle gaps too small for the service, per the
    # reference's fits-check (queue_model_history_list.cc:103-120).
    base = arrival
    for _ in range(3):
        base = _containing_end(res_g, base, svc, ring_start, ring_end)
    base = jnp.where(valid, base, arrival)

    # Exact within-batch serialization, with the per-row interval base.
    start, end = _serial_fcfs(res_eff, base, arrival, svc, valid, C)
    delay = jnp.where(valid, start - arrival, 0)

    # ---- record busy intervals: one merged [min start, max end] per
    # (resource, batch) for the requests, one more for the occupancy rows.
    def merged(res_m, valid_m, s_m, e_m):
        return _merged(res_m, valid_m, s_m, e_m, C)

    if record_split > 1:
        # Split-record (the miss-chain replay's batches): one merged
        # interval per batch is fine when a batch's arrivals span less
        # than a service or two, but a chain pass serves MULTIPLE QUANTA
        # of one tile's sequential misses beside another tile's — a
        # single [min, max] record marks that whole span busy and
        # convoy-pushes every later-pass request that arrives inside it
        # (the phantom-convoy over-delay the docstring bounds grows with
        # chain depth; measured +5-7% completion drift on fft8 at full
        # window spanning).  Recording TWO merged intervals per
        # controller — requests below/above the controller's batch
        # midpoint — keeps the record exact for the common 1-2 requests
        # per controller per iteration and halves the phantom span
        # otherwise, at one extra ring slot per batch.
        loF, hiF, _hasF = merged(res_eff, valid, start, end)
        # (loF is the BIG sentinel on empty controllers, hiF -BIG —
        # compute the midpoint via the half-difference so it can't
        # overflow.)
        mid = loF + (jnp.maximum(hiF, loF) - loF) // 2    # [C]
        grpA = valid & (start < mid[res_g])
        groups = (grpA, valid & ~grpA)
    else:
        groups = (valid,)
    for grp in groups:
        ring_start, ring_end, ring_ptr = _ring_record(
            ring_start, ring_end, ring_ptr,
            *merged(res_eff, grp, start, end))
    if occ_res is not None:
        ring_start, ring_end, ring_ptr = _ring_record(
            ring_start, ring_end, ring_ptr,
            *merged(jnp.where(occ_valid, occ_res, C).astype(jnp.int32),
                    occ_valid, occ_arr, occ_arr + occ_svc))
    return RingFcfsResult(start=jnp.where(valid, start, 0),
                          end=jnp.where(valid, end, 0),
                          delay=delay, ring_start=ring_start,
                          ring_end=ring_end, ring_ptr=ring_ptr)


def insert_busy(ring_start: jnp.ndarray, ring_end: jnp.ndarray,
                ring_ptr: jnp.ndarray, res: jnp.ndarray, t0: jnp.ndarray,
                svc, valid: jnp.ndarray):
    """Occupancy-only insertion (writebacks off the critical path): one
    merged busy interval per (resource, call).  Returns updated rings."""
    svc = jnp.broadcast_to(jnp.asarray(svc, jnp.int64), t0.shape)
    return _ring_record(ring_start, ring_end, ring_ptr,
                        *_merged(res, valid, t0, t0 + svc,
                                 ring_start.shape[1]))


# Queue-model types the config may select (reference factory
# QueueModel::create, queue_model.cc:18-37, rejects everything else
# loudly; ``m_g_1`` is the reference's analytic fallback engine inside
# history_tree, exposed here as a directly selectable type per its own
# class queue_model_m_g_1.cc).  Single source of truth shared with the
# config validator so dispatch and validation cannot drift.
from graphite_tpu.params import QUEUE_MODEL_TYPES as VALID_TYPES  # noqa: E402


def _earlier_mask(res_eff, arrival, valid):
    """[K, K] bool: j is served before i (same resource, FCFS by
    (arrival, index))."""
    K = res_eff.shape[0]
    idx = jnp.arange(K, dtype=jnp.int32)
    same = valid[None, :] & valid[:, None] \
        & (res_eff[None, :] == res_eff[:, None])
    return same & ((arrival[None, :] < arrival[:, None])
                   | ((arrival[None, :] == arrival[:, None])
                      & (idx[None, :] < idx[:, None])))


def _serial_fcfs(res_eff, base, arrival, svc, valid, C, earlier=None):
    """Exact FCFS ends for rows serialized per resource in (arrival,
    index) order, each starting no earlier than its own ``base`` — the
    closed form end_i = S_i + max_{j<=i}(base_j - S_{j-1}) as a dense
    pairwise compare: sort-free, because XLA:TPU lowers sorts to
    serialized while-loops of dynamic-update-slices (profiled ~31 ms per
    [2048] lexsort), while a [K, K] masked compare-reduce is a few fused
    vector ops.
    ``earlier`` may carry a precomputed ordering mask (callers that
    already built it for the moving average avoid a second [K, K] pass).
    Returns (start, end)."""
    K = res_eff.shape[0]
    if earlier is None:
        earlier = _earlier_mask(res_eff, arrival, valid)
    S_prev = jnp.sum(jnp.where(earlier, svc[None, :], 0), axis=1)
    cand = base - S_prev
    self_or_earlier = earlier | (jnp.eye(K, dtype=bool) & valid[:, None])
    run = jnp.max(jnp.where(self_or_earlier, cand[None, :],
                            jnp.int64(-(2**62))), axis=1)
    start = run + S_prev
    return start, start + svc


# EMA window factor for the basic model's arithmetic-mean window: an
# exponential window with alpha = 1/W has the same effective length as
# the reference's W-sample sliding window (moving_average.h
# ARITHMETIC_MEAN) without carrying W samples per resource — a
# documented approximation; the two agree exactly for steady arrivals.
def _ma_ref_time(arrival, res_eff, valid, earlier_mask, ma_mean, ma_n,
                 window, C):
    """Per-row reference time = moving average of arrivals up to and
    including this row (reference QueueModelBasic::computeQueueDelay:
    ref_time = _moving_average->compute(pkt_time)).  Blends the carried
    cross-batch EMA with the exact within-batch prefix mean."""
    res_g = jnp.minimum(res_eff, C - 1)
    m0 = ma_mean[res_g]
    n0 = ma_n[res_g]
    arr_f = arrival.astype(jnp.float64)
    pref_n = jnp.sum(earlier_mask, axis=1).astype(jnp.float64) + 1.0
    pref_sum = jnp.sum(jnp.where(earlier_mask,
                                 arr_f[None, :], 0.0), axis=1) + arr_f
    pref_mean = pref_sum / pref_n
    # Carried-history weight decays by (1-1/W) per in-batch sample.
    w_hist = jnp.where(n0 > 0.0,
                       jnp.minimum(n0, window) / (jnp.minimum(n0, window)
                                                  + pref_n),
                       0.0)
    return (w_hist * m0 + (1.0 - w_hist) * pref_mean), pref_n, pref_sum


def _ma_update(ma_mean, ma_n, res_eff, arrival, valid, window, C):
    """Fold a batch of arrivals into the per-resource EMA state."""
    arr_f = jnp.where(valid, arrival, 0).astype(jnp.float64)
    r = jnp.where(valid, res_eff, C).astype(jnp.int32)
    cnt = jnp.zeros((C,), jnp.float64).at[r].add(
        jnp.where(valid, 1.0, 0.0), mode="drop")
    tot = jnp.zeros((C,), jnp.float64).at[r].add(arr_f, mode="drop")
    batch_mean = tot / jnp.maximum(cnt, 1.0)
    keep = jnp.power(1.0 - 1.0 / window, cnt)
    new_mean = jnp.where(cnt > 0,
                         keep * ma_mean + (1.0 - keep) * batch_mean,
                         ma_mean)
    # First batch seeds the mean directly.
    new_mean = jnp.where((ma_n == 0.0) & (cnt > 0), batch_mean, new_mean)
    return new_mean, jnp.minimum(ma_n + cnt, window)


def basic_ring(resource, arrival, service, valid,
               ring_start, ring_end, ring_ptr,
               occ_res=None, occ_arr=None, occ_svc=None,
               occ_valid=None, moments=None,
               ma_window: int = 0) -> RingFcfsResult:
    """The reference's 'basic' model: ONE carried horizon per resource —
    delay = max(0, queue_time - ref_time); queue_time = max(queue_time,
    ref_time) + service per probe (queue_model_basic.cc:36-63, no
    insertion into past idle gaps).  ``ref_time`` is the request's
    arrival, or its moving-averaged arrival when [queue_model/basic]
    moving_avg_enabled (the reference's default) — approximated here by
    an equal-effective-length exponential window (see _ma_ref_time).

    Batched: request AND occupancy rows serialize together in exact FCFS
    order on top of the horizon — what serial probes in arrival order
    produce (the reference's basic model charges every probe, writeback
    or not).

    State layout: the horizon lives in ring slot 0 (ring_end[0, :]);
    other slots are unused so the caller's ring arrays serve every model
    type unchanged.  ``moments`` rows 4-5 carry the EMA state.
    """
    K = resource.shape[0]
    R, C = ring_start.shape
    if occ_res is not None:
        resource = jnp.concatenate([resource, occ_res])
        arrival = jnp.concatenate([arrival, occ_arr])
        service = jnp.concatenate([service, occ_svc])
        valid = jnp.concatenate([valid, occ_valid])
    res_eff = jnp.where(valid, resource, C).astype(jnp.int32)
    svc = jnp.where(valid, service, 0)
    horizon = ring_end[0]                                    # [C]

    # One [K, K] ordering mask serves both the MA prefix and the FCFS
    # serialization — both order by true arrival (the reference's probes
    # arrive in call order; ref_time changes the delay charge, never the
    # service order).
    earlier_m = _earlier_mask(res_eff, arrival, valid)
    if ma_window > 0 and moments is not None:
        ref_f, _, _ = _ma_ref_time(arrival, res_eff, valid, earlier_m,
                                   moments[4], moments[5], ma_window, C)
        ref = jnp.where(valid, ref_f.astype(jnp.int64), arrival)
        new_mean, new_n = _ma_update(moments[4], moments[5], res_eff,
                                     arrival, valid, ma_window, C)
        moments = moments.at[4].set(new_mean).at[5].set(new_n)
    else:
        ref = arrival

    # Serialization runs on ref times (the reference's queue_time
    # advances from max(queue_time, ref_time)); the CHARGED delay is
    # queue_time - ref_time, applied from the true arrival.
    base = jnp.maximum(ref, horizon[jnp.minimum(res_eff, C - 1)])
    start_srl, end_srl = _serial_fcfs(res_eff, base, arrival, svc, valid,
                                      C, earlier=earlier_m)
    delay = jnp.where(valid, jnp.maximum(start_srl - ref, 0), 0)
    start = arrival + delay
    end = start + svc
    new_h = horizon.at[res_eff].max(jnp.where(valid, end_srl, 0),
                                    mode="drop")
    return RingFcfsResult(start=jnp.where(valid, start, 0)[:K],
                          end=jnp.where(valid, end, 0)[:K],
                          delay=delay[:K],
                          ring_start=ring_start,
                          ring_end=ring_end.at[0].set(new_h),
                          ring_ptr=ring_ptr), moments


def mg1_delay(resource, arrival, service, valid, moments,
              occ_res=None, occ_arr=None, occ_svc=None, occ_valid=None):
    """Analytic M/G/1 waiting time from carried service-time moments —
    the reference's QueueModelMG1 (queue_model_m_g_1.cc:18-47):

        W = 0.5 * mu * lam * (1/mu^2 + Var[s]) / (mu - lam),
        mu = n / sum_s,  lam = n / newest_arrival,  lam <= 0.999 mu.

    moments: [4, C] float64 — (sum_s, sum_s_sq, n, newest_arrival) per
    resource.  The whole batch is priced from the PRE-batch moments (the
    reference updates per probe; at engine batch sizes the per-probe
    drift within one batch is negligible), then the moments absorb the
    batch.  Returns (start, end, delay, new_moments).
    """
    C = moments.shape[1]
    res_eff = jnp.where(valid, resource, C).astype(jnp.int32)
    sum_s, sum_s2, n, newest = moments[0], moments[1], moments[2], moments[3]
    have = n > 0
    nn = jnp.maximum(n, 1.0)
    var = sum_s2 / nn - jnp.square(sum_s / nn)
    mu = nn / jnp.maximum(sum_s, 1.0)                        # 1/ps
    lam = nn / jnp.maximum(newest, 1.0)
    lam = jnp.minimum(lam, 0.999 * mu)
    w = 0.5 * mu * lam * (1.0 / jnp.square(mu) + var) / (mu - lam)
    w_c = jnp.where(have, jnp.ceil(w), 0.0).astype(jnp.int64)  # [C]
    delay = jnp.where(valid, w_c[jnp.minimum(res_eff, C - 1)], 0)
    start = arrival + delay
    end = start + jnp.where(valid, service, 0)

    def absorb(m, res, arr, svc, val):
        sv = jnp.where(val, svc, 0).astype(jnp.float64)
        r = jnp.where(val, res, C).astype(jnp.int32)
        m = m.at[0, r].add(sv, mode="drop")
        m = m.at[1, r].add(jnp.square(sv), mode="drop")
        m = m.at[2, r].add(jnp.where(val, 1.0, 0.0), mode="drop")
        return m.at[3, r].max(
            jnp.where(val, (arr + svc).astype(jnp.float64), 0.0),
            mode="drop")

    new_m = absorb(moments, res_eff, start, service, valid)
    if occ_res is not None:
        new_m = absorb(new_m, occ_res, occ_arr, occ_svc, occ_valid)
    return start, end, delay, new_m


def probe(qtype: str, resource, arrival, service, valid,
          ring_start, ring_end, ring_ptr, moments,
          occ_res=None, occ_arr=None, occ_svc=None, occ_valid=None,
          ma_window: int = 0, record_split: int = 1):
    """Config-dispatched queue probe (reference QueueModel::create,
    queue_model.cc:18-37): returns (start, end, delay, ring_start,
    ring_end, ring_ptr, moments).  ``qtype`` is static (from SimParams),
    so exactly one model is traced into the step program.
    ``record_split`` > 1 records split busy intervals (history types
    only — see fcfs_ring; the chain replay's wide-arrival batches).
    """
    if qtype in ("history_list", "history_tree"):
        q = fcfs_ring(resource, arrival, service, valid, ring_start,
                      ring_end, ring_ptr, occ_res=occ_res, occ_arr=occ_arr,
                      occ_svc=occ_svc, occ_valid=occ_valid,
                      record_split=record_split)
        return (q.start, q.end, q.delay, q.ring_start, q.ring_end,
                q.ring_ptr, moments)
    if qtype == "basic":
        q, moments2 = basic_ring(
            resource, arrival, service, valid, ring_start, ring_end,
            ring_ptr, occ_res=occ_res, occ_arr=occ_arr, occ_svc=occ_svc,
            occ_valid=occ_valid, moments=moments, ma_window=ma_window)
        return (q.start, q.end, q.delay, q.ring_start, q.ring_end,
                q.ring_ptr, moments2 if moments2 is not None else moments)
    if qtype == "m_g_1":
        start, end, delay, new_m = mg1_delay(
            resource, arrival, service, valid, moments, occ_res=occ_res,
            occ_arr=occ_arr, occ_svc=occ_svc, occ_valid=occ_valid)
        return start, end, delay, ring_start, ring_end, ring_ptr, new_m
    raise ValueError(f"unknown queue model type {qtype!r} "
                     f"(valid: {', '.join(VALID_TYPES)})")


def occupy(qtype: str, ring_start, ring_end, ring_ptr, moments,
           res, t0, svc, valid, ma_window: int = 0):
    """Occupancy-only insertion dispatched by type (writebacks off the
    critical path).  Returns (ring_start, ring_end, ring_ptr, moments)."""
    if qtype in ("history_list", "history_tree"):
        rs, re, rp = insert_busy(ring_start, ring_end, ring_ptr, res, t0,
                                 svc, valid)
        return rs, re, rp, moments
    if qtype == "basic":
        # Occupancy rows ARE probes to the reference's basic model
        # (every computeQueueDelay call advances _queue_time, writeback
        # or not): route them through basic_ring — exact per-row
        # serialization AND the same moving-average ref time as request
        # probes — and discard the delays.
        svc_b = jnp.broadcast_to(jnp.asarray(svc, jnp.int64), t0.shape)
        q, moments2 = basic_ring(
            res.astype(jnp.int32), t0, svc_b, valid, ring_start, ring_end,
            ring_ptr, moments=moments, ma_window=ma_window)
        return (ring_start, q.ring_end, ring_ptr,
                moments2 if moments2 is not None else moments)
    if qtype == "m_g_1":
        svc_b = jnp.broadcast_to(jnp.asarray(svc, jnp.int64), t0.shape)
        sv = jnp.where(valid, svc_b, 0).astype(jnp.float64)
        C = moments.shape[1]
        r = jnp.where(valid, res, C).astype(jnp.int32)
        m = moments.at[0, r].add(sv, mode="drop")
        m = m.at[1, r].add(jnp.square(sv), mode="drop")
        m = m.at[2, r].add(jnp.where(valid, 1.0, 0.0), mode="drop")
        m = m.at[3, r].max(
            jnp.where(valid, (t0 + svc_b).astype(jnp.float64), 0.0),
            mode="drop")
        return ring_start, ring_end, ring_ptr, m
    raise ValueError(f"unknown queue model type {qtype!r} "
                     f"(valid: {', '.join(VALID_TYPES)})")
