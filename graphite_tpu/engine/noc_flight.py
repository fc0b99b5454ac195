"""Contended hop-by-hop mesh traversal (emesh_hop_by_hop).

The reference's hop-by-hop EMesh model routes each packet XY
dimension-ordered, one hop at a time, charging router + link delay plus a
per-link queue-model contention delay at every hop, and occupying each
traversed link for the packet's serialization time (reference:
common/network/models/network_model_emesh_hop_by_hop.cc:146 routePacket,
per-hop queue models in components/router/router_model.cc and
[network/emesh_hop_by_hop] carbon_sim.cfg:299-313).

TPU re-expression: all in-flight packets advance one hop per iteration of
a bounded ``lax.while_loop``; each iteration probes the 4*T directed mesh
links through ``queue_models.probe`` with the network's configured
``queue_model/type``:

  * ``history_list`` / ``history_tree``: a ring of busy intervals per link
    (``LinkRings``), as many slots as [queue_model/<type>] max_list_size.
    A packet arriving in an idle gap its flits fit in crosses at once,
    even when a packet of an earlier batch was served later; one merged
    interval is recorded per link and probe (``queue_models.fcfs_ring``).
    Past max_list_size the oldest interval is overwritten; the reference's
    M/G/1 fallback for packets older than its list is not modelled.
  * ``basic``: one carried horizon per link (a one-slot ring, in ``end``):
    a packet never starts before the latest service that link has given.
    The [queue_model/basic] moving average is not applied to links.

Within one probe, same-link packets serialize FCFS by arrival, ties by
row.  A packet's head advances router+link cycles per hop; each traversed
link stays busy for the packet's flit count (wormhole serialization), and
the tail's (flits-1)-cycle serialization is charged once at the
destination, matching the zero-load hop-counter formula when links are
idle.  Multicasts do not fly here: under the broadcast tree they stay
zero-load (``noc.max_hop_to_mask_ps``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from graphite_tpu.engine import queue_models
from graphite_tpu.engine.vparams import NetVariant, net_variant
from graphite_tpu.obs import scopes
from graphite_tpu.params import NetworkParams

# Link direction codes (outgoing link of a tile).
DIR_E, DIR_W, DIR_N, DIR_S = 0, 1, 2, 3
NUM_DIRS = 4


class LinkRings(NamedTuple):
    """Per-directed-link queue state, link id ``dir * T + tile``."""
    start: jnp.ndarray   # [R, NUM_DIRS * T] int64 busy-interval starts
    end: jnp.ndarray     # [R, NUM_DIRS * T] int64 busy-interval ends
    ptr: jnp.ndarray     # [NUM_DIRS * T] int32 next ring slot


def contended(net: NetworkParams) -> bool:
    """Does this network price link contention?  The hop-by-hop model
    with its queue model off charges per-hop latency only (the zero-load
    hop-counter form)."""
    return net.model == "emesh_hop_by_hop" and net.queue_model_enabled


def make_link_rings(net: NetworkParams, num_tiles: int) -> LinkRings:
    """Empty rings of ``net.queue_slots`` slots over the 4*T links;
    zero-sized when ``net`` is not contended."""
    R, L = ((net.queue_slots, NUM_DIRS * num_tiles) if contended(net)
            else (0, 0))
    return LinkRings(start=jnp.zeros((R, L), jnp.int64),
                     end=jnp.zeros((R, L), jnp.int64),
                     ptr=jnp.zeros(L, jnp.int32))


def _xy_step(pos: jnp.ndarray, dst: jnp.ndarray, mesh_width: int):
    """One XY-dimension-ordered routing decision.

    Returns (dir, next_pos, at_dst) for each packet (reference:
    network_model_emesh_hop_by_hop.cc computeNextDest — X first, then Y).
    """
    sx, sy = pos % mesh_width, pos // mesh_width
    tx, ty = dst % mesh_width, dst // mesh_width
    go_e = sx < tx
    go_w = sx > tx
    go_y = ~go_e & ~go_w
    go_n = go_y & (sy < ty)
    d = jnp.where(go_e, DIR_E,
                  jnp.where(go_w, DIR_W,
                            jnp.where(go_n, DIR_N, DIR_S))).astype(jnp.int32)
    delta = jnp.where(go_e, 1,
                      jnp.where(go_w, -1,
                                jnp.where(go_n, mesh_width, -mesh_width)))
    return d, (pos + delta).astype(pos.dtype), pos == dst


class FlightResult(NamedTuple):
    arrival: jnp.ndarray    # [K] int64 — tail arrival at the destination
    wait_ps: jnp.ndarray    # [K] int64 — total queueing delay en route
    rings: LinkRings        # updated link queues


@jax.named_scope(scopes.NOC_FLIGHT)
def flight(net: NetworkParams, mesh_width: int, mesh_height: int,
           src: jnp.ndarray, dst: jnp.ndarray, depart: jnp.ndarray,
           flits, active: jnp.ndarray, rings: LinkRings,
           period_ps: jnp.ndarray, vnet: NetVariant = None) -> FlightResult:
    """Fly a batch of packets src->dst, contending on shared links.

    src/dst: [K] int32 tiles; depart: [K] int64 ps; flits: scalar or [K];
    active: [K] bool (inactive packets neither move nor occupy);
    period_ps: [K] int32 ps per network cycle (sender's DVFS domain, used
    for the whole path as in the zero-load model).  ``vnet`` supplies the
    per-hop delays as traced operands (sweep engine); derived from
    ``net`` as constants when omitted.
    """
    if vnet is None:
        vnet = net_variant(net)
    K = src.shape[0]
    hop_cyc = jnp.asarray(vnet.router_delay_cycles
                          + vnet.link_delay_cycles, jnp.int64)
    max_hops = (mesh_width - 1) + (mesh_height - 1)
    T = rings.ptr.shape[0] // NUM_DIRS
    per = jnp.asarray(period_ps, jnp.int64)
    fl = jnp.broadcast_to(jnp.asarray(flits, jnp.int64), (K,))
    occ = fl * per                       # per-link serialization occupancy

    def cond(c):
        i, _pos, _t, infl, _rings, _w = c
        return (i < max_hops) & infl.any()

    def body(c):
        i, pos, t, infl, rg, wait = c
        d, npos, at = _xy_step(pos, dst, mesh_width)
        fly = infl & ~at
        link = (d * T + pos).astype(jnp.int32)
        start, _, delay, rs, re, rp, _ = queue_models.probe(
            net.queue_model_type, link, t, occ, fly, rg.start, rg.end,
            rg.ptr, None)
        t2 = jnp.where(fly, start + hop_cyc * per, t)
        return (i + 1, jnp.where(fly, npos, pos), t2, fly,
                LinkRings(rs, re, rp), wait + jnp.where(fly, delay, 0))

    pos0 = jnp.asarray(src, jnp.int32)
    t0 = jnp.where(active, depart, 0)
    carry = (jnp.int32(0), pos0, t0, active & (pos0 != dst), rings,
             jnp.zeros(K, dtype=jnp.int64))
    _, _, t, _, rings, wait = jax.lax.while_loop(cond, body, carry)
    arrival = jnp.where(active, t + jnp.maximum(fl - 1, 0) * per, 0)
    return FlightResult(arrival=arrival, wait_ps=wait, rings=rings)
