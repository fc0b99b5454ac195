"""Network-on-chip latency models (vectorized, zero-load forms).

Covers the reference's NetworkModel plug-ins (reference:
common/network/network_model.h:39-207 and common/network/models/):

  * ``magic`` — zero-latency direct delivery
    (network_model_magic.cc routePacket).
  * ``emesh_hop_counter`` — analytical 2D electrical mesh: XY hop count x
    (router + link delay) + flit serialization, no contention
    (network_model_emesh_hop_counter.cc:143).
  * ``emesh_hop_by_hop`` — per-link contention, modeled in
    engine/noc_flight.py (hop-by-hop flights priced by the configured
    link queue model, over queues carried in ``SimState.link_mem``);
    resolve prices every memory-network unicast leg through it when this
    model and its queue model are selected.  The functions here still
    supply the zero-load forms for multicasts, and for the model with its
    queue model off.
  * ``atac`` — hybrid optical broadcast network, analytic form in
    engine/noc_atac.py (network_model_atac.cc); dispatched from the
    functions here.

All functions are elementwise over [K]-shaped tile-id arrays so one call
prices every in-flight packet at once.  Tiles are laid out row-major on a
``mesh_width x mesh_height`` grid, matching the reference's EMesh layout
(network_model_emesh_hop_counter.cc computePosition).
"""

from __future__ import annotations

import jax.numpy as jnp

from graphite_tpu.engine.vparams import NetVariant, net_variant
from graphite_tpu.params import NetworkParams

# NetPacket header bytes modeled on the wire (reference: common/network/
# network.h:27-55 — sender, receiver, type, length, time metadata).
PACKET_HEADER_BYTES = 8


def num_flits(payload_bytes, flit_width_bits: int):
    """Packet length in flits (reference: network_model.h flit math)."""
    bits = (payload_bytes + PACKET_HEADER_BYTES) * 8
    return (bits + flit_width_bits - 1) // flit_width_bits


def hop_count(src, dst, mesh_width: int):
    """Manhattan distance under XY dimension-ordered routing."""
    sx, sy = src % mesh_width, src // mesh_width
    dx, dy = dst % mesh_width, dst // mesh_width
    return jnp.abs(sx - dx) + jnp.abs(sy - dy)


def unicast_ps(net: NetworkParams, src, dst, payload_bytes,
               period_ps, mesh_width: int, vnet: NetVariant = None):
    """Zero-load packet latency in ps.

    ``period_ps``: int32 [K] — ps per cycle of the sender's network DVFS
    domain (latencies scale with DVFS, reference:
    network_model.h DVFS recompute).

    ``vnet`` carries the network's numeric delays as traced operands
    (sweep engine); omitted, they derive from ``net`` and trace as
    constants — the pre-sweep program, bit-identically.
    """
    if net.model == "magic":
        return jnp.zeros(jnp.shape(src), dtype=jnp.int64)
    if vnet is None:
        vnet = net_variant(net)
    if net.model == "atac":
        from graphite_tpu.engine import noc_atac
        return noc_atac.unicast_ps(net, src, dst, payload_bytes, period_ps,
                                   vnet=vnet)
    hops = hop_count(src, dst, mesh_width)
    flits = num_flits(payload_bytes, vnet.flit_width_bits)
    cycles = hops * (vnet.router_delay_cycles + vnet.link_delay_cycles) \
        + jnp.maximum(flits - 1, 0)
    return jnp.asarray(cycles, jnp.int64) * jnp.asarray(period_ps, jnp.int64)


def max_hop_to_mask_ps(net: NetworkParams, src, tile_mask,
                       payload_bytes, period_ps, mesh_width: int,
                       vnet: NetVariant = None):
    """Latency of the farthest unicast from ``src`` ([K]) to any tile set in
    ``tile_mask`` ([K, T] bool) — the invalidation-round-trip bound the
    directory charges when it must reach all sharers (reference:
    dram_directory_cntlr.cc invalidation fan-out).

    Masks with no bits set return 0.
    """
    if net.model == "magic":
        return jnp.zeros(jnp.shape(src), dtype=jnp.int64)
    if vnet is None:
        vnet = net_variant(net)
    if net.model == "atac":
        from graphite_tpu.engine import noc_atac
        return noc_atac.max_to_mask_ps(net, src, tile_mask, payload_bytes,
                                       period_ps, vnet=vnet)
    T = tile_mask.shape[-1]
    tiles = jnp.arange(T)
    hops = hop_count(src[:, None], tiles[None, :], mesh_width)  # [K, T]
    max_hops = jnp.max(jnp.where(tile_mask, hops, 0), axis=-1)
    flits = num_flits(payload_bytes, vnet.flit_width_bits)
    cycles = max_hops * (vnet.router_delay_cycles + vnet.link_delay_cycles) \
        + jnp.maximum(flits - 1, 0)
    cycles = jnp.where(tile_mask.any(axis=-1), cycles, 0)
    return jnp.asarray(cycles, jnp.int64) * jnp.asarray(period_ps, jnp.int64)
