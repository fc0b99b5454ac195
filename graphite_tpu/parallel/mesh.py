"""Tile-axis sharding over a jax device mesh.

This is the TPU-native replacement for the reference's multi-process
distribution: Graphite partitions target tiles across host processes with
TCP sockets carrying modeled packets between them and a process barrier in
the transport (reference: common/misc/config.h:173
computeProcessToTileMapping, common/transport/socktransport.cc:61-287).
Here the tile axis of every state array is sharded over a
``jax.sharding.Mesh``; cross-tile gathers/scatters in the resolve phase
(requests to home directories, invalidation fan-out) compile to XLA
collectives riding ICI, and the quantum min-reduction is the barrier.

Multi-host scaling rides the same mechanism: `jax.distributed` extends the
mesh across hosts (ICI within a slice, DCN across), with no engine changes
— the reference needed ssh spawners and a socket fabric for the same reach
(tools/spawn_master.py).  tools/multihost_dryrun.py (tests/test_multihost.py)
exercises the two-process path: coordinator-connected processes run one
fused megastep over a global 8-device mesh with collectives crossing the
process boundary (capability-probed first — the CPU backend refuses
cross-process computations).

Two sharding mechanisms live here, one current and one superseded:

  * **Explicit shard_map** (``tpu/tile_shards`` > 1, round 11 — the
    CURRENT path): :func:`shard_wrap` wraps the quantum program in
    ``shard_map`` over this mesh with every operand replicated; inside,
    the engine slices ONLY the block window's operands to the shard's
    T/S tiles (engine/kernels/window.run_window_sharded), all_gathers
    the walk's outputs back, and reduces the quantum barrier with an
    explicit ``pmin`` — the ZSim bound-weave shape: a shard-local bound
    phase with ZERO cross-device traffic, then a bounded set of
    explicit collectives.
  * **GSPMD auto-sharding** (:func:`shard_pytree` under a whole-program
    jit — SUPERSEDED as the scale-out path): device_put the state
    tile-sharded and let the partitioner guess.  Measured 0.95x on 8
    CPU devices (pure overhead: resolve's full-T gathers/scatters force
    resharding of everything — PROFILE.md round 11).  It remains the
    placement layer for multi-host dryruns and the resharding-on-restore
    tests, not the performance path.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

TILE_AXIS = "tiles"


def pmin_i64(x: jax.Array, axis: str = TILE_AXIS) -> jax.Array:
    """Exact ``pmin`` of int64 values over ``axis``.  The TPU compiler
    lowers only Sum all-reduces of 64-bit integers (an s64 min is
    refused: "Supported lowering only of Sum all reduce"), so the min
    runs as two int32 pmins: the high words, then the biased low words
    of the shards that hold the minimal high word.  Exact over the whole
    int64 range."""
    hi = (x >> 32).astype(jnp.int32)
    lo = ((x & 0xFFFFFFFF) - 2**31).astype(jnp.int32)
    mhi = jax.lax.pmin(hi, axis)
    mlo = jax.lax.pmin(
        jnp.where(hi == mhi, lo, jnp.iinfo(jnp.int32).max), axis)
    return (mhi.astype(jnp.int64) << 32) \
        | ((mlo.astype(jnp.int64) + 2**31) & 0xFFFFFFFF)


def make_mesh(devices: Optional[Sequence[jax.Device]] = None,
              axis: str = TILE_AXIS) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), (axis,))


def shard_wrap(tile_shards: int, fn: Callable, nargs: int) -> Callable:
    """Wrap ``fn(*nargs arrays/pytrees)`` in ``shard_map`` over the first
    ``tile_shards`` devices when sharding is on; the identity at 1 — the
    single-device program is untouched, bit for bit.

    Every in/out spec is REPLICATED (``P()``): the engine's state stays
    whole on every device, and the sharded work happens INSIDE ``fn``
    via ``lax.axis_index`` slicing (the window walk) + explicit
    collectives (all_gather, the pmin barrier).  Replication also makes
    the bit-identity contract structural — each shard computes the same
    full-T arrays wherever it is not explicitly sliced.
    ``check_vma=False`` because the engine's while_loops and explicit
    collectives defeat the varying-axes checker, not because anything is
    unreplicated."""
    if tile_shards <= 1:
        return fn
    devices = jax.devices()
    if len(devices) < tile_shards:
        raise ValueError(
            f"tpu/tile_shards={tile_shards} needs at least that many "
            f"devices; jax sees {len(devices)} (force virtual CPU "
            f"devices with --xla_force_host_platform_device_count)")
    mesh = make_mesh(devices[:tile_shards])
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * nargs,
                     out_specs=P(), check_vma=False)


# Tile-axis position per engine array field.  Engine arrays keep small
# structural dims (assoc ways, bitmap words, channel slots, event fields)
# LEADING so the minor two dims stay large — TPU pads the minor dims to
# (8, 128) tiles, and a trailing assoc-sized axis wastes 8-16x memory and
# bandwidth — which puts the tile axis at position 0, 1, or 2 depending on
# the array.  Matching by field name (not by axis size) avoids sharding a
# structural axis that happens to equal the tile count (e.g. channel_depth
# == num_tiles).
_TILE_AXIS_BY_FIELD = {
    "word": 1, "meta": 1,            # CacheArrays [A, T, sets] / trace
    "win_meta": 1,                   # [3, T, WC] window-cache slice
    #   (WC = 4K since the round-9 boundary-spanning windows; win_addr/
    #   win_base/win_seat and the round-9 chain_fanout_served/
    #   chain_fallback counters are tile-leading, covered by the
    #   default axis-0 rule below)
    "dir_word": 0,                   # [T*dsets*A*2] u32 (entry-major flat)
    "dir_sharers": 0,                # [T*dsets*A*2W] u32 (entry-major flat)
    "ch_time": 1,                    # [D, T, T]
    "mq_req": 1,                     # [P, T] banked miss chains
    "mq_delta": 1, "mq_extra": 1,    # (blocking chain replay, round 7)
    "lq_ready": 1, "sq_ready": 1,    # [entries, T]
    "dram_ring_start": 1, "dram_ring_end": 1,   # [R, T]
    "stat_icount": 1,                # [S, T] progress-trace snapshots
    "tel_cursor": 1,                 # [S, T] telemetry cursor snapshots
    "tel_pend": 1,                   # [S, T] telemetry pend_kind snapshots
}

# Fields whose tile axis is FLATTENED with a per-tile structural axis
# (directory sets): tile-major, so an even split over the flat axis is an
# even split over tiles.
_TILE_MAJOR_FLAT = {"dir_word", "dir_sharers"}


def tile_sharding(mesh: Mesh, num_tiles: int):
    """Sharding-spec builder: each array's tile axis is split over the
    mesh; global arrays (sync objects, the quantum boundary) replicate."""

    def spec_for(name: str, leaf: Any):
        shape = np.shape(leaf)
        ax = _TILE_AXIS_BY_FIELD.get(name, 0)
        ok = len(shape) > ax and (
            shape[ax] == num_tiles
            or (name in _TILE_MAJOR_FLAT and shape[ax] % num_tiles == 0))
        if ok:
            return NamedSharding(mesh, P(*([None] * ax + [TILE_AXIS])))
        return NamedSharding(mesh, P())

    return spec_for


def shard_pytree(tree: Any, mesh: Mesh, num_tiles: int) -> Any:
    """Place a pytree (SimState / TraceArrays) onto the mesh, tile-sharded."""
    spec = tile_sharding(mesh, num_tiles)

    def place(path, leaf):
        name = ""
        for p in reversed(path):
            if hasattr(p, "name"):
                name = p.name
                break
        return jax.device_put(leaf, spec(name, leaf))

    return jax.tree_util.tree_map_with_path(place, tree)


# ------------------------------------------------- round 15: resident mode
# (tpu/shard_state = "resident", engine/resident.py): state leaves stay
# SHARDED along the tile axis for the whole run, so shard_map in/out specs
# are per-leaf PartitionSpecs instead of the replicated P() above.  The
# field->axis table is the replicated one plus dram_qacc (the [6, T] DRAM
# moment accumulators never cross the replicated path's shard_map seam, so
# the round-11 table omits them).
_RESIDENT_EXTRA_AXES = {"dram_qacc": 1}


def _path_name(path) -> str:
    for p in reversed(path):
        if hasattr(p, "name"):
            return p.name
    return ""


def resident_spec_for_shape(name: str, shape, num_tiles: int):
    """PartitionSpec of one leaf SHAPE under resident sharding: tile axis
    split over the mesh, everything else (scalars, sync objects,
    zero-size compiled-out arrays) replicated."""
    ax = _TILE_AXIS_BY_FIELD.get(name, _RESIDENT_EXTRA_AXES.get(name, 0))
    ok = len(shape) > ax and (
        shape[ax] == num_tiles
        or (name in _TILE_MAJOR_FLAT and shape[ax] % num_tiles == 0
            and shape[ax] > 0))
    if ok:
        return P(*([None] * ax + [TILE_AXIS]))
    return P()


def resident_spec_for(name: str, leaf: Any, num_tiles: int):
    """PartitionSpec of one leaf under resident sharding."""
    return resident_spec_for_shape(name, np.shape(leaf), num_tiles)


def resident_specs(tree: Any, num_tiles: int) -> Any:
    """Matching pytree of PartitionSpecs for ``tree`` (SimState /
    TraceArrays / any container of named leaves) under resident
    sharding — the in_specs/out_specs form shard_map wants."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: resident_spec_for(_path_name(path), leaf,
                                             num_tiles),
        tree)


def resident_place(tree: Any, mesh: Mesh, num_tiles: int) -> Any:
    """device_put a pytree onto the mesh with resident (tile-sharded)
    placement — the driver-entry placement for resident runs."""

    def place(path, leaf):
        spec = resident_spec_for(_path_name(path), leaf, num_tiles)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, tree)
