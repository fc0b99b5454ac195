"""Design-space partition of ``SimParams`` + sweep-spec parsing.

Every ``SimParams`` leaf is either

  * **STRUCTURAL** — shape- or program-bearing: tile counts, cache and
    directory geometry, model selections, engine loop caps (block_events
    K, miss-chain depth, rounds per quantum), queue-model history
    lengths.  All variants batched into one vmapped program must agree
    on every structural leaf — they determine array shapes and the
    compiled program itself.
  * **VARIANT** — numeric scalars that only flow into timing math:
    core/cache/NoC/DRAM latencies and bandwidths, quantum lengths, DVFS
    frequencies, syscall costs.  These enter the engine as traced
    operands (engine/vparams.py) and may differ per batch lane.

The partition is DECLARED here and enforced two ways: the completeness
test (tests/test_sweep.py) walks every numeric leaf and fails when a new
``SimParams`` field is unclassified — a new leaf cannot silently default
into the batch and break vmap safety — and ``structural_signature``
refuses to bucket variants whose structural leaves differ.

Notes on individual calls:

  * ``core.static_costs`` is STRUCTURAL even though it is a latency
    table: the costs are baked into the TRACE at annotation time
    (events/schema.py, tools/annotate_trace.py), and the trace is
    broadcast across the batch — varying them per lane would require
    per-lane traces, not per-lane operands.
  * ``dram.basic_ma_window`` is STRUCTURAL: it is the moving-average
    HISTORY LENGTH of the basic queue model (an effective sample-count
    knob, like the DRAM ring capacity), and its zero/non-zero state
    selects compiled code paths (queue_models.basic_ring).
  * ``max_frequency_ghz`` and ``dvfs_domains`` are VARIANT but
    state-borne rather than operand-borne: they set the initial
    ``period_ps`` arrays in make_state, which the sweep batches per
    lane like the rest of ``SimState``.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Iterator, List, Optional, Tuple

from graphite_tpu.config import Config, ConfigError
from graphite_tpu.params import SimParams

# ------------------------------------------------------------- partition

VARIANT_LEAVES = frozenset({
    # quantum cadence + DVFS points
    "quantum_ps", "thread_switch_quantum_ps", "max_frequency_ghz",
    # fast-forward accuracy budget (run-ahead ps; the MODE is structural)
    "fast_forward_span_ps",
    "dvfs_domains", "dvfs_sync_delay_cycles",
    # syscall service table
    "syscall_cost_cycles",
    # core
    "core.bp_mispredict_penalty",
    # cache hit/tag latencies
    "l1i.data_access_cycles", "l1i.tags_access_cycles",
    "l1d.data_access_cycles", "l1d.tags_access_cycles",
    "l2.data_access_cycles", "l2.tags_access_cycles",
    # directory
    "directory.access_cycles", "directory.limitless_trap_cycles",
    "directory.inv_ack_cycles",
    # DRAM
    "dram.latency_ns", "dram.per_controller_bandwidth_gbps",
    # NoCs (both logical networks)
    "net_user.flit_width_bits", "net_user.router_delay_cycles",
    "net_user.link_delay_cycles",
    "net_memory.flit_width_bits", "net_memory.router_delay_cycles",
    "net_memory.link_delay_cycles",
    # ATAC delays (absent leaves are simply never visited)
    "net_user.atac.unicast_distance_threshold",
    "net_user.atac.send_hub_router_delay",
    "net_user.atac.receive_hub_router_delay",
    "net_user.atac.star_net_router_delay",
    "net_user.atac.optical_link_delay_cycles",
    "net_memory.atac.unicast_distance_threshold",
    "net_memory.atac.send_hub_router_delay",
    "net_memory.atac.receive_hub_router_delay",
    "net_memory.atac.star_net_router_delay",
    "net_memory.atac.optical_link_delay_cycles",
})

_CACHE_STRUCT = ("line_size", "size_kb", "associativity", "num_banks")
_ATAC_STRUCT = ("num_tiles", "enet_width", "enet_height", "cluster_size",
                "num_clusters", "numx_clusters", "numy_clusters",
                "cluster_width", "cluster_height", "num_access_points")

STRUCTURAL_LEAVES = frozenset({
    "num_tiles", "mesh_width", "mesh_height", "max_threads_per_core",
    "core.static_costs",          # trace-baked (see module docstring)
    "core.bp_size", "core.load_queue_entries", "core.store_queue_entries",
    "l2_max_hw_sharers",
    "directory.total_entries", "directory.associativity",
    "directory.max_hw_sharers",
    "dram.num_controllers", "dram.controller_home_stride",
    "dram.basic_ma_window",       # EMA history length (see docstring)
    "stack_base", "stack_size_per_core", "technology_node",
    "stat_interval_ps", "max_stat_samples",
    "block_events", "max_events_per_quantum", "directory_conflict_rounds",
    "rounds_per_quantum", "quanta_per_step", "max_inv_fanout_per_round",
    "miss_chain", "max_resolve_rounds", "channel_depth",
    "tile_shards",                # selects the sharded vs solo program
    "shard_state",                # replicated vs resident program family
    "route_capacity",             # sizes the resident routing buffers
    "fast_forward",               # compiles the analytic leg in or out
    "segment_events",             # streaming ingest capacity — sizes the
    #   resident segment arrays (a SHAPE), so it can never ride a
    #   vmapped variant axis
    "net_user.queue_slots", "net_memory.queue_slots",  # link ring depth
} | {f"{c}.{f}" for c in ("l1i", "l1d", "l2") for f in _CACHE_STRUCT}
  | {f"{n}.atac.{f}" for n in ("net_user", "net_memory")
     for f in _ATAC_STRUCT})


def iter_leaves(obj, prefix: str = "") -> Iterator[Tuple[str, object]]:
    """Walk a (possibly nested) params dataclass into (dotted-path, value)
    leaves.  Tuples are ONE leaf (their elements share a classification);
    ``None`` sub-models (e.g. ``atac`` on an electrical mesh) are skipped
    — their leaves simply do not exist for that config."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        for f in dataclasses.fields(obj):
            yield from iter_leaves(getattr(obj, f.name),
                                   prefix + f.name + ".")
    elif obj is None:
        return
    else:
        yield prefix[:-1], obj


def _tuple_types(value) -> set:
    out = set()
    for v in value:
        if isinstance(v, tuple):
            out |= _tuple_types(v)
        else:
            out.add(type(v))
    return out


def is_numeric_leaf(value) -> bool:
    """Numeric leaves need an explicit STRUCTURAL/VARIANT call; strings
    and booleans are model selections — structural by nature."""
    if isinstance(value, bool):
        return False
    if isinstance(value, (int, float)):
        return True
    if isinstance(value, tuple):
        return any(t in (int, float) for t in _tuple_types(value))
    return False


def classify(path: str, value) -> str:
    """'variant' | 'structural' for one leaf; raises on an unclassified
    numeric leaf (the vmap-safety tripwire for new SimParams fields)."""
    if path in VARIANT_LEAVES:
        return "variant"
    if path in STRUCTURAL_LEAVES or not is_numeric_leaf(value):
        return "structural"
    raise ConfigError(
        f"SimParams leaf {path!r} is numeric but declared neither "
        f"STRUCTURAL nor VARIANT in graphite_tpu/sweep/space.py — new "
        f"leaves must be classified before they can ride (or be barred "
        f"from) a vmapped sweep batch")


def structural_signature(params: SimParams) -> tuple:
    """Hashable signature of every non-VARIANT leaf: two configs batch
    into one sweep bucket iff their signatures are equal."""
    return tuple(sorted(
        (path, repr(value)) for path, value in iter_leaves(params)
        if classify(path, value) != "variant"))


def variant_signature(params: SimParams) -> tuple:
    """Hashable signature of every VARIANT leaf — the other half of the
    partition.  (structural_signature, variant_signature, trace content
    hash) is the durable identity of one design point: the sweep
    service's results_db cache key, stable across processes and
    restarts."""
    return tuple(sorted(
        (path, repr(value)) for path, value in iter_leaves(params)
        if classify(path, value) == "variant"))


def structural_diff(a: SimParams, b: SimParams) -> List[str]:
    """Human-readable list of structural leaves where ``a`` and ``b``
    disagree (empty = batchable together)."""
    da = dict(structural_signature(a))
    db = dict(structural_signature(b))
    out = []
    for path in sorted(set(da) | set(db)):
        if da.get(path) != db.get(path):
            out.append(f"{path}: {da.get(path)} != {db.get(path)}")
    return out


# ------------------------------------------------- canonical static arg

def canonical_params(params: SimParams) -> SimParams:
    """``params`` with every operand-borne VARIANT leaf pinned to a fixed
    value — the jit-STATIC argument of the sweep engine's compiled
    program.  Two buckets with equal structural signatures then hash to
    ONE jit cache key regardless of which variant values they carry (the
    traced code reads those only through the batched ``VariantParams``
    operands), so the compile cache is bounded by bucket SHAPES, not by
    visited design points.  It also acts as a tripwire: an engine read of
    a variant leaf that bypasses ``VariantParams`` would price every
    sweep lane with these canonical constants and fail the
    sweep-vs-serial bit-identity gate (tests/test_sweep.py)."""
    r = dataclasses.replace

    def cache(c):
        return r(c, data_access_cycles=1, tags_access_cycles=1)

    def net(n):
        atac = None
        if n.atac is not None:
            atac = r(n.atac, unicast_distance_threshold=1,
                     send_hub_router_delay=1, receive_hub_router_delay=1,
                     star_net_router_delay=1, optical_link_delay_cycles=1)
        return r(n, flit_width_bits=64, router_delay_cycles=1,
                 link_delay_cycles=1, atac=atac)

    return r(
        params,
        quantum_ps=1_000_000,
        thread_switch_quantum_ps=10_000_000,
        fast_forward_span_ps=0,
        max_frequency_ghz=1.0,
        dvfs_domains=((1.0, ()),),
        dvfs_sync_delay_cycles=1,
        syscall_cost_cycles=(1,) * len(params.syscall_cost_cycles),
        core=r(params.core, bp_mispredict_penalty=1),
        l1i=cache(params.l1i), l1d=cache(params.l1d), l2=cache(params.l2),
        directory=r(params.directory, access_cycles=1,
                    limitless_trap_cycles=1, inv_ack_cycles=1),
        dram=r(params.dram, latency_ns=1.0,
               per_controller_bandwidth_gbps=1.0),
        net_user=net(params.net_user),
        net_memory=net(params.net_memory),
    )


# --------------------------------------------------- sweep-spec parsing

def parse_sweep_spec(specs: List[str]) -> List[Dict[str, str]]:
    """Declarative sweep grammar -> per-variant config-override dicts.

    Each spec string is one AXIS:

      * ``key=v1,v2,...``                    — the axis takes each value
      * ``key1=a1,a2;key2=b1,b2``            — ';'-joined keys ZIP (the
        axis takes (a1, b1) then (a2, b2); lengths must match)

    The variant list is the CROSS PRODUCT of the axes, in spec order
    (later axes vary fastest).  Keys are config paths (``section/key``,
    the same grammar as ``--set``); a key may appear on only one axis.

        parse_sweep_spec(["dram/latency=80,120",
                          "l2_cache/T1/data_access_time=6,8"])
        -> [{latency: 80, dat: 6}, {latency: 80, dat: 8},
            {latency: 120, dat: 6}, {latency: 120, dat: 8}]
    """
    axes: List[List[Dict[str, str]]] = []
    seen_keys: set = set()
    for spec in specs:
        keyvals: List[Tuple[str, List[str]]] = []
        for part in spec.split(";"):
            part = part.strip()
            if not part:
                continue
            key, sep, raw = part.partition("=")
            key = key.strip()
            if not sep or "/" not in key:
                raise ConfigError(
                    f"bad sweep spec {part!r}: expected section/key=v1,v2,...")
            values = [v.strip() for v in raw.split(",")]
            if not values or any(not v for v in values):
                raise ConfigError(f"bad sweep values in {part!r}")
            if key in seen_keys:
                raise ConfigError(
                    f"sweep key {key!r} appears on more than one axis")
            seen_keys.add(key)
            keyvals.append((key, values))
        if not keyvals:
            raise ConfigError(f"empty sweep spec {spec!r}")
        n = len(keyvals[0][1])
        for key, values in keyvals[1:]:
            if len(values) != n:
                raise ConfigError(
                    f"zipped sweep axis {spec!r}: {key!r} has "
                    f"{len(values)} values, expected {n}")
        axes.append([{k: v[i] for k, v in keyvals} for i in range(n)])
    variants: List[Dict[str, str]] = []
    for combo in itertools.product(*axes):
        merged: Dict[str, str] = {}
        for d in combo:
            merged.update(d)
        variants.append(merged)
    return variants


def variant_label(overrides: Dict[str, str]) -> str:
    """Short stable label for one variant's override point.  Key names
    shorten to their last path component unless two swept keys share it
    (l1/l2 data_access_time), which would collapse distinct axes into
    one label — those keep the full path."""
    if not overrides:
        return "base"
    tails = [k.rsplit("/", 1)[-1] for k in overrides]
    dup = {t for t in tails if tails.count(t) > 1}
    def short(k):
        t = k.rsplit("/", 1)[-1]
        return k if t in dup else t
    return ",".join(f"{short(k)}={v}" for k, v in sorted(overrides.items()))


def build_variants(cfg: Config, specs: List[str],
                   num_tiles: Optional[int] = None
                   ) -> List[Tuple[str, Dict[str, str], SimParams]]:
    """Sweep specs -> [(label, overrides, SimParams)], validated: every
    variant must share the base config's STRUCTURAL signature (a swept
    structural key — a cache size, a tile count — fails loudly with the
    differing leaves, instead of silently compiling per point)."""
    points = parse_sweep_spec(specs)
    out = []
    base_sig = None
    for overrides in points:
        c = cfg.copy()
        for k, v in overrides.items():
            c.set(k, v)
        p = SimParams.from_config(c, num_tiles=num_tiles)
        sig = structural_signature(p)
        if base_sig is None:
            base_sig = sig
        elif sig != base_sig:
            diff = structural_diff(out[0][2], p)
            raise ConfigError(
                "sweep crosses a STRUCTURAL boundary — these keys change "
                "shapes or the compiled program and cannot vary within "
                "one vmapped batch (split into separate sweeps): "
                + "; ".join(diff[:8]))
        out.append((variant_label(overrides), overrides, p))
    return out
