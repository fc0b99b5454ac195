"""Kernel-mode resolution + shared pallas_call plumbing.

``tpu/pallas_kernels`` selects per-phase execution:

  * ``off``       — the untouched lax path, the default everywhere:
                    Mosaic refuses the kernels' int64 operands (ROADMAP
                    A.1), so no backend lowers them today.
  * ``interpret`` — ``pl.pallas_call(..., interpret=True)``: the same
                    kernel body evaluated by the Pallas interpreter on
                    any backend.  This is the CPU-testable path the
                    bit-identity gate runs.
  * ``on``        — real Mosaic lowering (one custom-call per phase),
                    resolved to ``tpu``.  A refusal surfaces as the
                    compiler's own error; nothing falls back to lax.

Phase support is gated here (``window_mode`` / ``chain_mode``): a config
the kernels do not cover (iocoom cores, non-divisible tile blocks) falls
back to lax for that phase — never a behavior change, because the kernel
and lax paths share one walk function and are bit-identical wherever
both run.

The pallas_call plumbing (:func:`call_blocked`) is shape-driven: inputs
and outputs are pytrees whose leaves each declare which axis (if any) is
the tile axis; leaves without one broadcast to every grid step.  Scalars
ride as (1, 1) operands (SMEM-shaped for the TPU path).
"""

from __future__ import annotations

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from graphite_tpu.params import SimParams


def kernels_mode(params: SimParams) -> str:
    """Resolve ``tpu/pallas_kernels`` to 'off' | 'interpret' | 'tpu'."""
    v = params.pallas_kernels
    return "tpu" if v == "on" else v


def tile_block(num_tiles: int, cap: int = 128) -> int:
    """Tile-block size of the window kernel's grid: the largest
    power-of-two divisor of T up to ``cap`` (T is a power of two in
    every supported mesh, so this is min(T, cap); a non-power-of-two T
    degrades to one block rather than a partial one)."""
    tb = min(num_tiles, cap)
    while tb > 1 and num_tiles % tb:
        tb //= 2
    return max(tb, 1)


def window_mode(params: SimParams) -> str:
    """Kernel mode for the block-window walk; 'off' when the config
    needs lax-only machinery (iocoom drain floors / register-annotated
    windows thread per-tile static masks the blocked kernel does not
    carry)."""
    mode = kernels_mode(params)
    if mode == "off":
        return "off"
    if params.core.model != "simple":
        return "off"
    return mode


def chain_mode(params: SimParams) -> str:
    """Kernel mode for the chain replay's classify phase.  The fast pass
    itself already requires simple cores + full_map + uncontended NoC
    (resolve.chain_fast_pass restrictions), so the kernel inherits those
    gates from its caller."""
    return kernels_mode(params)


def _as_operand(leaf):
    """Scalars become (1, 1) operands (TPU SMEM wants 2-D scalars)."""
    arr = jnp.asarray(leaf)
    if arr.ndim == 0:
        return arr.reshape(1, 1)
    return arr


def _load(ref, was_scalar: bool):
    val = ref[...]
    return val[0, 0] if was_scalar else val


def _block_spec(pl, shape, tile_axis, tb):
    if tile_axis is None or shape == ():
        blk = tuple(shape) if shape else (1, 1)
        nd = len(blk)
        return pl.BlockSpec(blk, lambda i, _nd=nd: (0,) * _nd)
    blk = tuple(tb if a == tile_axis else shape[a]
                for a in range(len(shape)))
    ta = tile_axis

    def imap(i, _ta=ta, _nd=len(blk)):
        return tuple(i if a == _ta else 0 for a in range(_nd))

    return pl.BlockSpec(blk, imap)


def call_blocked(fn, in_tree, in_axes, out_tree_shapes, out_axes,
                 num_tiles: int, mode: str, name: str):
    """Run ``fn(in_tree) -> out_tree`` as ONE pallas_call gridded over
    tile blocks.

    ``in_tree`` / ``out_tree_shapes``: pytrees of arrays / of
    ShapeDtypeStructs (from ``jax.eval_shape`` on the lax path, so the
    kernel's output contract is the walk function's, by construction).
    ``in_axes`` / ``out_axes``: matching pytrees of tile-axis ints (or
    None for broadcast leaves).  ``fn`` must be per-tile independent
    along those axes — the walk/classify functions are, by design.
    """
    from jax.experimental import pallas as pl

    in_leaves, treedef = jax.tree_util.tree_flatten(in_tree)
    ax_leaves = jax.tree_util.tree_leaves(
        in_axes, is_leaf=lambda x: x is None)
    assert len(ax_leaves) == len(in_leaves), (name, len(ax_leaves),
                                              len(in_leaves))
    out_leaves, out_treedef = jax.tree_util.tree_flatten(out_tree_shapes)
    oax_leaves = jax.tree_util.tree_leaves(
        out_axes, is_leaf=lambda x: x is None)
    assert len(oax_leaves) == len(out_leaves)

    tb = tile_block(num_tiles)
    grid = (num_tiles // tb,)

    # Trace the walk ONCE to a closed jaxpr AT BLOCK SHAPES (tile axes
    # sliced to tb — the shapes the kernel body actually sees; the walk
    # functions are shape-polymorphic over the tile axis, and every
    # shape-derived constant they mint — iotas, zero masks — is then
    # block-sized and identical for every grid step).  The jaxpr's
    # constants become extra broadcast operands — pallas_call kernels
    # may not close over consts — and the kernel body replays the jaxpr
    # on the loaded blocks.
    def _block_aval(leaf, ax):
        shape = tuple(jnp.shape(leaf))
        if ax is not None:
            shape = tuple(tb if a == ax else shape[a]
                          for a in range(len(shape)))
        return jax.ShapeDtypeStruct(shape, jnp.asarray(leaf).dtype)

    block_avals = jax.tree_util.tree_unflatten(
        treedef, [_block_aval(leaf, ax)
                  for leaf, ax in zip(in_leaves, ax_leaves)])
    closed = jax.make_jaxpr(lambda t: fn(t))(block_avals)
    consts = list(closed.consts)
    n_in = len(in_leaves)
    n_const = len(consts)
    all_in = consts + in_leaves
    all_axes = [None] * n_const + list(ax_leaves)
    scalars = [jnp.ndim(leaf) == 0 for leaf in all_in]
    operands = [_as_operand(leaf) for leaf in all_in]
    in_specs = [_block_spec(pl, op.shape, ax, tb)
                for op, ax in zip(operands, all_axes)]
    out_specs = [_block_spec(pl, tuple(o.shape), ax, tb)
                 for o, ax in zip(out_leaves, oax_leaves)]
    out_shape = [jax.ShapeDtypeStruct(tuple(o.shape), o.dtype)
                 for o in out_leaves]

    def kernel(*refs):
        ins = refs[:n_const + n_in]
        outs = refs[n_const + n_in:]
        loaded = [_load(r, sc) for r, sc in zip(ins, scalars)]
        res_leaves = jax.core.eval_jaxpr(
            closed.jaxpr, loaded[:n_const], *loaded[n_const:])
        assert len(res_leaves) == len(outs)
        for ref, val in zip(outs, res_leaves):
            ref[...] = val

    call = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=(mode == "interpret"),
        name=name)
    flat_out = call(*operands)
    if not isinstance(flat_out, (list, tuple)):
        flat_out = [flat_out]
    return jax.tree_util.tree_unflatten(out_treedef, list(flat_out))


def pack(nt, axes_table: dict, vp):
    """NamedTuple of operands (+ the VariantParams pytree) -> (dict of
    present leaves, dict of tile axes, vp treedef).  Dict trees flatten
    by sorted key, so operand and axis leaves stay aligned through
    pallas_call; None fields (machinery compiled out of this config)
    simply vanish."""
    d = {f: v for f, v in zip(type(nt)._fields, nt) if v is not None}
    axes = {f: axes_table[f] for f in d}
    vleaves, vdef = jax.tree_util.tree_flatten(vp)
    for i, leaf in enumerate(vleaves):
        d[f"zvp{i:03d}"] = leaf
        axes[f"zvp{i:03d}"] = None
    return d, axes, vdef


def unpack(cls, d: dict, vdef):
    """Inverse of :func:`pack` inside the kernel body."""
    nv = sum(1 for k in d if k.startswith("zvp"))
    vp = jax.tree_util.tree_unflatten(
        vdef, [d[f"zvp{i:03d}"] for i in range(nv)])
    nt = cls(**{f: d.get(f) for f in cls._fields})
    return nt, vp


def run_fused(core_fn, nt, vp, in_axes: dict, out_cls, out_axes: dict,
              grid_tiles: int, mode: str, name: str):
    """Run ``core_fn(operands, vp) -> out_cls(...)`` as one fused
    pallas_call (interpret or tpu).  ``grid_tiles`` is the tile count
    the in/out axes are blocked over (1 => a single whole-array grid
    step, the chain kernel's shape)."""
    d, axes, vdef = pack(nt, in_axes, vp)
    cls = type(nt)

    def fn(dd):
        nt2, vp2 = unpack(cls, dd, vdef)
        out = core_fn(nt2, vp2)
        return {f: v for f, v in zip(out_cls._fields, out)
                if v is not None}

    out_shapes = jax.eval_shape(fn, d)
    oaxes = {f: out_axes[f] for f in out_shapes}
    od = call_blocked(fn, d, axes, out_shapes, oaxes, grid_tiles, mode,
                      name)
    return out_cls(**{f: od.get(f) for f in out_cls._fields})


# ------------------------------------------------- structural evidence

def jaxpr_op_counts(fn, *args) -> dict:
    """Count the op classes the round-cost story is about in ``fn``'s
    closed jaxpr (recursively through scan/while/cond/pjit bodies):
    total equations, gathers, scatters, and pallas_call sites.  This is
    the CPU-checkable form of the "window phase collapses to one
    custom-call" claim — each pallas_call eqn lowers to exactly one TPU
    custom-call by construction."""
    closed = jax.make_jaxpr(fn)(*args)
    counts = {"eqns": 0, "gather": 0, "scatter": 0, "pallas_call": 0,
              "while": 0, "fori_or_scan": 0, "collective": 0}

    # Cross-device communication primitives, matched by prefix so each
    # family's variants (psum / psum_invariant, ...) count together.
    # The round-11 sharding gates assert the shard-LOCAL window phase has
    # zero of these and the whole sharded step a small bounded count.
    # Each family is ALSO counted under its own key (zero-initialized so
    # absent families read 0): the round-15 resident gate asserts the
    # per-family budget — zero all_gathers, a bounded all_to_all count,
    # the barrier's two int32 pmin words — not just the total.
    _COLLECTIVES = ("all_gather", "psum", "pmin", "pmax", "all_to_all",
                    "ppermute", "reduce_scatter", "pbroadcast")
    for fam in _COLLECTIVES:
        counts[fam] = 0

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            counts["eqns"] += 1
            prim = eqn.primitive.name
            if prim == "gather":
                counts["gather"] += 1
            elif prim.startswith("scatter"):
                counts["scatter"] += 1
            elif prim == "pallas_call":
                counts["pallas_call"] += 1
            elif prim == "while":
                counts["while"] += 1
            elif prim == "scan":
                counts["fori_or_scan"] += 1
            if prim.startswith(_COLLECTIVES):
                counts["collective"] += 1
                for fam in _COLLECTIVES:
                    if prim.startswith(fam):
                        counts[fam] += 1
                        break
            # Recurse into sub-jaxprs (loop/cond/pjit bodies ride in
            # eqn params) — pallas_call kernel jaxprs are deliberately
            # NOT descended into: their ops are fused inside one call.
            if prim != "pallas_call":
                for v in eqn.params.values():
                    for sub in _subjaxprs_of(v):
                        visit(sub)

    def _subjaxprs_of(v):
        if isinstance(v, jex_core.ClosedJaxpr):
            return [v.jaxpr]
        if isinstance(v, jex_core.Jaxpr):
            return [v]
        if isinstance(v, (list, tuple)):
            out = []
            for item in v:
                out.extend(_subjaxprs_of(item))
            return out
        return []

    visit(closed.jaxpr)
    return counts
