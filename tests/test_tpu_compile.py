"""Ahead-of-time compiles for a described TPU v5e — the one file that
asks the chip's compiler, with no chip attached (on-chip-measurement
guide, section 2).  A refusal here is what the chip would refuse, at no
chip time.  Nothing runs: these say nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the driver's workers all
import this file.  The persistent compile cache is off around these
compiles (an entry written for a described chip cannot be read back
here)."""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from graphite_tpu.engine import quantum, resident, resolve
from graphite_tpu.engine.state import TraceArrays, make_state
from graphite_tpu.events import synth
from graphite_tpu.parallel import mesh as meshmod

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from chip_smoke import RESIDENT_SETS, workload_params  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding_of):
    """ShapeDtypeStructs of ``tree``'s leaves, each with the sharding
    ``sharding_of(path, leaf)`` — shapes, since a described device
    cannot hold an array."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.asarray(x).dtype,
            sharding=sharding_of(path, x)), tree)


def test_megastep_t64_compiles_for_one_chip(topo):
    """The default (lax) quantum program at BASELINE config 1's width."""
    one = SingleDeviceSharding(topo.devices[0])
    params = workload_params(64, {})
    trace = synth.gen_radix(64, keys_per_tile=16, radix=16, seed=2)
    state = _shapes(make_state(params, has_capi=False), lambda *_: one)
    tarrays = _shapes(TraceArrays.from_trace(trace), lambda *_: one)
    compiled = jax.jit(functools.partial(quantum.megastep, params)).lower(
        state, tarrays).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30        # one v5e's HBM


_HLO_RESULT = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]"
                         r"\{([^}]*)\} (dynamic-update-slice|copy)\(")


def _relayouts(hlo, sizes):
    """Names of the dynamic-update-slice ops, and of the copies that
    transpose a 2-D layout ({0,1:...}), whose result has an element
    count in ``sizes``."""
    found = []
    for line in hlo.splitlines():
        m = _HLO_RESULT.match(line)
        if not m:
            continue
        name, dims, layout, op = m.groups()
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if n in sizes and (op == "dynamic-update-slice"
                           or layout.startswith("0,1:")):
            found.append(name)
    return found


@pytest.fixture(scope="module")
def resolve_t128(topo):
    """(state, HLO text) of memory resolve compiled for one v5e at 128
    tiles, where the sharer store's element counts differ from the
    directory words' old [A, F] count (at 64 they match)."""
    from graphite_tpu.engine.vparams import variant_params

    one = SingleDeviceSharding(topo.devices[0])
    params = workload_params(128, {})
    st = make_state(params, has_capi=False)
    vp = variant_params(params)
    hlo = jax.jit(lambda s: resolve.resolve_memory(params, vp, s)).lower(
        _shapes(st, lambda *_: one)).compile().as_text()
    return st, hlo


def test_resolve_keeps_sharer_store_layout(resolve_t128):
    """Memory resolve reads and writes the flat uint32 sharer store in
    place: no dynamic-update-slice and no transposing copy of an array of
    its size, in u32 words or in the u64 words of the 2-D plane layout
    it replaced (whose relayout took 72% of a 1,024-tile round)."""
    st, hlo = resolve_t128
    n = st.dir_sharers.size
    assert _relayouts(hlo, {n, n // 2}) == []


def test_resolve_keeps_dir_word_layout(resolve_t128):
    """The same for the flat uint32 directory-word store: nothing of its
    2*A*F u32 words, nor of the A*F int64 words of the [A, F] array it
    replaced (each of whose u32 halves was relaid out per conflict round:
    four transposing copies at 128 tiles, four loops of dynamic-update-
    slices at 1,024)."""
    st, hlo = resolve_t128
    n = st.dir_word.size
    assert _relayouts(hlo, {n, n // 2}) == []


def test_resident_quantum_compiles_on_four_chips(topo):
    """One resident quantum step over a 4-chip mesh of the described
    devices: the compiler keeps the routed all-to-alls and the barrier
    all-reduce (an s64 min all-reduce would be refused — the barrier is
    two int32 pmin words, parallel/mesh.pmin_i64)."""
    T = 64
    params = workload_params(T, {**RESIDENT_SETS, "tpu/tile_shards": 4})
    mesh = meshmod.make_mesh(topo.devices[:4])
    state = make_state(params, has_capi=False)
    tarrays = TraceArrays.from_trace(synth.gen_migratory(T, lines=64,
                                                         rounds=2))

    def placed(path, x):
        name = meshmod._path_name(path)
        return NamedSharding(mesh, meshmod.resident_spec_for(name, x, T))

    fn = resident.quantum_program(params, state, tarrays, mesh)
    compiled = jax.jit(fn).lower(_shapes(state, placed),
                                 _shapes(tarrays, placed)).compile()
    hlo = compiled.as_text()
    assert "all-to-all" in hlo
    assert "all-reduce" in hlo
    assert "all-gather" not in hlo


def test_window_kernel_refused_by_mosaic(topo):
    """``tpu/pallas_kernels = on`` fails with Mosaic's own error: the
    walk's int64 operands (simulated time is int64 ps) cannot become
    kernel scalars — why ``off`` is the default (ROADMAP A.1)."""
    from graphite_tpu.engine import core
    from graphite_tpu.engine.vparams import variant_params

    one = SingleDeviceSharding(topo.devices[0])
    params = workload_params(64, {"tpu/pallas_kernels": "on"})
    trace = synth.gen_radix(64, keys_per_tile=16, radix=16, seed=2)
    state = _shapes(make_state(params, has_capi=False), lambda *_: one)
    tarrays = _shapes(TraceArrays.from_trace(trace), lambda *_: one)
    vp = variant_params(params)
    with pytest.raises(ValueError, match="32-bit element types"):
        jax.jit(lambda s, t: core._block_retire(params, vp, s, t)).lower(
            state, tarrays).compile()
