"""The trace reduction (union of device intervals, idle gaps, gap
attribution by harness span) and the per-layer metric readers, on the
CPU."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "harness"))

import core  # noqa: E402
import phases  # noqa: E402
import xtrace  # noqa: E402


def test_union_and_gaps_arithmetic():
    iv = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert xtrace.union([(s, e) for _, s, e in iv]) == [(0, 15), (20, 30)]
    assert xtrace.busy_ns([(s, e) for _, s, e in iv]) == 25
    assert xtrace.gaps(iv, -5, 40) == [(-5, 0), (15, 20), (30, 40)]
    assert xtrace.gaps(iv, 2, 12) == []
    spans = [("bench.window", -5, 40), ("bench.poll", 0, 40),
             ("bench.dispatch", 14, 21)]
    got = xtrace.attribute([(-5, 0), (15, 20), (30, 40)], spans)
    assert got == [("bench.other", 5e-9), ("bench.dispatch", 5e-9),
                   ("bench.poll", 1e-8)]


def test_names_and_leaf_ops():
    assert xtrace.short_name("%while.582 = (s32[], u32[64]) while(...)") \
        == "while.582"
    assert xtrace.DEVICE_PLANE.match("/device:TPU:0")
    assert not xtrace.DEVICE_PLANE.match("/device:TPU:0 SparseCore")
    ops = [("while.1", 0, 100), ("fusion.1", 0, 40), ("fusion.2", 50, 90),
           ("copy.3", 120, 130)]
    assert [o[0] for o in xtrace.leaves(ops)] == ["fusion.1", "fusion.2",
                                                  "copy.3"]


def test_reduce_over_window():
    devices = {"/device:TPU:0": [("fusion.1", 0, 10), ("fusion.2", 12, 20),
                                 ("fusion.1", 30, 50)]}
    spans = [("bench.window", 5, 40), ("bench.poll", 5, 40)]
    red = xtrace.reduce(devices, spans)
    assert red["window_s"] == pytest.approx(35e-9)
    assert red["busy_s"] == pytest.approx((5 + 8 + 10) * 1e-9)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(15e-9)]
    assert red["idle_gaps"][0] == ["bench.poll", pytest.approx(10e-9)]
    assert xtrace.reduce(devices, []) == {}


def test_recorded_cpu_trace(tmp_path):
    """A small profiler trace recorded here: the harness spans are found on
    the host plane, the XLA ops of the CPU client stand in for a device,
    and the reduction stays inside the window."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.poll"):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    y = f(x)
                y.block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    devices, spans = phases.load(xtrace.find_xplane(str(tmp_path)), {},
                                 device_plane="/host:CPU",
                                 op_line="tf_XLAPjRtCpuClient")
    names = [s[0] for s in spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.dispatch") == 3
    red = xtrace.reduce(devices, spans)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] >= 0.02
    assert {g[0] for g in red["idle_gaps"]} <= {
        "bench.poll", "bench.dispatch", "bench.other"}
    assert any(g[0] == "bench.other" for g in red["idle_gaps"])


CTX = {"busy_s": 6.0, "window_s": 8.0, "rounds": 12000, "icount": 1_000_000,
       "sim_init_s": [0.02, 0.04], "compile_s": 21.5, "mode": "restart"}


@pytest.mark.parametrize("name, want", [
    ("device_idle_share", 25.0), ("device_ms_per_round", 0.5),
    ("rounds_per_kinst", 12.0), ("sim_init_ms", 30.0), ("compile_s", 21.5)])
def test_metric_readers(name, want):
    bm = core.benchmark()
    assert name in {m["name"] for m in bm["per_layer"]}
    read = core.load_reader(name)
    assert read(CTX) == pytest.approx(want)
    # Nothing to read: the reader returns nothing, never a 0 share.
    empty = {"busy_s": None, "window_s": None, "rounds": 0, "icount": 0,
             "sim_init_s": [], "compile_s": 0.0, "mode": "sustained"}
    assert read(empty) is None
