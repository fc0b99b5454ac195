"""The phase split of a profiler trace (benchmarks/harness/phases.py):
each device op's phase from its op_name, the sums over the window, and
the program's host spans, on hand-made ops and on a trace recorded on the
CPU."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "harness"))

import phases  # noqa: E402
import xtrace  # noqa: E402

WALK = "jit(_megarun_impl)/while/body/engine.window_walk/gather"
RESOLVE_IN_COND = ("jit(_megarun_impl)/while/body/cond/branch_1_fun/"
                   "engine.resolve_memory/while/body/engine.window_walk/add")


def test_phase_of_innermost_scope():
    assert phases.phase_of(WALK) == "engine.window_walk"
    # innermost wins when scopes nest
    assert phases.phase_of(RESOLVE_IN_COND) == "engine.window_walk"
    assert phases.phase_of("jit(_megarun_impl)/while/body/add") \
        == "unscoped"
    assert phases.phase_of(None) == "unscoped"
    assert phases.phase_of("") == "unscoped"
    # a renamed scope keeps its own name: it never lands in unscoped
    assert phases.phase_of("jit(f)/engine.walk2/mul") == "engine.walk2"


def test_program_scope_names_match():
    """The harness keeps its own copy of the program's scope names."""
    from graphite_tpu.obs import scopes
    assert phases.PHASES == scopes.PHASES


HLO = """HloModule jit_f

%fused.7 (p0: u32[8], p1: u32[8]) -> (u32[8], u32[8]) {
  %p0 = u32[8]{0} parameter(0)
  %or.1 = u32[8]{0} or(%p0, %p0), metadata={op_name="jit(f)/while/body/engine.window_walk/or"}
  ROOT %scatter.2 = (u32[8]{0}, u32[8]{0}) scatter(%p0, %or.1)
}

%relayout.body (param.1: (u32[], u32[8])) -> (u32[], u32[8]) {
  %param.1 = (u32[], u32[8]) parameter(0)
  %dynamic-update-slice.18 = u32[8]{0} dynamic-update-slice(%param.1)
  ROOT %tuple.1 = (u32[], u32[8]) tuple(%dynamic-update-slice.18)
}

%round.body (param.2: (u32[], u32[8])) -> (u32[], u32[8]) {
  %param.2 = (u32[], u32[8]) parameter(0)
  %while.618 = (u32[], u32[8]) while(%param.2), condition=%relayout.cond, body=%relayout.body
  %gather.3 = u32[8]{0} gather(%while.618), metadata={op_name="jit(f)/while/body/engine.resolve_memory/while/body/gather" stack_frame_id=6}
  ROOT %tuple.2 = (u32[], u32[8]) tuple(%gather.3)
}

%quantum.body (param.3: (u32[], u32[8])) -> (u32[], u32[8]) {
  %param.3 = (u32[], u32[8]) parameter(0)
  %fusion.10305 = (u32[8]{0}, u32[8]{0}) fusion(%param.3), kind=kCustom, calls=%fused.7
  %while.9 = (u32[], u32[8]) while(%param.3), condition=%round.cond, body=%round.body, metadata={op_name="jit(f)/while/body/engine.resolve_memory/while"}
  %copy.1169 = u32[8]{0} copy(%param.3)
  ROOT %tuple.3 = (u32[], u32[8]) tuple(%copy.1169)
}

ENTRY %main (x: u32[8]) -> u32[8] {
  %x = u32[8]{0} parameter(0)
  %while.1 = (u32[], u32[8]) while(%x), condition=%q.cond, body=%quantum.body, metadata={op_name="jit(f)/while"}
  ROOT %copy.7 = u32[8]{0} copy(%x)
}
"""


def test_op_names_from_hlo():
    """An op's own metadata first; an op the compiler made without any
    takes the last op_name fused into it, else that of the op whose
    computation it lives in; a loop carry of an unscoped loop stays
    unscoped, and an op of the entry computation with none has none."""
    got = phases.op_names_from_hlo(HLO)
    walk = "jit(f)/while/body/engine.window_walk/or"
    resolve_loop = "jit(f)/while/body/engine.resolve_memory/while"
    assert got["or.1"] == walk
    assert got["fusion.10305"] == walk
    assert got["gather.3"].endswith("engine.resolve_memory/while/body/gather")
    assert got["while.618"] == resolve_loop
    assert got["dynamic-update-slice.18"] == resolve_loop
    assert got["copy.1169"] == "jit(f)/while"
    assert "copy.7" not in got
    assert {n: phases.phase_of(got.get(n)) for n in (
        "fusion.10305", "dynamic-update-slice.18", "copy.1169", "copy.7")} \
        == {"fusion.10305": "engine.window_walk",
            "dynamic-update-slice.18": "engine.resolve_memory",
            "copy.1169": "unscoped", "copy.7": "unscoped"}


def test_self_time_covers_the_union_once():
    """A container's own time is what its nested ops leave uncovered; the
    pieces are disjoint and add up to the busy time."""
    ops = [("while.1", 0, 100, "w"), ("fusion.1", 0, 40, "a"),
           ("cond.2", 50, 90, "c"), ("fusion.2", 55, 60, "b"),
           ("fusion.3", 60, 70, "b"), ("copy.3", 120, 130, "d"),
           ("copy.4", 120, 130, "e")]
    own = {}
    for op, s, e in phases.self_time(ops):
        own[op[0]] = own.get(op[0], 0) + e - s
    # of two ops on one interval, the later one is taken as nested
    assert own == {"while.1": 20, "fusion.1": 40, "cond.2": 25,
                   "fusion.2": 5, "fusion.3": 10, "copy.4": 10}
    assert sum(own.values()) == xtrace.busy_ns([o[1:3] for o in ops])


def test_reduce_sums_own_time_in_window():
    """Each op's own time, clipped to the window, under its innermost
    scope; unknown metadata is unscoped; two device planes average; the
    phases add up to the busy time xtrace.reduce reads."""
    loop = "jit(f)/while"                       # an unscoped loop
    ops0 = [("while.1", 0, 100, loop),
            ("fusion.1", 0, 40, WALK),          # clipped to 10..40
            ("fusion.2", 50, 70, RESOLVE_IN_COND),
            ("copy.3", 70, 90, None),
            ("fusion.9", 200, 300, WALK)]       # outside the window
    ops1 = [("fusion.1", 10, 30, WALK)]
    spans = [("bench.window", 10, 150), ("sim.window", 10, 95),
             ("sim.dispatch", 10, 12), ("sim.sync", 12, 95),
             ("sim.sync", 120, 200),            # ends after the window
             ("bench.poll", 10, 150)]
    devices = {"/device:TPU:0": ops0, "/device:TPU:1": ops1}
    red = phases.reduce(devices, spans)
    ph = red["phases"]
    assert set(ph) == set(phases.PHASES) | {"unscoped"}
    assert ph["engine.window_walk"] == pytest.approx((30 + 20 + 20) / 2e9)
    # copy.3 and the loop's own 40..50 and 90..100
    assert ph["unscoped"] == pytest.approx((20 + 20) / 2e9)
    assert ph["engine.resolve_memory"] == 0.0
    assert ph["engine.barrier"] == 0.0
    assert red["op_phase"] == {"while.1": "unscoped",
                               "fusion.1": "engine.window_walk",
                               "fusion.2": "engine.window_walk",
                               "copy.3": "unscoped"}
    assert red["spans"] == {"sim.window": [pytest.approx(85e-9)],
                            "sim.dispatch": [pytest.approx(2e-9)],
                            "sim.sync": [pytest.approx(83e-9)]}
    busy = xtrace.reduce({k: [o[:3] for o in v]
                          for k, v in devices.items()}, spans)["busy_s"]
    assert sum(ph.values()) == pytest.approx(busy)
    # no window, or no device: nothing to read
    assert phases.reduce(devices, spans[1:]) == {}
    assert phases.reduce({}, spans) == {}


def test_one_load_serves_both_reductions():
    """xtrace.reduce reads phases.load's ops, which carry their op_name
    as a fourth field, as it reads its own (name, start, end) ones."""
    ops = [("while.1", 0, 100, "jit(f)/while"), ("fusion.1", 0, 40, WALK),
           ("fusion.2", 50, 70, RESOLVE_IN_COND), ("copy.3", 120, 130, None)]
    spans = [("bench.window", 10, 125), ("bench.poll", 10, 125)]
    four = xtrace.reduce({"/device:TPU:0": ops}, spans)
    three = xtrace.reduce({"/device:TPU:0": [o[:3] for o in ops]}, spans)
    assert four == three and four["busy_s"] > 0


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded here: ops of a scoped jitted function take their
    op_name from the compiled HLO's metadata, and the program's spans
    (its tracer on) are read from the host plane."""
    import jax
    import jax.numpy as jnp
    from graphite_tpu import obs

    @jax.jit
    def f(x):
        with jax.named_scope("engine.barrier"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("engine.sample"):
            z = jnp.cos(y) @ y.T
        return z.sum()

    x = jnp.ones((256, 256))
    text = f.lower(x).compile().as_text()
    f(x).block_until_ready()
    obs.enable_tracing()
    try:
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with obs.span("sim.window"):
                    with obs.span("sim.dispatch"):
                        y = f(x)
                    with obs.span("sim.sync"):
                        y.block_until_ready()
        jax.profiler.stop_trace()
    finally:
        obs.enable_tracing(False)
    devices, spans = phases.load(
        xtrace.find_xplane(str(tmp_path)), phases.op_names_from_hlo(text),
        device_plane="/host:CPU", op_line="tf_XLAPjRtCpuClient")
    # (the CPU client nests its thread-pool events inside each op, so
    # the sums are checked on hand-made ops above)
    ops = [op for plane in devices.values() for op in plane]
    assert {"engine.barrier", "engine.sample"} <= {
        phases.phase_of(op_name) for *_, op_name in ops}
    red = phases.reduce(devices, spans)
    assert {n: len(v) for n, v in red["spans"].items()} == {
        "sim.window": 3, "sim.dispatch": 3, "sim.sync": 3}
