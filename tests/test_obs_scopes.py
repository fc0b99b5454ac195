"""The simulator on the profiler's clock (graphite_tpu/obs): each engine
phase's ``jax.named_scope`` reaches the compiled quantum program, and the
driver's host spans reach the profiler trace while the tracer is enabled
(and nothing does while it is not)."""

import glob
import os
import re

import pytest

import jax

from graphite_tpu import obs
from graphite_tpu.config import load_config
from graphite_tpu.engine import quantum
from graphite_tpu.engine.sim import Simulator
from graphite_tpu.events import synth
from graphite_tpu.obs import scopes, spans
from graphite_tpu.params import SimParams

# The scopes every configuration's program carries.
ALWAYS = {scopes.BARRIER, scopes.WINDOW_WALK, scopes.COMPLEX_SLOT,
          scopes.RESOLVE_MEMORY, scopes.RESOLVE_SYNC}


def _params(tiles, **over):
    cfg = load_config()
    cfg.set("general/total_cores", tiles)
    for k, v in over.items():
        cfg.set(k, v)
    return SimParams.from_config(cfg)


def _radix8():
    return synth.gen_radix(8, keys_per_tile=32, radix=16)


@pytest.fixture
def metadata_in_cache_key():
    """JAX keys its persistent compile cache on the program stripped of
    debug info, scopes included: a program cached before a scope existed
    would come back without it."""
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    yield
    jax.config.update(key, before)


@pytest.mark.usefixtures("metadata_in_cache_key")
@pytest.mark.parametrize("tiles, over, make_trace, extra", [
    (8, {}, _radix8, set()),
    (8, {"tpu/fast_forward": 4, "tpu/fast_forward_span": 1000,
         "statistics_trace/enabled": "true",
         "statistics_trace/sampling_interval": 1000}, _radix8,
     {scopes.FAST_FORWARD, scopes.SAMPLE}),
    (4, {"general/max_threads_per_core": 4},
     lambda: synth.gen_threads_oversubscribed(num_streams=8),
     {scopes.SCHEDULE}),
    (8, {"network/memory": "emesh_hop_by_hop"}, _radix8,
     {scopes.NOC_FLIGHT}),
], ids=["radix8", "radix8_ff_sampled", "threads", "radix8_contended"])
def test_scopes_reach_compiled_program(tiles, over, make_trace, extra):
    """The compiled megarun's HLO metadata carries exactly the scopes of
    the phases this configuration runs: the contended mesh's flight loop
    only where the memory network is contended, nested in memory
    resolve."""
    params = _params(tiles, **over)
    sim = Simulator(params, make_trace())
    text = quantum._megarun_nodonate.lower(
        params, sim.state, sim.trace, 0).compile().as_text()
    found = {s for s in scopes.PHASES + (scopes.NOC_FLIGHT,)
             if f"/{s}/" in text}
    assert found == ALWAYS | extra
    if scopes.NOC_FLIGHT in extra:
        nested = f'/{scopes.RESOLVE_MEMORY}/[^"]*/{scopes.NOC_FLIGHT}/'
        assert re.search(nested, text)


def _host_spans(trace_dir, names):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name in names:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


WINDOW_SPANS = ("sim.compile+window", "sim.window")
NAMES = WINDOW_SPANS + ("sim.dispatch", "sim.sync", "trace.device_upload",
                        "trace.scan", "state.alloc")


def _traced_run(tmp_path, enabled):
    params = _params(8)
    Simulator(params, _radix8()).run(max_steps=1)     # compile outside
    obs.enable_tracing(enabled)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            summary = Simulator(params, _radix8()).run(poll_every=1)
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.enable_tracing(False)
    assert summary.done.all()
    return _host_spans(str(tmp_path), NAMES)


def test_spans_reach_profiler_trace(tmp_path):
    """Every polling window's span holds exactly one sim.dispatch followed
    by one sim.sync on the trace's host plane; the Simulator build's
    spans are there too."""
    got = _traced_run(tmp_path, enabled=True)
    names = [g[0] for g in got]
    for n in ("trace.device_upload", "trace.scan", "state.alloc"):
        assert names.count(n) == 1
    windows = [g for g in got if g[0] in WINDOW_SPANS]
    assert [w[0] for w in windows].count("sim.compile+window") == 1
    assert len(windows) >= 2
    for _, t0, t1 in windows:
        inside = sorted((s, n) for n, s, e in got
                        if n in ("sim.dispatch", "sim.sync")
                        and t0 <= s and e <= t1)
        assert [n for _, n in inside] == ["sim.dispatch", "sim.sync"]
    assert names.count("sim.dispatch") == names.count("sim.sync") \
        == len(windows)


def test_disabled_tracer_writes_no_span(tmp_path):
    """The disabled path stays the shared no-op: no span object, nothing
    in the profiler's trace."""
    obs.enable_tracing(False)
    assert obs.span("sim.window") is spans._NULL_SPAN
    assert obs.get_tracer().span("sim.sync", k=1) is spans._NULL_SPAN
    assert _traced_run(tmp_path, enabled=False) == []
