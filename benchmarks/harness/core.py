"""The benchmark harness: one cell of ``BENCHMARK.json``, from its data files.

A cell names a configuration and a traffic mix.  Nothing here names a
cell, a configuration, a generator, a reference or a metric: a later PR
adds each as new files, found by the name in the data, and edits none:

  configuration  ``benchmarks/configs/<name>.json`` (the file its
                 ``BENCHMARK.json`` entry names): ``sets``, the graphite
                 config keys the program runs with; ``model``, the
                 numbers the plain reference runs with; and optionally
                 ``"reference": "<ref>"``.
  reference      ``benchmarks/references/<ref>.py``, only where the
                 configuration names one: exposes ``ReferenceSim(model,
                 events)`` with ``run(stop_quanta=None) -> digest`` as
                 ``harness/reference.py``'s, which it may ``import
                 reference`` to subclass.  With no ``reference`` key the
                 configuration is compared against ``harness/
                 reference.py``.  A named module that is missing or has
                 no ``ReferenceSim`` is a ``HarnessError``, never a
                 fallback.
  traffic        ``benchmarks/traffic/<name>.json``: a ``generator``, its
                 ``params``, overrides per tile count (``scale``), and the
                 window mode.
  generator      ``benchmarks/generators/<generator>.py``, where the
                 traffic names one that ``traffic.py`` does not build in:
                 exposes ``generate(tiles, seed, params) -> {"ops",
                 "addr", "arg", "arg2"}`` (``traffic.generate``).
  metric         ``benchmarks/metrics/<name>.py`` per per-layer metric:
                 exposes ``read(ctx)``, returning a number or ``None``
                 when the run has nothing for it to read.

A traced run's ``ctx``: ``busy_s`` and ``window_s`` (device trace),
``rounds`` and ``icount`` (the window's ``round_ctr`` and instructions),
``counters`` (every per-tile counter summed over tiles and over the
window's simulations, the cut one included), ``round_ctrs`` (the same
sums of ``ROUND_FIELDS``), ``phase_s`` (device seconds per engine phase,
``phases.reduce``; empty with no device plane), ``span_s`` (the
durations of the program's spans that end in the window), ``sim_init_s``,
``compile_s`` and ``mode``.

A run: set-up (imports, trace from ``--seed``, program build, compile or
cache load, warm-up), then the measured window, then the memory reading,
then the plain reference over what the window produced, compared exactly.

Window modes:
  restart    fresh ``Simulator`` on the same trace, run to completion,
             again, until the window ends; the last one is cut at the
             first polling boundary after the end (``run(budget_s=)``).
  sustained  one ``Simulator`` built in set-up, advanced from its start
             with ``poll_every=1`` until the first polling boundary after
             the end.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import time

import numpy as np

import plugins
import traffic

HARNESS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HARNESS)
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")        # traces; listed in .gitignore
TRACE_DIR = os.path.join(OUT, "trace")
REFERENCE_DIR = os.path.join(BENCH, "references")
METRIC_DIR = os.path.join(BENCH, "metrics")
TRACE_SECONDS = 3           # the traced window, at most
MB = 1e6

# The tpu/* keys whose values change simulated results at miss_chain = 0
# (the engine's caps and window width are part of its schedule): the
# reference takes them from the configuration's ``sets``, so the two
# sides run the same numbers from one place.
ENGINE_KEYS = {
    "block_events": "tpu/block_events",
    "max_events_per_quantum": "tpu/max_events_per_quantum",
    "rounds_per_quantum": "tpu/rounds_per_quantum",
    "directory_conflict_rounds": "tpu/directory_conflict_rounds",
    "max_inv_fanout_per_round": "tpu/max_inv_fanout_per_round",
}


class HarnessError(RuntimeError):
    """The run cannot be measured (no chip, wrong checkout, compile in the
    window); it prints no result."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def benchmark():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name, bm=None):
    """Everything one workload needs, found by name."""
    bm = bm or benchmark()
    wl = {w["name"]: w for w in bm["workloads"]}[name]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[wl["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     wl["traffic"] + ".json"))
    return {"workload": wl, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bm["end_to_end"]
                           if name in m.get("workloads", [name])],
            "per_layer": [m for m in bm["per_layer"]
                          if name in m.get("workloads", [name])]}


def tiles_of(config):
    return int(config["sets"]["general/total_cores"])


def reference_model(config):
    model = json.loads(json.dumps(config["model"]))
    model["engine"] = dict(model.get("engine", {}))
    for k, key in ENGINE_KEYS.items():
        model["engine"][k] = int(config["sets"][key])
    return model


def window_mode(c):
    return traffic.resolve(c["traffic"], tiles_of(c["config"]))["window"]


def make_events(c, seed):
    return traffic.generate(c["traffic"], tiles_of(c["config"]), seed)


# ------------------------------------------------------------ program side

def import_program():
    """The system under test, from this checkout only."""
    try:
        import graphite_tpu
    except ImportError as e:
        raise HarnessError(f"graphite_tpu is not importable: {e}")
    here = os.path.dirname(os.path.abspath(graphite_tpu.__file__))
    if here != os.path.join(ROOT, "graphite_tpu"):
        raise HarnessError(f"graphite_tpu imported from {here}, not from "
                           f"this checkout ({ROOT})")
    return graphite_tpu


def program_params(config):
    from graphite_tpu.config import load_config
    from graphite_tpu.params import SimParams
    cfg = load_config()
    for k, v in config["sets"].items():
        cfg.set(k, v)
    return SimParams.from_config(cfg)


def program_trace(events):
    from graphite_tpu.events.schema import Trace
    return Trace(ops=events["ops"], addr=events["addr"], arg=events["arg"],
                 arg2=events["arg2"])


ROUND_FIELDS = ("ctr_quantum", "ctr_window", "ctr_complex", "ctr_conflict",
                "ctr_resolve", "round_ctr")


def program_digest(sim, summary):
    """Host copy of what a simulation produced."""
    import jax
    st = jax.device_get({f: getattr(sim.state, f)
                         for f in ("clock", "cursor", "done") + ROUND_FIELDS})
    return {"ctr_quantum": int(st["ctr_quantum"]),
            "completion_time_ps": int(np.asarray(st["clock"]).max()),
            "clock": np.asarray(st["clock"]),
            "cursor": np.asarray(st["cursor"]).astype(np.int64),
            "done": np.asarray(st["done"]),
            "counters": {k: np.asarray(v) for k, v in
                         summary.counters.items()},
            "round_ctrs": {f: int(st[f]) for f in ROUND_FIELDS},
            "all_done": bool(np.asarray(st["done"]).all())}


class CompileClock:
    """Counts jax's compile events (``/jax/core/compile/*``: tracing,
    lowering, backend compile) and sums their seconds."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event.startswith("/jax/core/compile/"):
            self.count += 1
            self.seconds += duration

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_restart(params, trace, seconds):
    """Fresh simulations of one trace until the window ends."""
    from graphite_tpu.engine.sim import Simulator
    sims, init_s = [], []
    t_start = time.perf_counter()
    deadline = t_start + seconds
    while not sims or time.perf_counter() < deadline:
        t0 = time.perf_counter()
        with _span("bench.sim_init"):
            sim = Simulator(params, trace)
        init_s.append(time.perf_counter() - t0)
        try:
            with _span("bench.poll"):
                summary = sim.run(
                    budget_s=max(deadline - time.perf_counter(), 0.0))
            rec = program_digest(sim, summary)
        except Exception as e:          # DeadlockError, device OOM
            rec = {"error": f"{type(e).__name__}: {e}"}
        sims.append(rec)
        del sim
        if "error" in rec or not rec["all_done"]:
            break
    return sims, time.perf_counter() - t_start, init_s


def run_sustained(sim, seconds):
    """One simulation advanced from its start until the window ends."""
    t_start = time.perf_counter()
    try:
        with _span("bench.poll"):
            summary = sim.run(poll_every=1, budget_s=seconds)
        rec = program_digest(sim, summary)
    except Exception as e:
        rec = {"error": f"{type(e).__name__}: {e}"}
    return [rec], time.perf_counter() - t_start, []


def setup_program(c, events, mode):
    """Build and warm the program for this cell: returns (params, trace,
    the sustained simulation or None)."""
    import jax
    from graphite_tpu.engine.quantum import megarun
    from graphite_tpu.engine.sim import Simulator
    params = program_params(c["config"])
    trace = program_trace(events)
    if mode == "restart":
        warm = Simulator(params, trace)
        warm.run(max_steps=1)
        del warm
        return params, trace, None
    if mode != "sustained":
        raise HarnessError(f"unknown window mode {mode!r}")
    sim = Simulator(params, trace)
    # max_quanta is a traced operand: 0 compiles the window's program and
    # runs no quantum, so the timed simulation starts untouched.
    jax.block_until_ready(megarun(params, sim.state, sim.trace, 0))
    jax.device_get((sim.state.all_done(), sim.state.cursor.sum(),
                    sim.state.clock.sum(), sim.state.ctr_quantum))
    return params, trace, sim


def run_window(mode, params, trace, sim, seconds):
    if mode == "restart":
        return run_restart(params, trace, seconds)
    return run_sustained(sim, seconds)


# ----------------------------------------------------------- comparison

def compare(prog, ref):
    """Exact comparison of one simulation's results: per-tile clocks,
    cursors, done flags, every per-tile counter, the quanta and the
    completion time.  Returns the numbers that are held to their
    limits: how many of those values differ, and the widest gap between
    a tile's clock and the reference's."""
    differing = 0
    for f in ("clock", "cursor", "done"):
        differing += int((np.asarray(prog[f]) != np.asarray(ref[f])).sum())
    zeros = np.zeros_like(prog["clock"])
    for k, v in prog["counters"].items():
        differing += int((np.asarray(v) != ref["counters"].get(k, zeros))
                         .sum())
    differing += int(prog["ctr_quantum"] != ref["ctr_quantum"])
    differing += int(prog["completion_time_ps"] != ref["completion_time_ps"])
    gap = int(np.abs(np.asarray(prog["clock"], np.int64)
                     - np.asarray(ref["clock"], np.int64)).max())
    return {"values_differing": differing, "max_clock_gap_ps": gap}


LIMITS = {"values_differing": 0, "max_clock_gap_ps": 0}


def reference_class(config):
    """The plain reference a configuration is compared against: the
    ``ReferenceSim`` of ``REFERENCE_DIR/<name>.py`` where its
    ``reference`` names one, else ``harness/reference.py``'s."""
    name = config.get("reference")
    if name is None:
        import reference
        return reference.ReferenceSim
    mod = plugins.load(REFERENCE_DIR, name, "reference", HarnessError)
    if not hasattr(mod, "ReferenceSim"):
        raise HarnessError(f"{plugins.path_of(REFERENCE_DIR, name)} has no "
                           f"ReferenceSim")
    return mod.ReferenceSim


def check(sims, model, events, ref_cls):
    """Run the plain reference ``ref_cls`` over the window's simulations:
    complete ones against its completed run, a cut one against its state
    at the quantum the cut one stopped at.  Returns (failed, compared
    numbers)."""
    worst = {k: 0 for k in LIMITS}
    failed, n = 0, 0
    ok_sims = [s for s in sims if "error" not in s]
    failed += len(sims) - len(ok_sims)
    if ok_sims:
        ref = ref_cls(model, events)
        cuts = sorted({s["ctr_quantum"] for s in ok_sims
                       if not s["all_done"]})
        snaps = {q: ref.run(stop_quanta=q) for q in cuts}
        if any(s["all_done"] for s in ok_sims):
            snaps["done"] = ref.run()
        for s in ok_sims:
            r = snaps["done"] if s["all_done"] else snaps[s["ctr_quantum"]]
            got = compare(s, r)
            n += 1
            if any(got[k] > LIMITS[k] for k in LIMITS):
                failed += 1
            for k in LIMITS:
                worst[k] = max(worst[k], got[k])
    worst["simulations_compared"] = n
    return failed, worst


# ------------------------------------------------------------ metrics

def load_reader(name):
    return plugins.load(METRIC_DIR, name, "metric", HarnessError).read


def window_totals(sims):
    """Every per-tile counter and every round counter, summed over tiles
    and over the window's simulations (the cut one included)."""
    ok = [s for s in sims if "error" not in s]
    counters, rounds = {}, dict.fromkeys(ROUND_FIELDS, 0)
    for s in ok:
        for k, v in s["counters"].items():
            counters[k] = counters.get(k, 0) + int(np.asarray(v).sum())
        for f in ROUND_FIELDS:
            rounds[f] += int(s["round_ctrs"][f])
    return counters, rounds


def device_line():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _instrument_dispatch():
    """Trace runs only: a ``bench.dispatch`` span around each device
    dispatch of ``Simulator.run``, so idle gaps split into dispatch and
    polling.  Returns the undo."""
    from graphite_tpu.engine import sim as simmod
    orig = simmod.megarun

    def megarun(*a, **k):
        with _span("bench.dispatch"):
            return orig(*a, **k)

    simmod.megarun = megarun

    def undo():
        simmod.megarun = orig
    return undo


def program_op_names(params, trace, sim):
    """Traced runs: instruction name -> ``op_name`` of the executable the
    window runs, from its HLO text (``phases.op_names_from_hlo``).  The
    compile finds the window's program in jax's caches; it counts as
    set-up, not as ``compile_s``."""
    import phases
    from graphite_tpu.engine import quantum
    from graphite_tpu.engine.sim import Simulator
    s = sim if sim is not None else Simulator(params, trace)
    fn = quantum._megarun_donate \
        if params.tile_shards <= 1 and quantum.state_donation_enabled() \
        else quantum._megarun_nodonate
    text = fn.lower(params, s.state, s.trace, 0).compile().as_text()
    return phases.op_names_from_hlo(text)


def control_cell(c, miss_chain=8):
    """The cell with the program's own lower-fidelity path switched on
    (``tpu/miss_chain`` > 0 banks misses into chains, which shifts
    simulated statistics by up to about 2%): the control that the exact
    comparison has to refuse.  The reference keeps the configuration's
    model."""
    c = dict(c)
    c["program_sets"] = {"tpu/miss_chain": miss_chain}
    return c


def measure_program(c, seed, seconds, traced, t_main, check_chip=True):
    """Set-up and the measured window of one run.  Returns (the result
    line without its comparison, the window's simulations, the events)."""
    import_program()
    import jax
    clock = CompileClock()
    if check_chip:
        dev = device_line()
        need = c["workload"]["chips"]
        if dev["platform"] != "tpu":
            raise HarnessError(f"needs a TPU; jax found {dev['platform']}")
        if dev["count"] < need:
            raise HarnessError(f"cell needs {need} chips; jax sees "
                               f"{dev['count']}")
    mode = window_mode(c)
    events = make_events(c, seed)
    config = c["config"]
    if c.get("program_sets"):
        config = dict(config, sets={**config["sets"], **c["program_sets"]})
    params, trace, sim = setup_program(dict(c, config=config), events, mode)
    compile_setup = clock.seconds
    window, undo = seconds, None
    if traced:
        from graphite_tpu import obs
        window = min(seconds, TRACE_SECONDS)
        t_hlo = time.perf_counter()
        op_names = program_op_names(params, trace, sim)
        hlo_s = time.perf_counter() - t_hlo
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        undo = _instrument_dispatch()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        obs.enable_tracing()
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    n_compiles = clock.count
    setup_s = time.perf_counter() - t_main
    try:
        with _span("bench.window"):
            sims, window_s, init_s = run_window(mode, params, trace, sim,
                                                window)
    finally:
        if traced:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            trace_stop_s = time.perf_counter() - t_stop
            obs.enable_tracing(False)
            undo()
    compiled_in_window = clock.count - n_compiles
    clock.close()
    if compiled_in_window:
        raise HarnessError(f"{compiled_in_window} compile events inside the "
                           f"measured window")
    peak = memory_peak()
    del sim
    counters, round_ctrs = window_totals(sims)
    icount, rounds = counters.get("icount", 0), round_ctrs["round_ctr"]
    dev = device_line()
    dev["memory_peak_bytes"] = peak
    out = {"correct": None, "attempted": len(sims), "failed": 0,
           "metrics": {}, "device": dev}
    if traced:
        import phases
        import xtrace
        t_red = time.perf_counter()
        xp = xtrace.find_xplane(TRACE_DIR)
        trace_mb = os.path.getsize(xp) / MB
        devices, spans = phases.load(xp, op_names)
        devices_seen = {k: len(v) for k, v in devices.items()}
        red = xtrace.reduce(devices, [sp for sp in spans if sp[0].startswith(
            xtrace.SPAN_PREFIX)])
        split = phases.reduce(devices, spans)
        del devices
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = {"busy_s": red.get("busy_s"), "window_s": red.get("window_s"),
               "rounds": rounds, "icount": icount, "sim_init_s": init_s,
               "compile_s": compile_setup, "mode": mode,
               "counters": counters, "round_ctrs": round_ctrs,
               "phase_s": split.get("phases", {}),
               "span_s": split.get("spans", {})}
        for m in c["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if red:
            dev["busy_s"] = red["busy_s"]
            dev["window_s"] = red["window_s"]
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
        trace_read_s = time.perf_counter() - t_red
        op_phase = split.get("op_phase", {})
        trace_info = {"stop_s": trace_stop_s, "read_s": trace_read_s,
                      "hlo_s": hlo_s, "xplane_mb": trace_mb,
                      "device_planes": devices_seen,
                      "top_op_phase": {n: op_phase.get(n) for n, _ in
                                       red.get("device_ops", [])}}
    else:
        trace_info = None
        values = {"sim_mips": icount / window_s / 1e6,
                  "hbm_peak_gb": peak / 1e9 if peak is not None else None,
                  "setup_s": setup_s}
        for m in c["end_to_end"]:
            v = values.get(m["name"])
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    out["window"] = {"mode": mode, "window_s": window_s, "icount": icount,
                     "rounds": rounds, "sims": len(sims),
                     "cut_at_quantum": [s.get("ctr_quantum") for s in sims
                                        if not s.get("all_done", True)],
                     "sim_init_ms_mean": statistics.mean(init_s) * 1e3
                     if init_s else None,
                     "compile_setup_s": compile_setup,
                     "trace": trace_info,
                     "errors": [s["error"] for s in sims if "error" in s]}
    return out, sims, events


def finish(out, sims, events, c):
    """The plain reference over the window's simulations; fills correct,
    failed and the compared numbers (the line's last key)."""
    ref_cls = reference_class(c["config"])
    t_ref = time.perf_counter()
    failed, compared = check(sims, reference_model(c["config"]), events,
                             ref_cls)
    out["window"]["reference_s"] = time.perf_counter() - t_ref
    out["failed"] = failed
    out["correct"] = failed == 0 and compared["simulations_compared"] > 0
    out["compared"] = {k: {"value": v, "limit": LIMITS.get(k)}
                       for k, v in compared.items()}
    return out


def measure(c, seed, seconds, traced, t_main, check_chip=True):
    """One run of a cell; returns the result line's dict."""
    reference_class(c["config"])          # a missing one fails before set-up
    out, sims, events = measure_program(c, seed, seconds, traced, t_main,
                                        check_chip)
    return finish(out, sims, events, c)


def main(args, t_main):
    try:
        c = cell(args.workload)
        out = measure(c, args.seed, args.seconds, bool(args.trace), t_main)
    except HarnessError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    for k, v in out["compared"].items():
        lim = "" if v["limit"] is None else f" (limit {v['limit']})"
        print(f"compared {k}: {v['value']}{lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
