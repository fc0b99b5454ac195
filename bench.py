"""Round benchmark: simulated MIPS on the BASELINE.md configs.

Headline: config 1 — SPLASH-2 radix, 64 tiles, carbon_sim.cfg defaults
(simple in-order cores, private L1/L2 + full-map MSI directory, emesh
NoC, lax_barrier @ 1000 ns) — on whatever accelerator jax selects.
Prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Honesty rules (VERDICT r2 "what's weak" #2-3):
  * MIPS is reported ONLY for runs that COMPLETE (``all_done``); bounded
    runs are labeled ``"kind": "throughput_probe"`` and report events/s,
    engine rounds, and ms/round instead of a MIPS figure.
  * The host-Graphite baseline cannot be measured in this image (its
    build needs Boost + Pin 2.13 — BASELINE.md "Measurement attempt"),
    so ``vs_baseline_bracket`` rates the headline against 5 / 20 / 50
    simulated MIPS: HPCA-2010-era Graphite reports single-digit-to-
    low-tens aggregate MIPS for this workload class; the top-level
    ``vs_baseline`` keeps the 20-MIPS midpoint for round-over-round
    comparability.
  * Every row carries events/s and host-seconds-per-simulated-megacycle;
    completed rows also carry total engine rounds and ms/round (the
    engine's unit of device work — see engine/core.py round_ctr).

Compile time of the fused step is excluded (one throwaway warm-up run),
matching how the reference's numbers exclude Pin instrumentation warm-up.

Un-killable protocol (VERDICT r5 #1 — two rounds of rc=124 voided every
number): the headline JSON line prints IMMEDIATELY after the radix64 row
completes, and the updated full line re-prints after every later row, so
whatever kills the process, the driver's tail holds the last complete
line.  An internal wall-clock budget (``GRAPHITE_BENCH_BUDGET_S``,
default 1200 s) is checked before each non-headline row; rows past the
budget emit ``"kind": "skipped_budget"`` instead of dying at the driver
timeout.

Telemetry: every row writes a RunReport + Chrome-trace artifact pair
under $GRAPHITE_BENCH_TELEMETRY_DIR (default ./bench_telemetry) AS IT
COMPLETES, so a timed-out bench (the r5 rc=124) still leaves per-row
profiles explaining where the time went.  Set the env var to an empty
string to disable.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Peak HBM bandwidth (GB/s) per chip, keyed by jax ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (16 GB HBM at 819 GB/s).
# A device missing here is an error, never a default.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}

BASELINE_BRACKET_MIPS = (5.0, 20.0, 50.0)
BASELINE_MIPS = 20.0
NUM_TILES = 64
KEYS_PER_TILE = 2048
# Internal wall-clock budget: rows that would start past it are skipped
# (never the radix64 headline — that row IS the benchmark).
DEFAULT_BUDGET_S = 1200.0

TELEMETRY_DIR = os.environ.get("GRAPHITE_BENCH_TELEMETRY_DIR",
                               "bench_telemetry")


def hbm_peak_gbps(device_kind: str) -> float:
    try:
        return HBM_PEAK_GBPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no HBM peak on record for device kind {device_kind!r}: add "
            f"it, with its source, to bench.HBM_PEAK_GBPS") from None


def _synth_cached(name, fn, **kwargs):
    """Disk-cached synthetic trace (events/trace_cache): generation is
    deterministic in (generator kwargs, generator SOURCE — the key
    hashes the generator module's content so an edited generator never
    serves its pre-edit trace), so warm bench runs skip straight to the
    engine (the r05 rc=124 fix, half 1)."""
    import inspect

    from graphite_tpu.events import trace_cache
    return trace_cache.cached(
        (name, sorted(kwargs.items())), lambda: fn(**kwargs),
        src_files=[inspect.getsourcefile(fn)])


class _RowSpans:
    """Host spans scoped to one bench row (slice of the global tracer)."""

    def __init__(self, tracer):
        self._tracer = tracer
        self._mark = tracer.mark()

    @property
    def events(self):
        return self._tracer.since(self._mark)


def _emit_row_telemetry(label: str, summary, row_spans):
    """Write the row's RunReport/trace pair; returns the report path, or
    None when disabled or the write failed (the bench row must not point
    at a file that does not exist)."""
    if not TELEMETRY_DIR:
        return None
    try:
        paths = summary.write_telemetry(TELEMETRY_DIR, tracer=row_spans,
                                        workload=label, prefix=label)
        # Cumulative host-span track (capture/build/annotate phases live
        # outside any one row); rewritten after every row so a timed-out
        # bench still shows where the wall clock went.
        from graphite_tpu import obs
        from graphite_tpu.obs.export import chrome_trace
        path = os.path.join(TELEMETRY_DIR, "bench_host_trace.json")
        with open(path, "w") as f:
            json.dump(chrome_trace(tracer=obs.get_tracer()), f)
        return paths["report"]
    except Exception as e:     # telemetry must never sink a bench row
        print(f"telemetry write failed for {label}: {e}", file=sys.stderr)
        return None


def _run(trace_fn, num_tiles: int, max_steps=None, label=None, **overrides):
    import jax

    from graphite_tpu import obs
    from graphite_tpu.config import load_config
    from graphite_tpu.engine.sim import Simulator
    from graphite_tpu.params import SimParams

    label = label or f"run{num_tiles}"
    row_spans = _RowSpans(obs.get_tracer())
    cfg = load_config()
    cfg.set("general/total_cores", num_tiles)
    # NOTE: device round metrics ([telemetry]) stay OFF here — the bench
    # must time exactly the program the BASELINE numbers were measured
    # on (honesty rules above); the RunReport still carries counters,
    # VM, completion time, and the host spans.  Profile a row's engine
    # health with `graphite-tpu run --telemetry-dir` instead.
    for k, v in overrides.items():
        cfg.set(k, v)
    params = SimParams.from_config(cfg)
    trace = trace_fn(num_tiles)

    with obs.span(f"{label}.warmup"):
        warm = Simulator(params, trace)
        warm.run(max_steps=2)

    sim = Simulator(params, trace)
    t0 = time.perf_counter()
    with obs.span(f"{label}.timed_run"):
        summary = sim.run(max_steps=max_steps)
    host_s = time.perf_counter() - t0
    d = summary.to_dict()
    events = int(sum(int(v.sum()) for k, v in summary.counters.items()
                     if k in ("l1d_read", "l1d_write", "branches"))) \
        + summary.total_instructions
    rounds = int(jax.device_get(sim.state.round_ctr))
    completed = bool(d["all_done"])
    # Device-utilization proxy (VERDICT r4 weak #5: "nothing reports
    # utilization"): every engine round streams most of the simulation
    # state through HBM, so state_bytes x rounds/s over the chip's HBM
    # peak bounds achievable efficiency from above — and makes the
    # fixed-overhead problem visible (the engine is dispatch-bound, not
    # bandwidth-bound).
    state_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(sim.state))
    # The HBM share exists only on a TPU (an unknown TPU kind is an
    # error); off the TPU the row keeps its other metrics and says null.
    dev = jax.devices()[0]
    hbm_util = None
    if dev.platform == "tpu":
        hbm_util = round((state_bytes * rounds / max(host_s, 1e-9))
                         / (hbm_peak_gbps(dev.device_kind) * 1e9), 5)
    row = {
        "kind": "completed" if completed else "throughput_probe",
        "num_tiles": num_tiles,
        "total_instructions": summary.total_instructions,
        "host_seconds": round(host_s, 3),
        # MIPS only when the workload ran to completion — a bounded run
        # mixes warm-up and unfinished work into the rate.
        "mips": round(summary.total_instructions / host_s / 1e6, 3)
        if completed else None,
        "events_per_sec": round(events / host_s),
        "engine_rounds": rounds,
        # Events retired per engine round — the round-COUNT lever's
        # metric (tpu/miss_chain serves whole chains per resolve pass;
        # the radix64_chain12 A/B row evidences the ratio even on a
        # CPU-only container where per-round dispatch cost is invisible).
        "events_per_round": round(events / max(rounds, 1), 3),
        "ms_per_round": round(host_s / max(rounds, 1) * 1e3, 3),
        "state_bytes": state_bytes,
        "hbm_bytes_per_sec": round(state_bytes * rounds / max(host_s, 1e-9)),
        "hbm_utilization_vs_peak": hbm_util,
        "completion_time_ns": d["completion_time_ns"],
        "device_steps": sim.steps,
        "all_done": completed,
        # host seconds per simulated megacycle (2 GHz core clock:
        # cycles = ns * 2, megacycles = ns * 2 / 1e6)
        "host_s_per_Mcycle": round(
            host_s / max(d["completion_time_ns"] * 2.0 / 1e6, 1e-9), 3),
    }
    if params.miss_chain > 0:
        # Round-9 fan-out occupancy: chain heads served in-pass by the
        # batched invalidation leg vs demoted to the round-loop fallback
        # (PROFILE.md round-9 — the fallback share is the residual).
        row["chain_fanout_served"] = int(
            summary.counters["chain_fanout_served"].sum())
        row["chain_fallback"] = int(
            summary.counters["chain_fallback"].sum())
    if sim.ingest is not None:
        # Round-16 streaming ingest: flatten the summary's ingest
        # section (seams, prefetch/rebuild split, stall seconds +
        # fraction, peak device trace bytes) into the row so
        # results_db's ingest_stall_fraction / peak_device_trace_bytes
        # chains see bench rows and RunReports alike.
        row.update(summary.ingest_section())
    if params.fast_forward > 0:
        # Round-12 adaptive-fidelity attribution: engaged analytic
        # rounds, events priced in closed form, and the headline
        # ff-quanta fraction (quanta that fast-forwarded at least one
        # span / all quanta) the results DB trends across runs.
        quanta = int(jax.device_get(sim.state.ctr_quantum))
        ffq = int(jax.device_get(sim.state.ctr_ffq))
        row["ff_rounds"] = int(jax.device_get(sim.state.ctr_ff))
        row["ff_events"] = int(jax.device_get(sim.state.ff_events))
        row["ff_quanta"] = ffq
        row["ff_quanta_frac"] = round(ffq / max(quanta, 1), 4)
    report_path = _emit_row_telemetry(label, summary, row_spans)
    if report_path:
        row["telemetry"] = report_path
    return row


def _pallas_ab_row():
    """Round-10 kernels A/B on the radix8 shape: the SAME trace with
    ``tpu/pallas_kernels`` off vs interpret (the CPU-testable kernel
    path), reporting rounds for both and the bit-identity flag
    ``kernels_match_lax`` (clocks + every counter).  Interpret mode is
    an emulation — its host time is NOT a speed claim; the structural
    row + PROFILE.md round 10 carry the device-win evidence."""
    import numpy as np

    from graphite_tpu.config import load_config
    from graphite_tpu.engine.sim import Simulator
    from graphite_tpu.events import synth
    from graphite_tpu.params import SimParams

    T = 8
    trace = _synth_cached("gen_radix", synth.gen_radix, num_tiles=T,
                          keys_per_tile=64, radix=16, seed=3)

    def one(mode):
        cfg = load_config()
        cfg.set("general/total_cores", T)
        cfg.set("tpu/miss_chain", 12)
        cfg.set("tpu/pallas_kernels", mode)
        params = SimParams.from_config(cfg)
        sim = Simulator(params, trace)
        s = sim.run(max_steps=256)
        import jax
        return int(jax.device_get(sim.state.round_ctr)), s

    t0 = time.perf_counter()
    rounds_off, a = one("off")
    rounds_on, b = one("interpret")
    host_s = time.perf_counter() - t0
    match = bool(a.done.all() and b.done.all()) \
        and bool(np.array_equal(a.clock, b.clock)) \
        and all(np.array_equal(a.counters[k], b.counters[k])
                for k in a.counters)
    return {
        "kind": "completed" if match else "failed",
        "num_tiles": T,
        "host_seconds": round(host_s, 3),
        "engine_rounds": rounds_on,
        "rounds_lax": rounds_off,
        "kernels_match_lax": match,
        "workload": "radix8 chain12: pallas_kernels interpret vs off",
    }


def _structural_row(main_run):
    """Lowered-op evidence for the kernel win (no TPU attached in this
    container, so the dispatch-cost drop is recorded structurally, like
    round 6's 78 -> 68 scatter count): jaxpr op counts of one window
    round and one resolve pass at the radix64 bench config, kernels off
    vs on.  With kernels on the window phase is exactly ONE pallas_call
    equation — one TPU custom-call by construction.  Back-fills
    ``lowered_window_calls`` / scatter counts into the radix64 headline
    row so results_db tracks them per run."""
    import dataclasses

    from graphite_tpu.config import load_config
    from graphite_tpu.engine import core
    from graphite_tpu.engine import resolve as rs
    from graphite_tpu.engine.kernels import dispatch as kdispatch
    from graphite_tpu.engine.sim import Simulator
    from graphite_tpu.engine.vparams import variant_params
    from graphite_tpu.events import synth
    from graphite_tpu.params import SimParams

    cfg = load_config()
    cfg.set("general/total_cores", NUM_TILES)
    cfg.set("tpu/miss_chain", 12)
    p_off = dataclasses.replace(SimParams.from_config(cfg),
                                pallas_kernels="off")
    p_on = dataclasses.replace(p_off, pallas_kernels="interpret")
    trace = _synth_cached("gen_radix", synth.gen_radix,
                          num_tiles=NUM_TILES, keys_per_tile=64,
                          radix=64, seed=1)
    sim = Simulator(p_off, trace)

    def counts(p, phase):
        vp = variant_params(p)
        if phase == "window":
            fn = lambda s: core._block_retire(p, vp, s, sim.trace)
        else:
            fn = lambda s: rs.resolve_memory(p, vp, s)
        return kdispatch.jaxpr_op_counts(fn, sim.state)

    w_off = counts(p_off, "window")
    w_on = counts(p_on, "window")
    r_off = counts(p_off, "resolve")
    r_on = counts(p_on, "resolve")
    row = {
        "kind": "completed",
        "num_tiles": NUM_TILES,
        "lowered_window_calls": w_on["pallas_call"],
        "window_eqns": {"off": w_off["eqns"], "on": w_on["eqns"]},
        "window_gathers": {"off": w_off["gather"], "on": w_on["gather"]},
        "window_scatters": {"off": w_off["scatter"],
                            "on": w_on["scatter"]},
        "resolve_pallas_calls": r_on["pallas_call"],
        "resolve_eqns": {"off": r_off["eqns"], "on": r_on["eqns"]},
        "resolve_gathers": {"off": r_off["gather"], "on": r_on["gather"]},
        "resolve_scatters": {"off": r_off["scatter"],
                             "on": r_on["scatter"]},
        "workload": "jaxpr op counts, radix64 config, kernels off vs on",
    }
    # Headline-row metrics (results_db regression-flags these).
    main_run["lowered_window_calls"] = w_on["pallas_call"]
    main_run["lowered_window_scatters_off"] = w_off["scatter"]
    main_run["lowered_resolve_scatters_off"] = r_off["scatter"]
    main_run["lowered_resolve_scatters_on"] = r_on["scatter"]
    return row


def _sweep_row():
    import time

    import numpy as np

    from graphite_tpu import obs
    from graphite_tpu.config import load_config
    from graphite_tpu.engine.sim import Simulator
    from graphite_tpu.events import synth
    from graphite_tpu.sweep import SweepDriver, build_variants

    V = 8
    T = 8
    cfg = load_config()
    cfg.set("general/total_cores", T)
    trace = synth.gen_radix(T, keys_per_tile=256, radix=64, seed=7)
    specs = ["dram/latency=" + ",".join(
        str(60 + 20 * i) for i in range(V))]
    variants = build_variants(cfg, specs)
    assert len(variants) == V

    with obs.span("radix8_sweep8.warmup"):
        warm = SweepDriver(trace, max_steps=2)
        for _, _, p in variants:
            warm.submit(p)
        warm.drain()

    drv = SweepDriver(trace)
    tickets = [drv.submit(p) for _, _, p in variants]
    t0 = time.perf_counter()
    with obs.span("radix8_sweep8.timed_run"):
        results = drv.drain()
    host_s = time.perf_counter() - t0
    summaries = [results[t] for t in tickets]
    all_done = all(bool(s.done.all()) for s in summaries)

    # Bit-identity spot check: first + last lanes vs solo runs (checking
    # all 8 would pay 8 serial compiles for no extra signal — the lanes
    # run one program, so two endpoints witness the whole batch).
    def matches(idx):
        solo = Simulator(variants[idx][2], trace).run()
        lane = summaries[idx]
        if not np.array_equal(lane.clock, solo.clock):
            return False
        return all(np.array_equal(lane.counters[k], solo.counters[k])
                   for k in lane.counters)

    sweep_matches_serial = bool(matches(0) and matches(V - 1))
    return {
        "kind": "completed" if all_done else "throughput_probe",
        "num_tiles": T,
        "variants": V,
        "host_seconds": round(host_s, 3),
        "variants_per_sec": round(V / max(host_s, 1e-9), 3),
        "sweep_matches_serial": sweep_matches_serial,
        "compiles": drv.compiles_observed,
        "all_done": all_done,
        "completion_time_ns_by_variant": [
            round(s.completion_time_ps / 1000.0, 1) for s in summaries],
        "workload": "radix8 x 8 DRAM-latency variants (vmapped sweep)",
    }


def _service_row():
    """Fault-tolerant service layer over the sweep engine (ISSUE 15):
    V=4 design points served through SweepService (journal + results_db)
    and then RE-SERVED from cache by a second service instance —
    cache_hits must equal V with zero buckets run, which is the
    serve-from-cache acceptance shape as a bench row."""
    import os
    import shutil
    import tempfile
    import time

    from graphite_tpu.config import load_config
    from graphite_tpu.events import synth
    from graphite_tpu.sweep import SweepDriver, SweepService, build_variants

    V = 4
    T = 8
    cfg = load_config()
    cfg.set("general/total_cores", T)
    trace = synth.gen_radix(T, keys_per_tile=64, radix=16, seed=9)
    spec = ["dram/latency=" + ",".join(
        str(60 + 20 * i) for i in range(V))]
    variants = build_variants(cfg, spec)
    points = [overrides for _, overrides, _ in variants]

    tmp = tempfile.mkdtemp(prefix="svc_bench_")
    try:
        # Warm the V=4 bucket program so host_seconds is serving time,
        # not compile time (same policy as every other row).
        warm = SweepDriver(trace, max_steps=2)
        for _, _, p in variants:
            warm.submit(p)
        warm.drain()

        db = os.path.join(tmp, "results.db")
        svc = SweepService(trace, os.path.join(tmp, "j1"), cfg=cfg,
                           db_path=db)
        tids = [svc.submit(ov) for ov in points]
        t0 = time.perf_counter()
        res = svc.serve()
        host_s = time.perf_counter() - t0
        all_done = all(res[t].status == "done" for t in tids)

        svc2 = SweepService(trace, os.path.join(tmp, "j2"), cfg=cfg,
                            db_path=db)
        for ov in points:
            svc2.submit(ov)
        t1 = time.perf_counter()
        svc2.serve()
        cache_s = time.perf_counter() - t1
        # Serving-latency percentiles from the SIMULATED pass (svc):
        # first-result latency is submit -> streamed lane-done poll,
        # so it reflects the per-lane streaming path, not cache reads.
        lat = svc.latency_stats()
        lat2 = svc2.latency_stats()
        return {
            "kind": "completed" if all_done else "throughput_probe",
            "num_tiles": T,
            "variants": V,
            "host_seconds": round(host_s, 3),
            "variants_per_sec": round(V / max(host_s, 1e-9), 3),
            "compiles": svc.compiles_observed,
            "cache_hits": svc2.stats["cache_hits"],
            "cache_serve_seconds": round(cache_s, 3),
            "served_from_cache": bool(
                svc2.stats["cache_hits"] == V
                and svc2.stats["buckets_run"] == 0),
            "p50_first_result_s": (
                round(lat["p50_first_result_s"], 4)
                if lat["p50_first_result_s"] is not None else None),
            "p99_first_result_s": (
                round(lat["p99_first_result_s"], 4)
                if lat["p99_first_result_s"] is not None else None),
            "cache_hit_ratio": lat2["cache_hit_ratio"],
            "all_done": all_done,
            "workload": "radix8 x 4 variants via fault-tolerant service "
                        "+ results_db cache re-serve",
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# Captured SPLASH-2 workloads (reference: tests/benchmarks/Makefile:4-8):
# UNMODIFIED vendored sources, macro-expanded (tools/splash_m4.py) +
# TSan-instrumented (tools/capture_build.sh), run natively to produce a
# real event trace.  Sources + args are sized so each row simulates in
# about a minute on one chip.
_CAPTURES = {
    # radix at -n16384 keeps the captured row's full-bench share ~5 min
    # (the r5 -n32768 run simulated 26M instructions in 558 s — fine
    # alone, but the whole bench must fit the driver budget that the r4
    # round blew).
    "radix": dict(srcs=["radix/radix.C"],
                  args=["-p64", "-n16384", "-r256"]),
    "fft": dict(srcs=["fft/fft.C"], args=["-p64", "-m12"], libs=["-lm"]),
    "lu": dict(srcs=["lu_contiguous/lu.C"], args=["-p64", "-n64"],
               libs=["-lm"]),
    # barnes is trace-dense (TSan instruments the whole O(N log N) force
    # phase), so its capture runs under a first-120k-events-per-tile
    # sampling window (CARBON_MAX_EVENTS_PER_TILE keeps the sync
    # skeleton complete past the cap, so the trace still runs to
    # completion; timing covers the captured prefix).
    "barnes": dict(srcs=["barnes/code.C", "barnes/code_io.C",
                         "barnes/getparam.C", "barnes/load.C",
                         "barnes/grav.C", "barnes/util.C"],
                   headers=["barnes/code.H", "barnes/code_io.H",
                            "barnes/defs.H", "barnes/getparam.H",
                            "barnes/grav.H", "barnes/load.H",
                            "barnes/stdinc.H", "barnes/util.H",
                            "barnes/vectmath.H"],
                   args=[], libs=["-lm"], tiles=32,
                   env={"CARBON_MAX_EVENTS_PER_TILE": "120000"},
                   stdin="\n128\n123\n\n0.025\n0.05\n1.0\n2.0\n5.0\n"
                         "0.05\n0.25\n32\n"),
}


def _pad_trace(trace):
    """Pad the event axis up to the next power of two with NOPs so
    repeated captures (whose raw event counts jitter with native thread
    interleaving) land on ONE compiled program shape."""
    import numpy as np

    from graphite_tpu.events.schema import Trace
    n = trace.ops.shape[1]
    n2 = 1 << (n - 1).bit_length()
    if n2 == n:
        return trace
    pad = ((0, 0), (0, n2 - n))
    return Trace(ops=np.pad(trace.ops, pad), addr=np.pad(trace.addr, pad),
                 arg=np.pad(trace.arg, pad), arg2=np.pad(trace.arg2, pad))


def _captured_row(name: str):
    """Build + run + simulate one captured benchmark; returns a bench row,
    or None when the reference tree is absent.  A capture or simulation
    error propagates (the row fails)."""
    import os
    import subprocess
    import sys
    import tempfile

    from graphite_tpu import obs

    spec = _CAPTURES[name]
    bench_root = "/root/reference/tests/benchmarks"
    macros = os.path.join(bench_root, "splash_support/c.m4.null.POSIX")
    repo = os.path.dirname(os.path.abspath(__file__))
    if not os.path.exists(os.path.join(bench_root, spec["srcs"][0])):
        return None

    def _capture_build():
        """Build + run + annotate ONE capture; returns the padded Trace.
        Only runs on a trace-cache miss — capture output is
        deterministic in (sources, args, env), and the r05 bench burned
        its budget re-annotating ~890k-event traces every invocation."""
        with obs.span(f"{name}.capture"), \
                tempfile.TemporaryDirectory() as td:
            def expand(rel, out_name):
                out = subprocess.run(
                    [sys.executable,
                     os.path.join(repo, "tools", "splash_m4.py"),
                     macros, os.path.join(bench_root, rel)],
                    check=True, capture_output=True, text=True)
                path = os.path.join(td, out_name)
                with open(path, "w") as f:
                    f.write(out.stdout)
                return path

            csrcs = [expand(rel, f"{name}_{i}.c")
                     for i, rel in enumerate(spec["srcs"])]
            for rel in spec.get("headers", []):
                base = os.path.basename(rel)[:-2].lower() + ".h"
                expand(rel, base)
            exe = os.path.join(td, name)
            subprocess.run(
                ["bash", os.path.join(repo, "tools", "capture_build.sh"),
                 *csrcs, "-o", exe, "-I", td, *spec.get("libs", [])],
                check=True, capture_output=True)
            trace_path = os.path.join(td, f"{name}.trc")
            env = dict(os.environ, CARBON_TRACE_PATH=trace_path,
                       CARBON_MAX_TILES=str(spec.get("tiles", 64)),
                       **spec.get("env", {}))
            subprocess.run([exe, *spec["args"]], check=True, env=env,
                           capture_output=True, timeout=600,
                           input=spec.get("stdin", "").encode() or None)
            # Static-decode annotation: replace the runtime's per-block
            # instruction estimates with the binary's real typed costs
            # (tools/annotate_trace.py; the capture analog of the
            # reference's Pin decode, instruction_modeling.cc:157-348).
            sys.path.insert(0, os.path.join(repo, "tools"))
            from annotate_trace import annotate_raw
            with obs.span(f"{name}.annotate"):
                annotate_raw(exe, trace_path)
            from graphite_tpu.events.binio import load_binary_trace
            with obs.span(f"{name}.trace_load"):
                return _pad_trace(load_binary_trace(trace_path))

    from graphite_tpu.events import trace_cache
    # Key includes the CONTENT of the vendored sources/headers and
    # the capture toolchain, not just their names — an edited
    # benchmark source or frontend change re-captures.
    srcs = [os.path.join(bench_root, rel)
            for rel in spec["srcs"] + spec.get("headers", [])]
    tools = [os.path.join(repo, "tools", t)
             for t in ("capture_build.sh", "annotate_trace.py",
                       "splash_m4.py")]
    trace = trace_cache.cached(
        ("captured", name, spec["srcs"], spec["args"],
         spec.get("tiles", 64), sorted(spec.get("env", {}).items()),
         spec.get("stdin", "")),
        _capture_build, src_files=srcs + tools + [macros])
    row = _run(lambda T: trace, trace.num_tiles,
               label=f"{name}_captured",
               **{"general/trigger_models_within_application": "true",
                  "tpu/cond_replay": "true"})
    row["workload"] = f"SPLASH-2 {name} (captured, unmodified source)"
    return row


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--help" in argv or "-h" in argv:
        print(__doc__)
        print(f"env: GRAPHITE_BENCH_BUDGET_S   wall-clock budget in "
              f"seconds (default {DEFAULT_BUDGET_S:.0f}); rows starting "
              f"past it emit kind=skipped_budget\n"
              f"     GRAPHITE_BENCH_TELEMETRY_DIR   RunReport/trace "
              f"output dir ('' disables; default ./bench_telemetry)")
        return 0

    from graphite_tpu import obs
    from graphite_tpu.events import synth

    if TELEMETRY_DIR:
        obs.enable_tracing()
    t_start = time.monotonic()
    budget_s = float(os.environ.get("GRAPHITE_BENCH_BUDGET_S",
                                    str(DEFAULT_BUDGET_S)))

    radix = lambda keys: (
        lambda T: _synth_cached("gen_radix", synth.gen_radix,
                                num_tiles=T, keys_per_tile=keys,
                                radix=256))
    # Pending headline FIRST: whatever kills the process mid-row-1, the
    # driver's tail still parses a headline-shaped JSON line (the r05
    # run died during row work and left an annotator progress line as
    # the last stdout line — parsed: null).
    print(json.dumps({"metric": "simulated_mips_radix64", "value": None,
                      "unit": "MIPS", "vs_baseline": None,
                      "kind": "pending", "detail": {}}), flush=True)
    main_run = _run(radix(KEYS_PER_TILE), NUM_TILES, label="radix64")
    mips = main_run["mips"] or 0.0
    out = {
        "metric": "simulated_mips_radix64",
        "value": mips,
        "unit": "MIPS",
        "vs_baseline": round(mips / BASELINE_MIPS, 4),
        "vs_baseline_bracket": {
            f"at_{int(b)}_mips": round(mips / b, 4)
            for b in BASELINE_BRACKET_MIPS},
        "detail": {"radix64": main_run},
    }
    det = out["detail"]

    def emit():
        """Re-print the whole result as ONE line after every row: the
        driver keeps the last complete line, so a kill at any point
        still leaves every finished row on record."""
        print(json.dumps(out), flush=True)

    emit()                       # headline lands before any other row

    def chain_ab():
        """radix64 headline A/B partner: the SAME trace with
        tpu/miss_chain = 12 (blocking-semantics chain replay), so every
        BENCH records the round-count win next to the baseline row —
        compare engine_rounds / events_per_round against detail.radix64
        (identical config otherwise)."""
        row = _run(radix(KEYS_PER_TILE), NUM_TILES, label="radix64_chain12",
                   **{"tpu/miss_chain": 12})
        base_rounds = main_run.get("engine_rounds") or 0
        if base_rounds and row.get("engine_rounds"):
            row["rounds_vs_miss_chain_0"] = round(
                base_rounds / row["engine_rounds"], 2)
        return row

    def safe(key, fn, optional=False):
        """One broken row must not stop the later rows (the r4 bench
        died whole and left the round numberless), and one SLOW row must
        not overrun the driver timeout (the r4/r5 rc=124).  A broken row
        is recorded as ``kind: failed`` and makes the bench exit
        non-zero at the end.  ``optional`` rows may return None
        (workload unavailable in this container) and then leave no
        detail entry at all."""
        spent = time.monotonic() - t_start
        if spent >= budget_s:
            det[key] = {"kind": "skipped_budget",
                        "budget_s": budget_s,
                        "elapsed_s": round(spent, 1)}
        else:
            try:
                row = fn()
            except Exception as e:
                row = {"kind": "failed",
                       "reason": f"{type(e).__name__}: {str(e)[:200]}"}
            if row is None and optional:
                return
            det[key] = row
        emit()

    # Miss-chain A/B: the headline trace with chains on (ISSUE 6) —
    # runs FIRST so the round-count evidence survives any later timeout.
    safe("radix64_chain12", chain_ab)

    def fanout_ab():
        """Sharing-heavy fan-out A/B (ISSUE 9): a write-back fft64 trace
        (alternating transpose direction — every transpose writes lines
        the previous one left read-shared, the real fft.C signature)
        under the chain replay, with the round-9 fan-out leg ON vs OFF
        (``tpu/fanout_replay``).  rounds_vs_head8 is the round-count
        ratio against the round-8 engine (fan-outs demoted to the
        one-element-per-round fallback); chain_fanout_served /
        chain_fallback report the in-pass fan-out occupancy."""
        fft_wb = lambda T: _synth_cached(
            "gen_fft", synth.gen_fft, num_tiles=T, points_per_tile=64,
            writeback=True)
        row = _run(fft_wb, NUM_TILES, label="fft64",
                   **{"tpu/miss_chain": 12})
        off = _run(fft_wb, NUM_TILES, label="fft64_fanout_off",
                   **{"tpu/miss_chain": 12, "tpu/fanout_replay": "false"})
        row["rounds"] = row["engine_rounds"]
        if row.get("engine_rounds") and off.get("engine_rounds"):
            row["rounds_vs_head8"] = round(
                off["engine_rounds"] / row["engine_rounds"], 2)
        row["fanout_off_rounds"] = off.get("engine_rounds")
        row["workload"] = "fft64 write-back transposes (sharing-heavy)"
        return row

    safe("fft64", fanout_ab)

    def ff_radix_ab():
        """Round-12 adaptive-fidelity A/B: the radix64 headline trace
        with ``tpu/fast_forward`` on, against the headline row (ff = 0,
        the exact program).  rounds_vs_ff_0 is the round-count win of
        pricing miss-free spans in closed form; ff_drift is the
        completion-time error vs exact, budgeted at <= 2% (the same
        ceiling tools/run_tests.sh gates on a tiny shape every run)."""
        row = _run(radix(KEYS_PER_TILE), NUM_TILES, label="radix64_ff",
                   **{"tpu/fast_forward": 8})
        base_rounds = main_run.get("engine_rounds") or 0
        base_ct = main_run.get("completion_time_ns") or 0
        if base_rounds and row.get("engine_rounds"):
            row["rounds_vs_ff_0"] = round(
                base_rounds / row["engine_rounds"], 2)
        if base_ct and row.get("completion_time_ns"):
            row["ff_drift"] = round(
                abs(row["completion_time_ns"] - base_ct) / base_ct, 6)
        return row

    safe("radix64_ff", ff_radix_ab)

    def ff_fft_ab():
        """fft64_ff: the sharing-heavy write-back fft64 trace (the
        fft64 fan-out row's exact config, chains on) with
        ``tpu/fast_forward`` added — evidences the analytic leg's
        drift and round win under coherence traffic + chain replay,
        not just the radix hit-run best case.  Reuses the recorded
        fft64 row as the ff = 0 base when it completed (identical
        config otherwise); runs its own base leg only if that row is
        missing."""
        fft_wb = lambda T: _synth_cached(
            "gen_fft", synth.gen_fft, num_tiles=T, points_per_tile=64,
            writeback=True)
        base = det.get("fft64") or {}
        if not base.get("engine_rounds"):
            base = _run(fft_wb, NUM_TILES, label="fft64_ff_off",
                        **{"tpu/miss_chain": 12})
        row = _run(fft_wb, NUM_TILES, label="fft64_ff",
                   **{"tpu/miss_chain": 12, "tpu/fast_forward": 8})
        if base.get("engine_rounds") and row.get("engine_rounds"):
            row["rounds_vs_ff_0"] = round(
                base["engine_rounds"] / row["engine_rounds"], 2)
        base_ct = base.get("completion_time_ns") or 0
        if base_ct and row.get("completion_time_ns"):
            row["ff_drift"] = round(
                abs(row["completion_time_ns"] - base_ct) / base_ct, 6)
        return row

    safe("fft64_ff", ff_fft_ab)

    # Round-10 kernel rows: the radix8 interpret-vs-lax A/B (bit-identity
    # flag) and the structural lowered-op evidence at the radix64 config
    # (back-fills lowered_window_calls into the headline row on re-emit).
    safe("radix8_pallas", _pallas_ab_row)
    safe("pallas_structural", lambda: _structural_row(main_run))

    # Sweep-engine row (ISSUE 7): V=8 DRAM-latency variants of a radix8
    # trace as ONE vmapped device program — the design-space-exploration
    # amortization headline.  variants_per_sec is the sweep's throughput
    # unit (completed config points per host second, compile excluded by
    # the warm-up drain like every other row); sweep_matches_serial
    # asserts the bit-identity contract on the batch's first and last
    # lanes against solo Simulator runs (clocks + every counter).
    safe("radix8_sweep8", _sweep_row)

    # Service-layer row (ISSUE 15): the same sweep engine behind the
    # crash-safe ticket service, plus the serve-from-cache re-serve —
    # cache_hits == variants with zero buckets run is the cache tier
    # working end to end (results_db keyed on structural + variant
    # signatures + trace hash).
    safe("radix8_service", _service_row)

    def _streamed_row():
        """Round-16 streaming-ingest row: a radix8 trace with a per-tile
        event axis ~4x the longest current synthetic (keys_per_tile =
        8192 vs the radix64 headline's 2048), simulated with only TWO
        segment-sized trace slices device-resident — the
        bigger-than-HBM demonstration, with the device trace footprint
        capped at peak_device_trace_bytes regardless of trace length.
        Segment sizing forces well past the acceptance floor of 4
        seams; ingest_stall_fraction is the double-buffering headline
        (near-zero = prefetch fully hides uploads behind megasteps) and
        chains in results_db with a >20% growth flag."""
        KEYS, SEG, T = 8192, 4096, 8
        trace_fn = lambda _: _synth_cached(
            "gen_radix", synth.gen_radix, num_tiles=T,
            keys_per_tile=KEYS, radix=64)
        row = _run(trace_fn, T, label="radix8_streamed",
                   **{"trace/segment_events": SEG})
        n_total = trace_fn(T).ops.shape[1]
        whole_bytes = T * n_total * (8 + 3 * 4)
        row["trace_events_per_tile"] = n_total
        row["whole_trace_bytes"] = whole_bytes
        if row.get("peak_device_trace_bytes"):
            row["trace_bytes_vs_whole"] = round(
                row["peak_device_trace_bytes"] / whole_bytes, 4)
        row["workload"] = ("radix8 long trace via streaming segmented "
                           "ingest (two resident segments)")
        return row

    safe("radix8_streamed", _streamed_row)

    # BASELINE config 1 scaling: radix at 256 and 1024 tiles.  Every
    # point COMPLETES (valid MIPS) — the 1024 row runs a narrow block
    # window (the trace is miss-dominated, so a wide window only pays
    # gather cost) on a completion-sized key count; this is the config
    # the north star scores (BASELINE.json).
    safe("radix256", lambda: _run(radix(96), 256, label="radix256"))
    safe("radix1024", lambda: _run(
        lambda T: _synth_cached("gen_radix", synth.gen_radix,
                                num_tiles=T, keys_per_tile=16, radix=64),
        1024, label="radix1024", **{"tpu/block_events": 4}))

    def shard8_ab():
        """Round-11 scale-out A/B: the radix1024 shape with
        ``tpu/tile_shards = 8`` vs 1, in THIS process on the devices it
        sees (a chip belongs to one process, so no child can take it) —
        reports quanta_per_s for both legs and the bit-identity flag.
        Fewer than 8 devices skips the row and says so."""
        import jax
        if len(jax.devices()) < 8:
            return {"kind": "skipped",
                    "reason": f"needs 8 devices; jax sees "
                              f"{len(jax.devices())} "
                              f"({jax.devices()[0].platform})"}
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        from weak_scaling import shard_ab_row
        return shard_ab_row(8, tiles=1024)

    safe("radix1024_shard8", shard8_ab)
    # BASELINE config 2: directory-MSI coherence stress at 256 tiles,
    # sized to complete.
    safe("fft256", lambda: _run(
        lambda T: _synth_cached("gen_fft", synth.gen_fft, num_tiles=T,
                                points_per_tile=64), 256,
        label="fft256"))
    safe("lu256", lambda: _run(
        lambda T: _synth_cached("gen_lu", synth.gen_lu, num_tiles=T,
                                matrix_blocks=8, block_lines=4), 256,
        label="lu256"))
    # Real workloads: reference SPLASH-2 programs captured from
    # UNMODIFIED vendored source via the TSan frontend (VERDICT r4
    # missing #9 — fft/lu/barnes as real captures, not synthetics).
    # Optional: a container without the reference tree yields no row.
    for name in ("radix", "fft", "lu", "barnes"):
        tiles = _CAPTURES[name].get("tiles", 64)
        safe(f"{name}{tiles}_captured",
             lambda name=name: _captured_row(name), optional=True)
    emit()
    failed = [k for k, v in det.items()
              if isinstance(v, dict) and v.get("kind") == "failed"]
    if failed:
        print(f"bench rows failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
