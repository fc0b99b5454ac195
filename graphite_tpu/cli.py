"""Command-line launcher.

Plays the role of the reference's Makefile/tools launcher layer
(reference: tools/, tests/Makefile.tests:44-78): compose a config from a
file plus ``--section/key=value`` overrides and run a simulation.

Usage:
    graphite-tpu run [-c CONFIG] [--section/key=value ...] --trace TRACE.npz
    graphite-tpu sweep [-c CONFIG] --trace TRACE.npz --sweep key=v1,v2 ...
    graphite-tpu params [-c CONFIG] [--section/key=value ...]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import List, Optional

from graphite_tpu.config import load_config, parse_overrides
from graphite_tpu.params import SimParams


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="graphite-tpu")
    sub = ap.add_subparsers(dest="command", required=True)

    run = sub.add_parser("run", help="run a simulation from a trace")
    run.add_argument("-c", "--config", default=None)
    run.add_argument("--trace", required=True, help="trace .npz path")
    run.add_argument("-o", "--output", default=None, help="summary output path")
    run.add_argument("--telemetry-dir", default=None, metavar="DIR",
                     help="enable run telemetry (host span tracing + "
                          "[telemetry] round metrics) and write "
                          "run_report.json + run_trace.json under DIR")
    run.add_argument("--segment-events", type=int, default=None,
                     metavar="N",
                     help="streaming segmented ingest (round 16): keep "
                          "only two N-event device-resident trace "
                          "segments (active + prefetch) and stream the "
                          "host trace through them — traces bigger than "
                          "HBM run whole, bit-identically. Shorthand "
                          "for --trace/segment_events=N. Unvalidated "
                          "combinations (resident shard_state, "
                          "fast_forward, multi-thread scheduling) are "
                          "rejected loudly")

    sw = sub.add_parser(
        "sweep", help="run V config variants of one trace as a single "
                      "vmapped device program")
    sw.add_argument("-c", "--config", default=None)
    sw.add_argument("--trace", required=True, help="trace .npz path")
    sw.add_argument("--sweep", action="append", default=[], metavar="SPEC",
                    help="sweep axis: section/key=v1,v2,... — repeat for "
                         "a cross product; join keys with ';' inside one "
                         "flag to zip them (sweep/space.py grammar). "
                         "Keys must be VARIANT leaves (timing numerics); "
                         "structural keys are rejected. Required unless "
                         "--resume replays an existing journal.")
    sw.add_argument("-o", "--output", default=None,
                    help="write per-variant JSON rows here (shaped like a "
                         "bench result: {'detail': {label: row}}, so "
                         "tools/results_db.py add ingests it directly)")
    sw.add_argument("--serve", action="store_true",
                    help="run through the fault-tolerant SweepService "
                         "(crash-safe ticket journal, bucket bisection, "
                         "preempt/resume — sweep/service.py) instead of "
                         "the bare driver; requires --journal")
    sw.add_argument("--resume", action="store_true",
                    help="recover an interrupted service run from "
                         "--journal (re-queues in-flight tickets, "
                         "resumes preempted buckets, never re-runs DONE "
                         "ones); implies --serve, --sweep optional")
    sw.add_argument("--journal", default=None, metavar="DIR",
                    help="service journal directory (ticket records + "
                         "preemption checkpoints)")
    sw.add_argument("--db", default=None, metavar="PATH",
                    help="results_db sqlite path: completed tickets are "
                         "stored and identical re-submissions are served "
                         "from cache without simulating")
    sw.add_argument("--segment-events", type=int, default=None,
                    metavar="N",
                    help="key tickets on the N-event streamed content "
                         "hash (events/segments.py) instead of the "
                         "whole-trace hash — identical streamed "
                         "submissions share DONE tickets and cached "
                         "rows. Shorthand for --trace/segment_events=N "
                         "(buckets still execute whole-trace)")
    sw.add_argument("--metrics-path", default=None, metavar="PATH",
                    help="(--serve only) enable the obs metrics "
                         "registry and write its Prometheus text "
                         "exposition here, atomically after every "
                         "drain and once more on exit (ticket_latency_s"
                         " / first_result_latency_s histograms, "
                         "cache_hit_ratio, tickets_in_state, ...)")

    st = sub.add_parser(
        "status", help="summarize a sweep-service journal: per-state "
                       "counts and a per-ticket table (works on a live "
                       "service's journal — records are atomic)")
    st.add_argument("-c", "--config", default=None)
    st.add_argument("--journal", required=True, metavar="DIR",
                    help="service journal directory to fold")
    st.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the raw journal_status dict as JSON "
                         "instead of the table")

    par = sub.add_parser("params", help="print derived simulation parameters")
    par.add_argument("-c", "--config", default=None)
    return ap


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    overrides, rest = parse_overrides(argv)
    args = _build_parser().parse_args(rest)
    telemetry_dir = getattr(args, "telemetry_dir", None)
    from graphite_tpu import obs
    if telemetry_dir:
        obs.enable_tracing()
    with obs.span("config.load", path=args.config or "<defaults>"):
        cfg = load_config(args.config, overrides=overrides)
    if telemetry_dir and not any(p == "telemetry/enabled"
                                 for p, _ in overrides):
        cfg.set("telemetry/enabled", "true")
    if getattr(args, "segment_events", None) is not None:
        cfg.set("trace/segment_events", int(args.segment_events))
    from graphite_tpu import log as logmod
    logmod.configure(cfg)

    if args.command == "params":
        params = SimParams.from_config(cfg)
        print(json.dumps(dataclasses.asdict(params), indent=2, default=str))
        return 0

    if args.command == "run":
        try:
            return _run_command(cfg, args, telemetry_dir)
        finally:
            if telemetry_dir:
                # The tracer is process-global; a long-lived embedder
                # (tests, notebooks) must not keep accumulating spans
                # after this run's artifacts are written.
                obs.enable_tracing(False)

    if args.command == "sweep":
        return _sweep_command(cfg, args)

    if args.command == "status":
        return _status_command(args)

    return 2


def _sweep_command(cfg, args) -> int:
    import time

    from graphite_tpu.events.schema import Trace
    from graphite_tpu.sweep import SweepDriver, build_variants
    from graphite_tpu.time_base import ps_to_ns

    if args.serve or args.resume:
        return _serve_command(cfg, args)
    if not args.sweep:
        print("sweep: --sweep is required (unless --serve/--resume)",
              file=sys.stderr)
        return 2
    trace = Trace.load(args.trace)
    variants = build_variants(cfg, args.sweep, num_tiles=trace.num_tiles)
    drv = SweepDriver(trace)
    tickets = [(label, overrides, drv.submit(p))
               for label, overrides, p in variants]
    t0 = time.perf_counter()
    results = drv.drain()
    host_s = time.perf_counter() - t0
    detail = {}
    for label, overrides, ticket in tickets:
        s = results[ticket]
        d = s.to_dict()
        detail[label] = {
            "kind": "sweep_variant",
            "overrides": overrides,
            "num_tiles": d["num_tiles"],
            "completion_time_ns": d["completion_time_ns"],
            "total_instructions": d["total_instructions"],
            "all_done": d["all_done"],
            "quanta": d["quanta"],
            "aggregate": d["aggregate"],
        }
        # Round-12 adaptive-fidelity attribution rides the variant rows
        # when tpu/fast_forward > 0, so `results_db.py add` chains the
        # ff-quanta-fraction regression flag over sweep output too.
        for k in ("ff_rounds", "ff_quanta", "ff_events",
                  "ff_quanta_frac"):
            if k in d:
                detail[label][k] = d[k]
        print(f"{label}: completion "
              f"{ps_to_ns(s.completion_time_ps):.1f} ns, "
              f"{'done' if d['all_done'] else 'INCOMPLETE'}, "
              f"{d['total_instructions']} instrs")
    out = {
        "metric": "sweep",
        "workload": args.trace,
        "variants": len(tickets),
        "host_seconds": round(host_s, 3),
        "variants_per_sec": round(len(tickets) / max(host_s, 1e-9), 3),
        "compiles": drv.compiles_observed,
        "detail": detail,
    }
    line = json.dumps(out)
    if args.output:
        with open(args.output, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


def _serve_command(cfg, args) -> int:
    """sweep --serve / --resume: the fault-tolerant service path.  New
    --sweep points are submitted as tickets; with --resume the journal's
    recovered tickets (re-queued in-flight work, preempted buckets) are
    served too.  Output rows mirror the driver path's shape so
    results_db ingestion and the recovery gate's bit-identity diff work
    unchanged."""
    import time

    from graphite_tpu.events.schema import Trace
    from graphite_tpu.sweep import SweepService, parse_sweep_spec

    journal = args.journal or cfg.get_str("service/journal_dir", "")
    if not journal:
        print("sweep --serve/--resume needs --journal DIR",
              file=sys.stderr)
        return 2
    if not args.sweep and not args.resume:
        print("sweep --serve: nothing to do (no --sweep and no "
              "--resume)", file=sys.stderr)
        return 2
    trace = Trace.load(args.trace)
    svc = SweepService(trace, journal, cfg=cfg, db_path=args.db,
                       metrics_path=args.metrics_path)
    for overrides in parse_sweep_spec(args.sweep) if args.sweep else []:
        svc.submit(overrides)
    t0 = time.perf_counter()
    try:
        tickets = svc.serve()
    finally:
        # Exposition on exit even when serve() raises: the scrape file
        # reflects whatever the process actually got through.
        svc.write_metrics()
    host_s = time.perf_counter() - t0
    detail = {}
    for t in sorted(tickets.values(), key=lambda t: t.ticket):
        if t.status == "done":
            row = dict(t.summary)
            row["overrides"] = t.overrides
            row["ticket"] = t.ticket
            row["status"] = t.status
            row["from_cache"] = t.from_cache
        else:
            row = {"kind": "service_ticket", "ticket": t.ticket,
                   "overrides": t.overrides, "status": t.status,
                   "error": t.error}
        detail[t.label] = row
        print(f"ticket {t.ticket} [{t.label}]: {t.status}"
              f"{' (cache)' if t.from_cache else ''}"
              f"{' — ' + t.error if t.error else ''}")
    served = sum(1 for t in tickets.values() if t.status == "done")
    lat = svc.latency_stats()
    out = {
        "metric": "sweep_service",
        "workload": args.trace,
        "tickets": len(tickets),
        "variants": served,
        "host_seconds": round(host_s, 3),
        "variants_per_sec": round(served / max(host_s, 1e-9), 3),
        "p50_first_result_s": lat["p50_first_result_s"],
        "p99_first_result_s": lat["p99_first_result_s"],
        "cache_hit_ratio": lat["cache_hit_ratio"],
        "compiles": svc.compiles_observed,
        "stats": svc.stats,
        "detail": detail,
    }
    if args.metrics_path:
        out["metrics_path"] = args.metrics_path
    line = json.dumps(out)
    if args.output:
        with open(args.output, "w") as f:
            f.write(line + "\n")
    print(line)
    quarantined = sum(1 for t in tickets.values()
                      if t.status in ("quarantined", "failed"))
    return 0 if quarantined == 0 else 3


def _status_command(args) -> int:
    """status --journal DIR: fold the journal into a per-state /
    per-ticket table without loading a trace or building params."""
    import os

    from graphite_tpu.sweep.service import STATES, journal_status

    if not os.path.isdir(args.journal):
        print(f"status: no journal directory at {args.journal!r}",
              file=sys.stderr)
        return 2
    st = journal_status(args.journal)
    if args.as_json:
        print(json.dumps(st))
        return 0
    counts = " ".join(f"{s}={st['counts'][s]}" for s in STATES)
    print(f"journal {st['journal_dir']}: {len(st['tickets'])} tickets "
          f"({counts})")
    for k in ("p50_first_result_s", "p99_first_result_s",
              "p50_ticket_latency_s", "p99_ticket_latency_s"):
        if st[k] is not None:
            print(f"  {k} = {st[k]:.3f}")
    for r in st["tickets"]:
        tm = r["times"]
        when = ""
        if "submit" in tm and "done" in tm:
            when = f"  ({tm['done'] - tm['submit']:.3f}s)"
        elif "submit" in tm and "first_result" in tm:
            when = (f"  (first result after "
                    f"{tm['first_result'] - tm['submit']:.3f}s)")
        cache = " (cache)" if r["from_cache"] else ""
        err = f" — {r['error']}" if r["error"] else ""
        print(f"  ticket {r['ticket']:4d} [{r['label']}]: "
              f"{r['status']}{cache}{when}{err}")
    return 0


def _run_command(cfg, args, telemetry_dir: Optional[str]) -> int:
    from graphite_tpu import obs
    from graphite_tpu.engine.sim import run_simulation_from_trace

    summary = run_simulation_from_trace(cfg, args.trace)
    text = summary.render()
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    else:
        print(text)
    # [runtime_energy_modeling/power_trace] enabled=true: write the
    # per-interval power file beside the summary (reference
    # carbon_sim.cfg:141-145).
    if cfg.get_bool("runtime_energy_modeling/power_trace/enabled",
                    False):
        ptpath = (args.output or "sim") + ".power.csv"
        summary.write_power_trace(ptpath)
    if telemetry_dir:
        paths = summary.write_telemetry(
            telemetry_dir, tracer=obs.get_tracer(),
            workload=args.trace)
        print(f"telemetry: {paths['report']} "
              f"{paths['trace']} (open the trace in "
              f"https://ui.perfetto.dev or chrome://tracing)",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
