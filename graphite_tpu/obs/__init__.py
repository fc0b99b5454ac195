"""Run telemetry: host span tracing, device round metrics, exports.

The reference treats observability of the *simulated machine* as
first-class (StatisticsManager sampling, progress trace, Log framework —
statistics_manager.cc:41-114, pin/progress_trace.cc, common/misc/log.h);
engine/sim.py + engine/quantum.py carry those over.  This package adds
observability of the *simulator itself*:

  * ``scopes`` — the names of the quantum program's phases, each applied
    as a ``jax.named_scope`` where the engine does that phase's work
    (barrier, schedule, sample, window walk, complex slot, fast forward,
    memory resolve, sync resolve).  Metadata only: a profiler trace of
    the chip splits device time by phase through each op's ``op_name``.
  * ``spans`` — nestable host-side wall-clock span tracing for the driver
    path (config resolution, trace load/upload/scan, state alloc, each
    polling window split into ``sim.dispatch`` and ``sim.sync``).  An
    enabled span also writes a ``jax.profiler.TraceAnnotation``, so it
    lands in a running profiler trace on the device's clock.  Near-zero
    overhead when disabled: one attribute check and a shared no-op
    context manager.
  * ``metrics`` — the device round-metric series sampled at quantum
    boundaries by engine/quantum._maybe_sample when [telemetry] is
    enabled (engine-health gauges: events retired, stall-reason
    breakdown, quanta/round counters, clock skew) plus per-tile
    progress/occupancy snapshots.
  * ``export`` — a machine-readable RunReport JSON (superset of the text
    summary; consumed by bench.py / tools/results_db.py) and a Chrome
    trace-event / Perfetto JSON merging host wall-clock span tracks with
    per-tile simulated-time tracks (plus, when given tickets, the sweep
    service's per-ticket lifecycle track on the same wall-clock axis).
  * ``registry`` — process-wide SERVICE metrics (counters, gauges,
    fixed-bucket histograms with labels): the sweep service's ticket
    latencies, cache-hit ratio, and per-state gauges, rendered as a
    Prometheus text exposition + JSON snapshot.  Same null-path
    discipline as spans: one attribute check when disabled.
"""

from graphite_tpu.obs.spans import (  # noqa: F401
    SpanTracer, enable_tracing, get_tracer, span, tracing_enabled)
from graphite_tpu.obs.metrics import TEL_SERIES  # noqa: F401
from graphite_tpu.obs.registry import (  # noqa: F401
    MetricsRegistry, enable_metrics, get_registry, metrics_enabled,
    parse_exposition, render_exposition, write_exposition)
from graphite_tpu.obs.export import (  # noqa: F401
    RUN_REPORT_SCHEMA, build_run_report, chrome_trace, ticket_events,
    write_telemetry_dir)
