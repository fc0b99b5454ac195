"""Host-side span tracing for the simulator's own driver path.

The engine's device work is split by phase through ``jax.named_scope``
(obs/scopes.py) and sampled by the round-metric gauges (obs/metrics.py);
everything outside the jitted step — config resolution, trace load and
upload, state allocation, jit compile, each polling window's dispatch
(``sim.dispatch``) and progress read (``sim.sync``) — is wall-clock host
work.  A ``SpanTracer`` records nestable begin/end wall-clock events from
``with span(...)`` context managers and renders them as Chrome
trace-event ``X`` slices (obs/export.chrome_trace).  An enabled span also
opens a ``jax.profiler.TraceAnnotation`` of the same name, so under a
running profiler it lands on the host plane of the profiler's trace, on
the device trace's clock.

Disabled-path cost: ``span()`` on a disabled tracer is one attribute
check returning a shared no-op context manager — no allocation, no
clock read — so instrumentation can stay in the driver unconditionally.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation


class SpanEvent(NamedTuple):
    """One completed span (wall-clock, nanoseconds since tracer epoch)."""

    name: str
    t0_ns: int
    dur_ns: int
    depth: int
    args: Optional[Dict[str, Any]]


class _NullSpan:
    """Shared reentrant no-op context manager (the disabled path)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _LiveSpan:
    """Context manager recording one span into its tracer on exit, and
    into the profiler's trace (when one is running) as a
    ``TraceAnnotation`` of the same name."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_annotation")

    def __init__(self, tracer: "SpanTracer", name: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        self._tracer._depth += 1
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        tr = self._tracer
        tr._depth -= 1
        tr._record(SpanEvent(
            name=self._name, t0_ns=self._t0 - tr.epoch_ns,
            dur_ns=t1 - self._t0, depth=tr._depth, args=self._args))
        self._annotation.__exit__(*exc)
        return False


class SpanTracer:
    """Collects nested wall-clock spans; exported via obs/export.

    Events are appended at span EXIT (a parent therefore follows its
    children in ``events``); ``t0_ns`` is relative to the tracer's epoch
    so runs serialize with stable small timestamps.

    ``events`` is BOUNDED (``max_events``, default 64k): an always-on
    tracer inside a long-lived serving process must not grow without
    limit.  Past the cap, new spans are counted in ``dropped`` (and the
    process-wide ``spans_dropped_total`` registry counter) instead of
    recorded — the oldest spans win because they hold the compile story
    a drain's timeline is usually read for.
    """

    DEFAULT_MAX_EVENTS = 65536

    def __init__(self, enabled: bool = False,
                 max_events: int = DEFAULT_MAX_EVENTS):
        self.enabled = enabled
        self.epoch_ns = time.perf_counter_ns()
        self.events: List[SpanEvent] = []
        self.max_events = max_events
        self.dropped = 0
        self._depth = 0

    def span(self, name: str, **args):
        if not self.enabled:
            return _NULL_SPAN
        return _LiveSpan(self, name, args or None)

    def _record(self, event: SpanEvent) -> None:
        if len(self.events) >= self.max_events:
            self.dropped += 1
            from graphite_tpu.obs.registry import get_registry
            get_registry().counter(
                "spans_dropped_total",
                "spans discarded past SpanTracer.max_events").inc()
            return
        self.events.append(event)

    def clear(self) -> None:
        self.events = []
        self.dropped = 0
        self._depth = 0

    def mark(self) -> int:
        """Cursor into ``events`` for slicing one phase's spans later."""
        return len(self.events)

    def since(self, mark: int) -> List[SpanEvent]:
        return self.events[mark:]


# One process-wide tracer: the driver path is single-threaded host code,
# and a global keeps the instrumentation call sites one import away.
_TRACER = SpanTracer(enabled=False)


def get_tracer() -> SpanTracer:
    return _TRACER


def tracing_enabled() -> bool:
    return _TRACER.enabled


def enable_tracing(enabled: bool = True, reset: bool = True) -> SpanTracer:
    """Switch the global tracer on/off (fresh epoch/events by default)."""
    if reset:
        _TRACER.clear()
        _TRACER.epoch_ns = time.perf_counter_ns()
    _TRACER.enabled = enabled
    return _TRACER


def span(name: str, **args):
    """``with span("trace.load", path=...):`` on the global tracer."""
    return _TRACER.span(name, **args)
