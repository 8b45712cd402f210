"""repro.obs — unified tracing + metrics for the whole Deal pipeline.

One ``Telemetry`` object pairs a span ``Tracer`` (ring buffer, injectable
clock — see ``obs.trace``) with a ``MetricsRegistry`` (typed counters /
gauges / histograms under one naming scheme — see ``obs.metrics``), and
exporters turn either into a Perfetto-loadable trace JSON or a
Prometheus text dump (``obs.export``).

Instrumentation sites call the MODULE-LEVEL helpers so no tracer has to
be threaded through every constructor (the opentelemetry "current
provider" pattern):

    from repro import obs
    ...
    with obs.span("refresh.subset_plan") as sp:
        plan = build(...)
        if sp:                       # falsy in no-op mode: the attrs
            sp.set(rows=int(n))      # dict is never even built

    obs.add("store.evictions")       # counter += 1
    obs.observe("ops.spmm_ms", ms)   # histogram sample

The process default is a DISABLED singleton: every helper is a true
no-op whose cost is one attribute check (``tel.enabled``) and which
allocates nothing — hot paths stay instrumented at all times without a
perf tax.  The one exception is a span under an active ``jax.profiler``
trace: it then enters a ``TraceAnnotation`` of its name (still falsy),
so the program's phases show in the device trace (``obs.trace``).
``api.Session`` builds a ``Telemetry`` from its config's
``TelemetrySpec`` and ``install``s it for the session's lifetime;
tests use the ``use(tel)`` context manager.  Only ONE telemetry is
current per process at a time (sessions that overlap share the last
installed one — spans say which session via the root span attrs).
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

from repro.obs.export import (chrome_trace, dump_chrome_trace,
                              prometheus_text)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry)
from repro.obs.trace import (NOOP_SPAN, FakeClock, NoopSpan, ProfilerSpan,
                             Tracer, profiling)


class Telemetry:
    """One session's telemetry: enabled flag + tracer + metrics."""

    __slots__ = ("enabled", "tracer", "metrics")

    def __init__(self, enabled: bool = True, clock=None,
                 capacity: int = 65536):
        self.enabled = enabled
        self.tracer = Tracer(clock=clock, capacity=capacity)
        self.metrics = MetricsRegistry()
        # every completed span also feeds a per-name duration histogram
        # (``ops.spmm`` span -> ``ops.spmm_ms`` metric)
        self.tracer.on_record = self._span_metric

    def _span_metric(self, name, dur_ns, _attrs) -> None:
        self.metrics.histogram(name + "_ms").observe(dur_ns / 1e6)

    # -- spans ----------------------------------------------------------
    def span(self, name: str, attrs: Optional[dict] = None):
        if not self.enabled:
            # NOOP_SPAN, or a falsy annotation while a profiler traces
            return ProfilerSpan(name) if profiling() else NOOP_SPAN
        return self.tracer.span(name, attrs)

    # -- metrics --------------------------------------------------------
    def add(self, name: str, v: float = 1.0) -> None:
        if self.enabled:
            self.metrics.counter(name).inc(v)

    def gauge(self, name: str, v: float) -> None:
        if self.enabled:
            self.metrics.gauge(name).set(v)

    def observe(self, name: str, v: float) -> None:
        if self.enabled:
            self.metrics.histogram(name).observe(v)

    def now_ns(self) -> int:
        return self.tracer.clock()

    def clear(self) -> None:
        self.tracer.clear()
        self.metrics.clear()


DISABLED = Telemetry(enabled=False, capacity=1)
_CURRENT: Telemetry = DISABLED


def current() -> Telemetry:
    return _CURRENT


def enabled() -> bool:
    return _CURRENT.enabled


def install(tel: Optional[Telemetry]) -> Telemetry:
    """Make ``tel`` the process-current telemetry (None -> the disabled
    default).  Returns the previous one so callers can restore it."""
    global _CURRENT
    prev = _CURRENT
    _CURRENT = tel if tel is not None else DISABLED
    return prev


@contextmanager
def use(tel: Optional[Telemetry]):
    """Scoped ``install`` (tests, benches)."""
    prev = install(tel)
    try:
        yield tel
    finally:
        install(prev)


# -- module-level hot-path helpers (single attribute check, zero
#    allocation when disabled and no profiler trace runs) ---------------

def span(name: str, attrs: Optional[dict] = None):
    tel = _CURRENT
    if not tel.enabled:
        return ProfilerSpan(name) if profiling() else NOOP_SPAN
    return tel.tracer.span(name, attrs)


def add(name: str, v: float = 1.0) -> None:
    tel = _CURRENT
    if tel.enabled:
        tel.metrics.counter(name).inc(v)


def gauge(name: str, v: float) -> None:
    tel = _CURRENT
    if tel.enabled:
        tel.metrics.gauge(name).set(v)


def observe(name: str, v: float) -> None:
    tel = _CURRENT
    if tel.enabled:
        tel.metrics.histogram(name).observe(v)


__all__ = ["Telemetry", "Tracer", "FakeClock", "MetricsRegistry",
           "Counter", "Gauge", "Histogram", "NoopSpan", "NOOP_SPAN",
           "ProfilerSpan", "profiling",
           "DISABLED", "chrome_trace", "dump_chrome_trace",
           "prometheus_text", "current", "enabled", "install", "use",
           "span", "add", "gauge", "observe"]
