"""repro.obs — dependency-free serving telemetry (DESIGN.md §12).

Three primitives, one bundle:

* :class:`MetricsRegistry` — labeled counters/gauges/histograms; the
  process-wide :data:`REGISTRY` is the single source of truth that the
  ``ServeStats``/``BucketStats``/``ShardStats`` views, the Prometheus/
  JSON exporters and the benches all read.
* :class:`Trace`/:class:`TraceLog`/:class:`HeadSampler` — per-request
  span trees, head-sampled with an always-sample-on-slow override.
* :class:`EventLog` — structured ring + JSONL sink for discrete state
  changes (swaps, drift, sheds, requeues, quant fallbacks, covis).

:class:`Telemetry` bundles sampler + trace ring + event log (the
registry defaults to the shared :data:`REGISTRY`).  ``Telemetry.off()``
builds the disabled variant used by the instrumentation-overhead gate:
sampling rate 0, events suppressed — the registry stays live because it
*is* the serving stats.  ``Telemetry.timeline`` (off by default, can be
flipped on a live server) puts the batcher's ``serve.*`` stages on the JAX
profiler's timeline as host events (:meth:`Telemetry.span`).
"""

import contextlib

from .events import EventLog
from .locks import (LOCK_RANKS, LockOrderError, OrderedLock, held_locks,
                    lock_check_enabled, make_lock)
from .metrics import (DEFAULT_LATENCY_BOUNDS_MS, Counter, Gauge, Histogram,
                      MetricsRegistry, REGISTRY, log_bounds,
                      next_instance_id)
from .export import json_snapshot, parse_prometheus, prometheus_text
from .profile import (CompileCapture, CompileRecord, aot_cost,
                      disable_profile, enable_profile, normalize_cost,
                      profiled)
from .timing import Stopwatch, monotonic
from .trace import (ASYNC_STAGES, BUILD_STAGES, SYNC_STAGES, HeadSampler,
                    Span, Trace, TraceLog)
from .views import StatsView

__all__ = [
    "ASYNC_STAGES", "BUILD_STAGES", "SYNC_STAGES",
    "CompileCapture", "CompileRecord", "Counter",
    "DEFAULT_LATENCY_BOUNDS_MS",
    "EventLog", "Gauge", "HeadSampler", "Histogram", "LOCK_RANKS",
    "LockOrderError", "MetricsRegistry", "OrderedLock",
    "REGISTRY", "Span", "StatsView", "Stopwatch", "Telemetry", "Trace",
    "TraceLog",
    "aot_cost", "disable_profile", "enable_profile", "held_locks",
    "json_snapshot", "lock_check_enabled", "log_bounds", "make_lock",
    "monotonic", "next_instance_id", "normalize_cost",
    "parse_prometheus", "profiled", "prometheus_text",
]


_NO_SPAN = contextlib.nullcontext()


class Telemetry:
    """Sampler + trace ring + event log over a shared metrics registry.

    ``timeline``: when True, :meth:`span` records each serving stage as a
    host event on the JAX profiler's clock, in the same trace as the
    device's operations (an operator sets it before
    ``jax.profiler.start_trace`` against a live server).  Off, a span
    site is an attribute test and a shared no-op context.
    """

    def __init__(self, registry: MetricsRegistry = None,
                 sample_rate: float = 0.05, slow_ms: float = 50.0,
                 events: EventLog = None, span_capacity: int = 1024,
                 events_path: str = None):
        self.registry = REGISTRY if registry is None else registry
        self.sampler = HeadSampler(rate=sample_rate, slow_ms=slow_ms)
        self.spans = TraceLog(capacity=span_capacity)
        self.events = EventLog(path=events_path) if events is None \
            else events
        self.timeline = False

    @classmethod
    def off(cls, registry: MetricsRegistry = None) -> "Telemetry":
        """Spans and events disabled; registry recording stays on."""
        t = cls(registry=registry, sample_rate=0.0, slow_ms=0.0)
        t.events.enabled = False
        return t

    def span(self, name: str):
        """Context manager: a profiler host event ``name`` while
        ``timeline`` is on, else nothing."""
        if not self.timeline:
            return _NO_SPAN
        from jax.profiler import TraceAnnotation  # lazy: obs stays jax-free
        return TraceAnnotation(name)

    @property
    def enabled(self) -> bool:
        return (self.sampler.rate > 0.0 or self.sampler.slow_ms > 0.0
                or self.events.enabled)
