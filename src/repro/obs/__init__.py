"""Observability layer: tracing + metrics for the search service.

Importable without JAX (stdlib only), threaded through every serving
layer — SearchClient / SchedulerCore / ArenaPool / ExpansionEngine /
CompactionSession all accept an optional tracer + registry and default
to the shared no-op instances, so the disabled path costs a handful of
no-op calls per superstep.  Measured on one TPU v5e (the Pong cells of
perfbench/, 56,000-node arena): tracer and metrics on, with their
fences and without the profiler, cost 3% of committed moves per second
in batch self-play and 6% in single-caller analysis; off, the service
reads as it did before the spans were added.

  obs.trace    Tracer — nested spans (per-superstep phases: select /
               expand / simulate / backup / compact-gather /
               compact-scatter; the fused dispatch's admit /
               fused-upload / fused-run / fused-readback; a move's
               move-commit with commit-snapshot / commit-emit /
               commit-reroot / commit-write; overlap-wait-env /
               overlap-wait-device),
               with explicit block_until_ready fencing when tracing is
               live so device time is attributed honestly, + async
               request-lifecycle spans (submit -> supersteps -> result /
               cancel / evict), recorded into a lock-free drop-oldest
               ring and exported as Chrome-trace / Perfetto JSON
               (``Tracer.export()`` -> open at ui.perfetto.dev).  Each
               complete span is also a ``jax.profiler.TraceAnnotation``,
               so a profile's host plane shows it on the device's clock.
  obs.metrics  MetricsRegistry — labelled counters / gauges /
               histograms (queue depth, smoothed load, fused-batch
               rows, admission wait, evictions, retirements, expired
               results, expansion batch calls, compaction decisions,
               committed moves, host<->device bytes by site and
               direction) with a Prometheus-exposition-format text
               snapshot.

Entry points: ``SearchClient(trace=True, metrics=True)`` then
``client.trace_export("trace.json")`` / ``client.metrics()``; or build
a ``Tracer``/``MetricsRegistry`` yourself and hand the same instances to
several components.  Bit-identity of traced vs untraced runs across
every executor is pinned in tests/test_executor_matrix.py.
"""

from repro.obs.metrics import (
    NULL_METRIC, NULL_REGISTRY, Counter, Gauge, Histogram, MetricsRegistry,
    NullRegistry,
)
from repro.obs.trace import NULL_TRACER, NullTracer, Span, Tracer

__all__ = [
    "Tracer", "NullTracer", "Span", "NULL_TRACER",
    "MetricsRegistry", "NullRegistry", "Counter", "Gauge", "Histogram",
    "NULL_METRIC", "NULL_REGISTRY",
]
