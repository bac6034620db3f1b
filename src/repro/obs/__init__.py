"""Unified observability layer: span tracing, mergeable metrics, profiling.

Small pieces shared by serve / train / stream:

- :mod:`repro.obs.clock` — the single monotonic clock every duration in
  the repo is measured on (``time.time()`` is reserved for checkpoint
  metadata timestamps, where wall-clock meaning matters more than
  monotonicity).
- :mod:`repro.obs.trace` — a host-side span tracer with explicit clock
  injection and a ring-buffered event store, exporting Chrome trace
  event / Perfetto JSON.  ``NULL_TRACER`` is the default everywhere, so
  untraced hot paths pay only a no-op attribute call.
- :mod:`repro.obs.metrics` — typed counters / gauges / histograms whose
  snapshots merge associatively (the same discipline
  ``StreamingAUC`` / ``StreamingLogLoss`` follow), superseding the
  ad-hoc counter dicts in the scheduler, page pool and stream windows.
- :mod:`repro.obs.profile` — the ``jax.profiler`` trace hook; a trace
  that was asked for and cannot start raises. A ``jax_annotate`` tracer
  puts every span on the profiler's host line.
- :mod:`repro.obs.compiles` — the compile watch: every XLA compile as a
  ``jit.compile`` span and ``jit.compiles`` / ``jit.compile_s`` counters
  of the trainer and the scheduler that watch it.

See ``docs/observability.md`` for the span model, naming scheme and the
overhead contract (zero new device syncs on the serving hot path).
"""
from repro.obs.clock import monotonic, wall
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               merge_snapshots)
from repro.obs.trace import (NULL_TRACER, NullTracer, SpanTracer,
                             validate_chrome_trace)

__all__ = [
    "monotonic", "wall",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "merge_snapshots",
    "SpanTracer", "NullTracer", "NULL_TRACER", "validate_chrome_trace",
]
