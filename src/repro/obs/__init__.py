"""Runtime telemetry for the mining stack (metrics, spans, retraces).

The paper's claims are quantitative; the reproduction's self-measurement
was one-shot bench scripts over host walls that overlap under async
dispatch.  This package is the in-process substrate those scripts (and
the rebalancer, and CI gates) read instead:

  * ``metrics`` — a registry of counters / gauges / exponential-bucket
    histograms with labels; near-zero-cost no-op when disabled;
  * ``trace``   — begin/finish span trees with per-shard tracks,
    exported as JSON or Chrome-trace format (chrome://tracing,
    Perfetto), optionally bridged to ``jax.profiler.TraceAnnotation``;
  * ``count_dispatch`` — the ``kernel.dispatch`` counter every
    kernel-or-reference dispatch (pairgen, delta, fused) increments with
    the implementation it ran, so no fallback to the reference is silent;
  * ``telemetry`` — the per-session bundle of both, plus the
    :class:`RetraceTracker` that turns jax's compiled-variant counts
    into a per-tick ``jit.retraces`` counter (the O(log) recompile
    invariant, finally measured).

Invariant: telemetry reads host-side scalars and timestamps only — it
never changes what is mined, byte for byte, on or off
(tests/test_obs.py proves it across every planner engine).
"""
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, NOOP_METRIC, NOOP_REGISTRY,
                               NoopRegistry, count_dispatch)
from repro.obs.telemetry import (NOOP, RetraceTracker,  # noqa: F401
                                 Telemetry, default_hot_functions,
                                 jit_cache_size)
from repro.obs.trace import (NOOP_SPAN, NOOP_TRACER,  # noqa: F401
                             NoopTracer, Span, SpanTracer)
