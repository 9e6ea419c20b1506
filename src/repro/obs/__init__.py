"""Unified observability: tracing + metrics for serve and train.

SEBS's headline claims are *measured* claims — fewer updates and fewer
syncs at matched generalization — so the repo routes all its accounting
through one instrumentation layer instead of per-subsystem stats dicts:

- :mod:`repro.obs.trace` — a span/event :class:`~repro.obs.trace.Tracer`
  (ring buffer, injected monotonic clock, Chrome ``trace_event``
  export, optional ``jax.profiler`` bracketing). Engines record
  per-request lifecycle spans (enqueue → admit → prefill_done →
  first_token → done) and per-tick spans carrying pool occupancy, queue
  depth, prefix hits, admission stage, and seam-transfer bytes; trainers
  record per-update spans carrying stage, batch size and loss, with the
  update's phases (data, dispatch, wait) nested inside, and GNS.
- :mod:`repro.obs.metrics` — a counter/gauge/histogram
  :class:`~repro.obs.metrics.MetricsRegistry` with labeled series and
  fixed-bucket percentiles (p50/p99 in O(buckets) memory).

Everything is stdlib-only and deterministic by construction: no ambient
clock reads (the injected ``clock`` seam keeps lint rule R103 clean in
instrumented code), no randomness, sorted serialization. Disabled
instruments (:data:`~repro.obs.trace.NULL_TRACER`,
:data:`~repro.obs.metrics.NULL_METRICS`) are shared no-op singletons, so
an uninstrumented run records zero events and pays one attribute load per
site — and tracing must never change tokens, losses, or compile counts
(the compile-bucket-neutral guarantee, asserted in ``tests/test_obs.py``
and audited at run() end by
:func:`repro.analysis.sanitize.audit_tracer`).

Consumers: ``launch/serve.py --trace/--metrics``, ``launch/train.py
--trace/--metrics``, ``benchmarks/serve_throughput.py`` (SLO percentiles
derive from tracer spans via
:func:`~repro.obs.metrics.nearest_rank`), and ``tools/trace_view.py``
(per-phase p50/p99 per request class, per-stage update timing).
"""
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL_METRICS,
    nearest_rank,
    time_buckets,
)
from repro.obs.trace import NULL_TRACER, PHASES, Tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_METRICS",
    "NULL_TRACER",
    "PHASES",
    "Tracer",
    "nearest_rank",
    "time_buckets",
]
