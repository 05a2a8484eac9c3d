"""``repro.obs`` — serving observability: metrics, spans, load harness.

The engine's correctness and memory contracts are machine-checked
(``repro.analysis``); this package is the *runtime* scoreboard on top:

* :mod:`repro.obs.clock` — the one monotonic clock (``now`` =
  ``time.perf_counter``) every span, bench, and serving loop shares;
* :mod:`repro.obs.registry` — typed counters/gauges/histograms
  (:class:`MetricsRegistry`) plus :class:`EngineTelemetry`, the reader of
  the engine's device-side per-step metrics vector. Device quantities
  (phase-occupancy over ``t % stride``, middle-skip fires, speculative
  accepted counts) accumulate *inside* the jitted step and reach the
  host only through the serving loop's existing one-step-deferred drain
  — telemetry-on serving still passes the host-sync and donation gates
  (fixture: the ``gqa-paged-tele`` analysis target);
* :mod:`repro.obs.spans` / :mod:`repro.obs.tracefile` — the engine's
  host spans (:class:`span`, one ``jax.profiler.TraceAnnotation`` each,
  kept in memory inside :func:`record_spans`) and per-request lifecycle
  spans (queued → prefill → insert → first token → decode → done) with
  TTFT / ITL / queue-wait percentiles, exported as Chrome-trace JSON for
  Perfetto plus a flat metrics JSON;
* :mod:`repro.obs.loadgen` — the synthetic multi-tenant load harness
  (Zipf-shared prefixes, bursty Poisson arrivals) behind
  ``benchmarks/serving_trace_bench.py`` and ``BENCH_serving_trace.json``.

Metric names, units, the span schema, and the deferred-drain rule are
documented in ``docs/OBSERVABILITY.md``.
"""

from repro.obs.clock import now
from repro.obs.registry import (Counter, EngineTelemetry, Gauge, Histogram,
                                MetricsRegistry, percentile)
from repro.obs.spans import (RequestTrace, SpanRecord, SpanRecorder, Tracer,
                             record_spans, span)
from repro.obs.tracefile import chrome_trace, write_metrics, write_trace
from repro.obs.loadgen import LoadRequest, LoadResult, make_trace, run_load

__all__ = [
    "Counter", "EngineTelemetry", "Gauge", "Histogram", "LoadRequest",
    "LoadResult", "MetricsRegistry", "RequestTrace", "SpanRecord",
    "SpanRecorder", "Tracer", "chrome_trace", "make_trace", "now",
    "percentile", "record_spans", "run_load", "span", "write_metrics",
    "write_trace",
]
