"""Unified telemetry: metrics registry + trace spans.

The production observability layer the reference stack never had (its
StatsListener feeds a dashboard; it cannot answer "which 1% of steps are
slow and is it compute, ETL, or comms"). Two dependency-free halves:

- **Metrics** (monitor/metrics.py): thread-safe labeled counters /
  gauges / fixed-bucket histograms in a process-global registry,
  exposed as Prometheus text at ``GET /metrics`` on UIServer and as
  `dump()` / `summary()` dicts for tools and tests.
- **Tracing** (monitor/trace.py): `span("name", **attrs)` context
  manager — zero-cost while disabled — producing thread-aware Chrome
  trace-event JSON loadable in Perfetto / chrome://tracing, with
  optional mirroring into jax.profiler trace annotations.
- **Compiled-program ledger** (monitor/xla.py, `monitor.xla.*`): every
  hot-path XLA program's fingerprint, compile time, cost_analysis FLOPs
  / bytes accessed, and memory_analysis HBM breakdown — `xla_*` metric
  families, live `train_mfu_pct` / `serving_mfu_pct` gauges, and a JSON
  perf-ledger artifact gated by tools/perf_report.py. Zero-cost while
  disabled (the default), same contract as `span()`.
- **Time-series + SLO engine** (monitor/timeseries.py, monitor/slo.py):
  a bounded ring of registry snapshots turning counters/histograms into
  windowed rates and percentiles, and declarative SLO objectives
  evaluated as multi-window burn-rate alerts whose firings call
  `flight.trip()` — served at ``GET /v1/slo`` / ``GET /v1/timeseries``
  by the serving stack. Zero-cost while disabled, same contract.

Everything in-tree records into the default registry: the fit loops
(step wall time, host sync, examples/sec, score), the async ETL pipeline
(queue depth, fetch wait), the socket transport (bytes, latency,
reconnects, drops), ResilientTrainer (checkpoint IO, retries, NaN skips,
resumes, preemptions), and ParallelInference (request latency, batch
size, queue depth, timeouts). docs/OBSERVABILITY.md catalogs the metric
names and walks through a trace capture.

Quickstart:

    from deeplearning4j_tpu import monitor
    monitor.enable_tracing()
    net.fit(data, epochs=1)                   # instrumented end to end
    monitor.save_trace("/tmp/fit_trace.json") # load in ui.perfetto.dev
    print(monitor.prometheus_text())          # or scrape UIServer /metrics
"""
from deeplearning4j_tpu.monitor.metrics import (
    DEFAULT_BUCKETS, REGISTRY, Counter, Gauge, Histogram, MetricsRegistry,
    counter, dump, gauge, histogram, openmetrics_text, prometheus_text,
    summary,
)
from deeplearning4j_tpu.monitor.trace import (
    TRACEPARENT_HEADER, TraceContext, add_span, bind_context, clear_trace,
    current_context, disable_tracing, enable_tracing, instant,
    mint_context, parse_traceparent, save_trace, span, thread_names,
    trace_events, tracing_enabled,
)
# the compiled-program ledger (xla_* families, MFU gauges, perf ledger
# JSON) — namespaced as monitor.xla; see docs/OBSERVABILITY.md
from deeplearning4j_tpu.monitor import xla  # noqa: E402,F401
# the per-request flight recorder + SLO postmortems — namespaced as
# monitor.flight; see docs/OBSERVABILITY.md "Tracing a single request"
from deeplearning4j_tpu.monitor import flight  # noqa: E402,F401
# the in-process metrics time-series ring (windowed rates/percentiles)
# — namespaced as monitor.timeseries; docs/OBSERVABILITY.md "SLOs and
# burn-rate alerting"
from deeplearning4j_tpu.monitor import timeseries  # noqa: E402,F401
# the SLO engine (objectives, multi-window burn-rate alerts, fleet
# verdicts on GET /v1/slo) — namespaced as monitor.slo
from deeplearning4j_tpu.monitor import slo  # noqa: E402,F401
# the goodput ledger (wall-clock attribution per fit, train_goodput_pct,
# step-time anomaly trips) — namespaced as monitor.goodput;
# docs/OBSERVABILITY.md "Goodput accounting"
from deeplearning4j_tpu.monitor import goodput  # noqa: E402,F401

__all__ = [
    "DEFAULT_BUCKETS", "REGISTRY", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "counter", "dump", "gauge", "histogram",
    "openmetrics_text", "prometheus_text", "summary",
    "TRACEPARENT_HEADER", "TraceContext", "add_span", "bind_context",
    "clear_trace", "current_context", "disable_tracing", "enable_tracing",
    "instant", "mint_context", "parse_traceparent", "save_trace", "span",
    "thread_names", "trace_events", "tracing_enabled",
    "xla", "flight", "timeseries", "slo", "goodput",
]
