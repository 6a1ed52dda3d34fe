"""repro.obs — spans, metrics, and trace export for the whole pipeline.

The observability layer the serving/robustness roadmap items build on:

* `trace` — hierarchical span tracer with Chrome-trace JSON export
  (Perfetto-loadable); ambient installation via `tracing`, module-level
  helpers (`span`, `instant`, `counter`, async events) whose spans also
  reach the JAX profiler, as one TraceMe when no tracer is installed;
* `metrics` — thread-safe registry of counters/gauges/bounded histograms
  with bench-schema and Prometheus exports, plus `jax.monitoring` hooks
  for XLA retrace / compile-cache counters;
* `energy` — `EnergyTrack`, bridging `rosa.EnergyLedger` step pricing
  onto the trace timeline as cumulative counter tracks;
* `cli` — ``python -m repro.obs summarize`` trace summarizer.
"""

from repro.obs.energy import EnergyTrack
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    install_jax_hooks,
    registry,
    swap_registry,
)
from repro.obs.trace import (
    Tracer,
    async_begin,
    async_end,
    async_instant,
    counter,
    current_tracer,
    enabled,
    instant,
    span,
    span_on,
    traced,
    tracing,
)

__all__ = [
    "Counter",
    "EnergyTrack",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Tracer",
    "async_begin",
    "async_end",
    "async_instant",
    "counter",
    "current_tracer",
    "enabled",
    "install_jax_hooks",
    "instant",
    "registry",
    "span",
    "span_on",
    "swap_registry",
    "traced",
    "tracing",
]
