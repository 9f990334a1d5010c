"""Serving observability: tracing, metrics, and quant-drift telemetry.

Three host-side subsystems, all off-by-default-cheap and bounded-memory:

* :mod:`repro.obs.trace`   — the engine's phase spans (``span``: a
  ``jax.profiler`` annotation, and with a ring attached a typed event in a
  bounded ring buffer), exported as Chrome trace-event JSON (loadable in
  Perfetto / chrome://tracing) plus a per-request timeline
  (``trace_request``).
* :mod:`repro.obs.metrics` — Counter/Gauge/Histogram primitives with a central
  registry, Prometheus text exposition, and JSONL snapshots. The engine's
  stats-v8 dict view is derived from this registry.
* :mod:`repro.obs.drift`   — sampled quantization-drift monitor: per-site
  activation saturation rate vs the calibrated clip/OCS grid (paper §5:
  quantization quality depends on the outlier profile seen at calibration).
* :mod:`repro.obs.log`     — per-component ``logging`` loggers for the
  launchers and benches (stdout bench JSON stays on ``print``).
"""
from .log import get_logger, setup_logging
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .trace import SpanEvent, TraceRing, span

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SpanEvent",
    "TraceRing",
    "get_logger",
    "setup_logging",
    "span",
]
