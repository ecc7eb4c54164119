"""repro.obs — the federation telemetry plane.

Dependency-light (numpy + stdlib, with ``jax.profiler`` annotations)
observability for the whole stack:

  * :mod:`repro.obs.metrics` — thread-safe counters / gauges /
    fixed-bucket histograms in a :class:`MetricsRegistry`, with a
    Prometheus text exposition and a plain-dict snapshot;
  * :mod:`repro.obs.tracing` — monotonic-clock spans on a bounded ring
    buffer with parent/child nesting and a JSONL exporter, each also a
    ``jax.profiler.TraceAnnotation`` so it shows in any active profile;
  * :mod:`repro.obs.telemetry` — the :class:`Telemetry` facade every
    constructor accepts (``telemetry=None`` → the shared
    :data:`NULL` no-op);
  * :mod:`repro.obs.fedmetrics` — :class:`FedObserver`, per-round
    paper-level signals (participation, scheme weight mass, live
    Theorem 3.1 bound terms).

See docs/observability.md for the metric catalog and span inventory.
"""
from .metrics import (DEFAULT_BUCKETS, Counter, Family, Gauge, Histogram,
                      MetricsRegistry)
from .tracing import Span, Tracer
from .telemetry import NULL, NullTelemetry, Telemetry, resolve
from .fedmetrics import FedObserver, scheme_mass

__all__ = [
    "DEFAULT_BUCKETS", "Counter", "Family", "Gauge", "Histogram",
    "MetricsRegistry", "Span", "Tracer", "NULL", "NullTelemetry",
    "Telemetry", "resolve", "FedObserver", "scheme_mass",
]
