"""The `Telemetry` facade and its no-op null twin.

One ``Telemetry`` object threads through every constructor in the
federation stack (engine → scheduler → service → checkpoint → faults).
It owns a :class:`~repro.obs.metrics.MetricsRegistry` and a
:class:`~repro.obs.tracing.Tracer`, and wires them together: every
finished span's duration is also observed into the
``span_seconds{name=...}`` histogram family, so the prom exposition and
the JSONL trace describe the same events.

The default everywhere is :data:`NULL`, a ``NullTelemetry`` whose
metrics are shared no-op singletons and whose ``span()`` returns only a
``jax.profiler.TraceAnnotation`` — no ring, no clock read, no lock — so
the spans still name the host's work in any active JAX profile, and
with no profiler running each costs one inactive TraceMe.  Tier-1 tests
pin that a null-telemetry run is bit-identical to an uninstrumented one
and triggers zero extra recompiles.

Constructors accept ``telemetry=None`` and call :func:`resolve` so the
null default never needs importing at call sites.
"""
from __future__ import annotations

import json
import time

from jax.profiler import TraceAnnotation

from .metrics import DEFAULT_BUCKETS, MetricsRegistry
from .tracing import Tracer


class Telemetry:
    """Live telemetry: metrics registry + span tracer + sinks."""

    enabled = True

    def __init__(self, span_capacity: int = 4096):
        self.registry = MetricsRegistry()
        self._span_seconds = self.registry.histogram(
            "span_seconds", "wall time of traced spans by name",
            labelnames=("name",), buckets=DEFAULT_BUCKETS)
        self.tracer = Tracer(
            capacity=span_capacity,
            on_finish=lambda name, dur:
                self._span_seconds.labels(name).observe(dur))

    # -- metric / span creation (delegates) -----------------------------------
    def counter(self, name: str, help: str = "", labelnames=()):
        return self.registry.counter(name, help, labelnames)

    def gauge(self, name: str, help: str = "", labelnames=()):
        return self.registry.gauge(name, help, labelnames)

    def histogram(self, name: str, help: str = "", labelnames=(),
                  buckets=DEFAULT_BUCKETS):
        return self.registry.histogram(name, help, labelnames, buckets)

    def span(self, name: str, **attrs):
        return self.tracer.span(name, **attrs)

    # -- sinks ----------------------------------------------------------------
    def render_prom(self) -> str:
        return self.registry.render_prom()

    def write_prom(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.render_prom())

    def export_spans(self, path: str, append: bool = True) -> int:
        """Drain the span ring buffer to a JSONL file."""
        return self.tracer.export_jsonl(path, append=append)

    def dump_jsonl(self, path: str, append: bool = True) -> int:
        """One-stop JSONL sink: buffered spans (``{"kind": "span", ...}``)
        followed by a metrics snapshot (``{"kind": "metric", ...}`` per
        family).  Returns the number of lines written."""
        n = 0
        with open(path, "a" if append else "w") as f:
            for rec in self.tracer.drain():
                f.write(json.dumps({"kind": "span", **rec}) + "\n")
                n += 1
            t = time.monotonic()
            for name, fam in self.registry.snapshot().items():
                f.write(json.dumps(
                    {"kind": "metric", "t": t, "name": name, **fam}) + "\n")
                n += 1
        return n


class _NullMetric:
    """Absorbs every metric call; ``labels()`` returns itself so labeled
    and unlabeled call shapes both no-op."""
    __slots__ = ()

    def inc(self, v: float = 1.0) -> None: pass
    def dec(self, v: float = 1.0) -> None: pass
    def set(self, v: float) -> None: pass
    def observe(self, v: float) -> None: pass
    def observe_many(self, vs) -> None: pass
    def labels(self, *a, **kw): return self
    value = 0.0
    count = 0
    sum = 0.0
    def buckets(self): return []


_NULL_METRIC = _NullMetric()


class NullTelemetry:
    """Telemetry that does nothing — the default for every constructor.

    Shares the ``Telemetry`` call surface so instrumented code never
    branches on enablement; the few sites that must branch (e.g. to skip
    building an attrs dict) check ``telemetry.enabled``.
    """

    enabled = False
    registry = None
    tracer = None

    def counter(self, name: str, help: str = "", labelnames=()):
        return _NULL_METRIC

    def gauge(self, name: str, help: str = "", labelnames=()):
        return _NULL_METRIC

    def histogram(self, name: str, help: str = "", labelnames=(),
                  buckets=DEFAULT_BUCKETS):
        return _NULL_METRIC

    def span(self, name: str, **attrs):
        # profile-only: the span shows in an active JAX profile, and
        # nothing is recorded here
        return TraceAnnotation(name, **attrs)

    def render_prom(self) -> str:
        return ""

    def write_prom(self, path: str) -> None:
        pass

    def export_spans(self, path: str, append: bool = True) -> int:
        return 0

    def dump_jsonl(self, path: str, append: bool = True) -> int:
        return 0


NULL = NullTelemetry()


def resolve(telemetry) -> "Telemetry | NullTelemetry":
    """``None`` → the shared null singleton; anything else passes through."""
    return NULL if telemetry is None else telemetry
