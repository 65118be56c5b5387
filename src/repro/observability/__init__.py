"""Unified telemetry layer (DESIGN.md §13): span tracer + metrics registry +
autotune regret auditing.

Four pieces, one import surface:

- :mod:`repro.observability.trace` — spans that always enter a
  ``jax.profiler.TraceAnnotation`` (the device-trace view) and, under
  ``REPRO_TELEMETRY`` (default off), also record into a process-local ring
  buffer with a Chrome-trace/Perfetto exporter (the operator's view). The
  emitting object takes ``telemetry=False`` to drop both.
- :mod:`repro.observability.collector` — one ``gc.callbacks`` hook,
  installed on import: every collection runs inside a ``host/gc``
  annotation and counts on the ``process_gc_*`` counters.
- :mod:`repro.observability.metrics` — counters/gauges/fixed-bucket
  histograms with labeled series and a JSON-lines snapshot exporter;
  ``ServeMetrics`` and the trainer hooks sit on this registry.
- :mod:`repro.observability.regret` — the autotune decision audit:
  predicted-vs-measured per (impl, workload-key), flagged regret, and
  would-have-won alternatives.
"""
from repro.observability import collector
from repro.observability.metrics import (  # noqa: F401
    DEFAULT_TIME_BUCKETS,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from repro.observability.trace import (  # noqa: F401
    ENV_VAR,
    TRACER,
    TraceEvent,
    Tracer,
    enabled,
    export_chrome_trace,
    sanitize_json,
    set_enabled,
    span,
    telemetry,
)

collector.install()

__all__ = [
    "AUDITOR", "Counter", "DEFAULT_TIME_BUCKETS", "ENV_VAR", "Gauge",
    "Histogram", "MetricsRegistry", "REGISTRY", "RegretAuditor",
    "RegretEntry", "TRACER", "TraceEvent", "Tracer", "default_auditor",
    "default_registry", "enabled", "export_chrome_trace", "sanitize_json",
    "set_enabled", "span", "telemetry",
]

# The regret auditor imports repro.autotune (cost model + selector); loading
# it lazily keeps `kernels/ops.py`'s import of this package out of the
# autotune import graph (repro.core's __init__ pulls ops.py in while
# cost_model may still be initializing — see the note in cost_model.py).
_REGRET_NAMES = ("AUDITOR", "RegretAuditor", "RegretEntry",
                 "default_auditor")


def __getattr__(name: str):
    if name in _REGRET_NAMES:
        from repro.observability import regret

        return getattr(regret, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
