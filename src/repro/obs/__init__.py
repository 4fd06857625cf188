"""Unified observability: tracing spans, metrics registry, traffic audit.

One subsystem, three instruments, shared by every layer
(execute / autotune / serve / trajectory / dist):

* **Spans & events** (:mod:`repro.obs.trace`) — ``obs.trace(name,
  **attrs)`` context-manager spans and ``obs.event(...)`` instants in a
  bounded ring buffer; off by default, exportable as JSONL or Chrome
  ``trace_event`` JSON (``obs.export_chrome_trace`` /
  ``tools/trace_view.py``). While a ``jax.profiler`` session records,
  the same spans land in its trace, on its clock, beside the device ops
  that the engine names by layer (``jax.named_scope``: ``bin``, ``ghost``,
  ``pair``, ``scatter_back``, ...; ARCHITECTURE.md, Observability).
* **Metrics registry** (:mod:`repro.obs.metrics`) — labeled counters /
  gauges / histograms behind the historical counter shims
  (``core.api.dispatch_count`` etc.), rendered by ``obs.render_prom()``
  / ``obs.snapshot()``.
* **Traffic audit** (:mod:`repro.obs.audit`) — the model-vs-measured
  "model drift" metric the autotuner records with every prune decision.

``trace``/``metrics`` import nothing from the library, so ``core.api``
can depend on them without cycles; ``audit`` (which imports ``core``) is
loaded lazily on first attribute access (PEP 562).
"""

from __future__ import annotations

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, registry,
                      render_prom, snapshot)
from .trace import (DEFAULT_CAPACITY, active, chrome_events, clear,
                    device_scope, disable, enable, event,
                    export_chrome_trace, export_jsonl, spans, stats, trace,
                    tracing, tracing_enabled)

__all__ = [
    # trace
    "trace", "event", "device_scope", "enable", "disable", "tracing",
    "tracing_enabled", "active", "spans", "clear", "stats", "export_jsonl",
    "export_chrome_trace", "chrome_events", "DEFAULT_CAPACITY",
    # metrics
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "render_prom", "snapshot",
    # lazy: audit
    "MeasuredTraffic", "measured_traffic", "neighbor_pair_count",
    "model_drift", "audit_candidate",
]

_LAZY = {
    "MeasuredTraffic": "audit", "measured_traffic": "audit",
    "neighbor_pair_count": "audit", "model_drift": "audit",
    "audit_candidate": "audit",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib
    value = getattr(importlib.import_module(f".{mod}", __name__), name)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
