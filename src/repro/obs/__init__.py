"""Observability layer: host spans on the profiler's clock (``span``),
lifecycle tracing (Chrome-trace/Perfetto export), a labeled metrics
registry with Prometheus/JSON exposition, and estimator-drift probes over
the engine's calibration loop.

Import discipline: the engine and the runners import ``repro.obs.span``,
and ``repro.obs.probes``/``repro.obs.trace`` import the engine, so this
package init imports nothing eagerly: each name below loads its module at
first use. Nothing here may import ``repro.serving`` at module level —
``repro.serving.events`` imports ``repro.obs.metrics``. Probes take the
bus duck-typed instead.
"""
import importlib

_EXPORTS = {
    "metrics": ("Counter", "FRACTION_BUCKETS", "Gauge", "Histogram",
                "ITER_BUCKETS", "LATENCY_BUCKETS", "MetricsRegistry",
                "REL_ERR_BUCKETS", "parse_prometheus"),
    "probes": ("EngineProbe", "ServiceMetrics", "instrument",
               "instrument_engine"),
    "trace": ("Tracer",),
    "spans": ("span", "spanned", "step_span"),
}
_MODULE_OF = {name: mod for mod, names in _EXPORTS.items() for name in names}

__all__ = sorted(_MODULE_OF)


def __getattr__(name):
    mod = _MODULE_OF.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f"{__name__}.{mod}"), name)
