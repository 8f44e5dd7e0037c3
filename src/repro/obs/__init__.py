"""Observability layer: metrics registry, span tracer, profiler hooks.

One subsystem (DESIGN.md §17) behind the serving stack's three
measurement questions:

* **how much / how often** — :class:`MetricsRegistry` with
  :class:`Counter` / :class:`Gauge` / :class:`Histogram`, labeled by
  tenant / cache kind / phase; snapshot/delta replaces the old
  hand-merged metrics dicts.
* **when / in what order** — :class:`Tracer`, a ring-buffered span
  collector timestamped exclusively through the engine's injectable
  ``clock=`` seam, exporting Chrome/Perfetto ``trace_event`` JSON.
* **what is the device doing** — :mod:`.profile`, optional
  ``jax.profiler`` wrappers around the jitted entry points.
"""
from .metrics import (DEFAULT_MS_EDGES, Counter, Gauge, Histogram,
                      MetricGroup, MetricsRegistry, dist_ms,
                      never_nan_percentile)
from .profile import annotation, profile_session
from .trace import (PID_ENGINE, PID_REQUESTS, Tracer, check_span_nesting,
                    validate_trace)

__all__ = [
    "DEFAULT_MS_EDGES", "Counter", "Gauge", "Histogram", "MetricGroup",
    "MetricsRegistry", "dist_ms", "never_nan_percentile",
    "annotation", "profile_session",
    "PID_ENGINE", "PID_REQUESTS", "Tracer", "check_span_nesting",
    "validate_trace",
]
