"""Unified observability layer: metrics registry + lifecycle tracing.

``registry`` — thread-safe counters/gauges/fixed-bucket histograms with
JSON (:meth:`MetricsRegistry.snapshot`) and Prometheus-text
(:meth:`MetricsRegistry.render_prometheus`) exposition; ``tracing`` —
span trees following a query from submit to result, with ambient
(contextvar) propagation so library code attaches children without
parameter threading, each span also a ``repro.*`` profiler annotation,
and JAX's compile events land in the executing chunk's tree.
``global_registry()`` holds library-level metrics
(kernel dispatch, store memos, ingest, plan builds); each
:class:`~repro.serving.AnalyticsServer` owns a private registry for its
serving metrics.  See docs/observability.md for the metric catalog and
span model.
"""

from .registry import DEFAULT_BUCKETS, MetricsRegistry, global_registry
from .tracing import (BoundedLog, Span, activate, annotation, compiles_to,
                      current, current_clock, device_scope, plan_stage,
                      readback, readback_seconds, span, span_problems)

__all__ = ["MetricsRegistry", "DEFAULT_BUCKETS", "global_registry",
           "Span", "span", "activate", "current", "current_clock",
           "plan_stage", "readback", "readback_seconds", "annotation",
           "compiles_to", "device_scope", "BoundedLog", "span_problems"]
