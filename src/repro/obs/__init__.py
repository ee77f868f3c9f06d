"""Observability plane: tracing + metrics + trace analyzer
(DESIGN.md §Observability).

Instrumentation sites import the tracing facade as::

    from repro.obs import trace as otrace

and call ``otrace.span(...)`` / ``otrace.complete(...)`` — near-zero
cost until ``otrace.install()`` activates a tracer (``span()`` also
annotates a recording JAX profiler session). The obs-discipline
checker (``repro-check``) keys off the ``otrace`` alias; keep it.
"""
from repro.obs.metrics import MetricsRegistry, metrics
from repro.obs.trace import Tracer

__all__ = ["MetricsRegistry", "Tracer", "metrics"]
