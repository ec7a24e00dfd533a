"""repro.obs — program spans and metrics for the serving, adaptation,
and kernel stack.

* :func:`span`: the one span call of every instrumented site. It measures
  real time, shows on a profiler capture's host plane
  (``jax.profiler.TraceAnnotation``) beside the device's operations,
  accumulates ``span.<name>.calls`` / ``span.<name>.ns`` in the ambient
  metrics registry, and, when a :class:`Tracer` is installed
  (:func:`set_ambient_tracer`, or ``KGService(trace=True)``), is kept in
  memory with its parent and request id and exported as Chrome trace JSON
  (Perfetto-loadable).
* :class:`MetricsRegistry`: central counters/gauges/histograms threaded
  through the facade, executors, stream, migrate, replicate, write, and
  kernel dispatch; snapshot folded into ``KGService.stats()``.
* ``NULL_TRACER`` / ``NULL_METRICS``: inert defaults.
"""
from repro.obs.metrics import (NULL_METRICS, Counter, Gauge, Histogram,
                               MetricsRegistry, NullRegistry, ambient,
                               set_ambient)
from repro.obs.tracer import (NULL_TRACER, NullTracer, Span, Tracer,
                              ambient_tracer, set_ambient_tracer, span)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NullRegistry",
    "NULL_METRICS", "ambient", "set_ambient",
    "Span", "Tracer", "NullTracer", "NULL_TRACER", "span",
    "set_ambient_tracer", "ambient_tracer",
]
