"""Fleet telemetry subsystem: metrics registry, request tracing, exporters,
and host spans on the profiler's clock.

See DESIGN.md §9.  The disabled defaults (:data:`NULL`,
:data:`NULL_TRACER`) make instrumentation zero-cost and keep stream
digests byte-identical telemetry on vs off.
"""

from repro.obs.metric import (Counter, Gauge, Histogram, MetricsRegistry,
                              NullRegistry, NULL)
from repro.obs.trace import (NullTracer, Tracer, NULL_TRACER, SPANS,
                             TERMINAL, span)
from repro.obs.export import dump_all, parse_prometheus, to_prometheus

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NullRegistry",
    "NULL", "Tracer", "NullTracer", "NULL_TRACER", "SPANS", "TERMINAL",
    "span",
    "dump_all", "parse_prometheus", "to_prometheus",
]
