"""repro.obs — unified observability: metrics registry, span tracing, and
measured-vs-analytical communication-volume accounting.

See docs/OBSERVABILITY.md for the contract and metric name table.
"""

from repro.obs.registry import (  # noqa: F401
    INTERTOKEN_BUCKETS, TTFT_BUCKETS, Counter, Gauge, Histogram, Registry,
    current_scope, global_registry, merge_prometheus_text, parse_prometheus,
    parse_prometheus_families, scope,
)
from repro.obs.tracing import NULL_TRACER, Tracer  # noqa: F401
from repro.obs.host import HostWatch, watch_host  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Tracer", "NULL_TRACER",
    "HostWatch", "watch_host",
    "TTFT_BUCKETS", "INTERTOKEN_BUCKETS", "current_scope", "scope",
    "global_registry", "parse_prometheus", "parse_prometheus_families",
    "merge_prometheus_text",
]
