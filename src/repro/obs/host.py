"""Process-level host counters: garbage-collector pauses and XLA compiles.

Both come from hooks that are process-wide by nature (``gc.callbacks``,
``jax.monitoring``), so a process has one ``HostWatch``: ``watch_host()``
installs it on first use and returns it. The hooks keep plain sums on the
watch (no lock and no registry call inside a collection, which may start
at any bytecode); ``fold()`` copies them into a registry as

  host_gc_seconds_total, host_gc_collections_total{generation},
  host_compile_seconds_total, host_compiles_total

A compile is one request for an XLA executable that misses JAX's
in-memory caches (``/jax/core/compile/backend_compile_duration``): an XLA
compile, or a load from the persistent compilation cache.

When the watch's tracer annotates (``Tracer(annotate=True)``), each pause
and each compile is also a ``host/gc`` / ``host/compile`` span on the
profiler's clock, so a traced run shows what held the host in a gap.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List, Optional

from repro.obs.registry import Registry, global_registry
from repro.obs.tracing import NULL_TRACER, Tracer, _trace_annotation

GC_SPAN = "host/gc"
COMPILE_SPAN = "host/compile"
#: JAX's duration event around each backend compile (and persistent-cache
#: load); its start is recorded as a scalar event of the same name
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class HostWatch:
    """Sums of GC pauses and compiles since the hooks were installed."""

    def __init__(self):
        self.gc_s = 0.0
        self.gc_collections: Dict[int, int] = {0: 0, 1: 0, 2: 0}
        self.compile_s = 0.0
        self.compiles = 0
        self.tracer: Tracer = NULL_TRACER
        self._gc_t0: Optional[float] = None
        self._gc_span = None
        self._compile_spans = threading.local()
        self._lock = threading.Lock()      # compiles may run on any thread

    def snapshot(self) -> Dict[str, float]:
        """The sums as they stand: ``gc_s``, ``gc_gen<g>`` (collections of
        generation g), ``compile_s``, ``compiles``."""
        return {"gc_s": self.gc_s,
                **{f"gc_gen{g}": n for g, n in self.gc_collections.items()},
                "compile_s": self.compile_s, "compiles": self.compiles}

    def fold(self, registry: Optional[Registry] = None) -> None:
        reg = registry if registry is not None else global_registry()
        reg.counter("host_gc_seconds_total",
                    "Seconds in garbage-collector pauses").set(self.gc_s)
        coll = reg.counter("host_gc_collections_total",
                           "Garbage collections by generation")
        for g, n in list(self.gc_collections.items()):
            coll.set(n, generation=g)
        reg.counter("host_compile_seconds_total",
                    "Seconds in XLA compiles and cache loads").set(
                        self.compile_s)
        reg.counter("host_compiles_total",
                    "XLA compiles and cache loads").set(self.compiles)

    # -- hooks --------------------------------------------------------------
    def _annotating(self) -> bool:
        return self.tracer.enabled and self.tracer.annotate

    def _on_gc(self, phase: str, info: Dict) -> None:
        # collections never overlap, so one open pause at a time
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            if self._annotating():
                self._gc_span = _trace_annotation(GC_SPAN)
                self._gc_span.__enter__()
            return
        if self._gc_t0 is None:          # installed mid-collection
            return
        self.gc_s += time.perf_counter() - self._gc_t0
        self._gc_t0 = None
        g = info.get("generation", 0)
        self.gc_collections[g] = self.gc_collections.get(g, 0) + 1
        if self._gc_span is not None:
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None

    def _open_spans(self) -> List:
        spans = getattr(self._compile_spans, "open", None)
        if spans is None:
            spans = self._compile_spans.open = []
        return spans

    def _on_compile_start(self, event: str, value, **kwargs) -> None:
        if event == COMPILE_EVENT and self._annotating():
            span = _trace_annotation(COMPILE_SPAN)
            span.__enter__()
            self._open_spans().append(span)

    def _on_compile_done(self, event: str, duration: float, **kwargs) -> None:
        if event != COMPILE_EVENT:
            return
        with self._lock:
            self.compile_s += duration
            self.compiles += 1
        spans = self._open_spans()
        if spans:
            spans.pop().__exit__(None, None, None)

    def install(self) -> None:
        gc.callbacks.append(self._on_gc)
        try:
            from jax import monitoring
        except ImportError:              # pure-host tools: GC only
            return
        monitoring.register_scalar_listener(self._on_compile_start)
        monitoring.register_event_duration_secs_listener(
            self._on_compile_done)


_WATCH: Optional[HostWatch] = None
_WATCH_LOCK = threading.Lock()


def watch_host(tracer: Optional[Tracer] = None) -> HostWatch:
    """The process's ``HostWatch``, installed on first use. A ``tracer``
    given here receives the host spans from now on (the last one given
    wins; ``NULL_TRACER`` turns them off)."""
    global _WATCH
    with _WATCH_LOCK:
        if _WATCH is None:
            _WATCH = HostWatch()
            _WATCH.install()
    if tracer is not None:
        _WATCH.tracer = tracer
    return _WATCH
