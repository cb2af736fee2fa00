"""Nestable spans with a thread-safe in-memory buffer, exported as
Chrome-trace-format JSON (chrome://tracing / Perfetto "traceEvents").

Two span flavors:

  * ``span(name, ...)`` — a synchronous complete event (ph="X") covering
    a with-block: an engine step, a prefill chunk, a train phase. Nesting
    comes for free from Chrome's stack-building on (pid, tid, ts, dur).
  * ``async_begin``/``async_end`` — async events (ph="b"/"e") keyed by an
    id, for spans that outlive any single stack frame: a request's whole
    lifecycle from admission to finish, crossing gateway router →
    scheduler → engine steps.

The disabled tracer (default, and the module-level ``NULL_TRACER``) makes
every call a no-op returning a shared null context manager — the serving
hot loop pays one attribute check per span site, nothing else, so leaving
instrumentation in place costs ~nothing when ``--trace-out`` is absent.

``annotate=True`` additionally wraps each sync span in
``jax.profiler.TraceAnnotation`` so host-side spans line up with device
timelines when a jax profile is captured alongside.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional


class _NullContext:
    """Reusable no-op context manager (allocated once, never per-span)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CONTEXT = _NullContext()


class Tracer:
    def __init__(self, enabled: bool = True, annotate: bool = False):
        self.enabled = enabled
        self.annotate = annotate
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._ids = itertools.count(1)
        # epoch for ts: trace-relative µs keeps numbers small and stable
        self._t0 = time.perf_counter()

    # -- clock ------------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    # -- sync spans -------------------------------------------------------
    def span(self, name: str, cat: str = "repro", **args):
        """Context manager recording a complete event over the block."""
        if not self.enabled:
            return _NULL_CONTEXT
        return self._span(name, cat, args)

    @contextlib.contextmanager
    def _span(self, name: str, cat: str, args: Dict[str, Any]):
        if self.annotate:
            ann = _trace_annotation(name)
        else:
            ann = _NULL_CONTEXT
        ts = self._now_us()
        with ann:
            try:
                yield self
            finally:
                dur = self._now_us() - ts
                self._emit({"name": name, "cat": cat, "ph": "X",
                            "ts": ts, "dur": dur, "pid": os.getpid(),
                            "tid": threading.get_ident(),
                            **({"args": args} if args else {})})

    # -- async (cross-frame) spans ---------------------------------------
    def async_begin(self, name: str, cat: str = "request",
                    span_id: Optional[str] = None, **args) -> Optional[str]:
        """Open an async span; returns the id to pass to ``async_end``."""
        if not self.enabled:
            return None
        sid = span_id if span_id is not None else f"s{next(self._ids)}"
        self._emit({"name": name, "cat": cat, "ph": "b", "id": str(sid),
                    "ts": self._now_us(), "pid": os.getpid(),
                    "tid": threading.get_ident(),
                    **({"args": args} if args else {})})
        return sid

    def async_end(self, name: str, span_id: Optional[str],
                  cat: str = "request", **args) -> None:
        if not self.enabled or span_id is None:
            return
        self._emit({"name": name, "cat": cat, "ph": "e", "id": str(span_id),
                    "ts": self._now_us(), "pid": os.getpid(),
                    "tid": threading.get_ident(),
                    **({"args": args} if args else {})})

    # -- buffer -----------------------------------------------------------
    def _emit(self, event: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append(event)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def extend(self, events: List[Dict[str, Any]]) -> None:
        """Merge events recorded by another tracer (typically shipped back
        from a worker process at drain — each event already carries its
        origin ``pid``, so Chrome/Perfetto lays processes out side by
        side). Events are appended as-is: the two tracers' clocks are
        both process-relative, close enough for eyeballing one serve run.
        Works on a disabled tracer too — the merged trace is still
        dumpable even when local span recording is off."""
        with self._lock:
            self._events.extend(events)

    # -- export -----------------------------------------------------------
    def chrome_trace(self) -> Dict[str, Any]:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def dump(self, path) -> None:
        import pathlib

        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(self.chrome_trace()))


def _trace_annotation(name: str):
    """A jax.profiler.TraceAnnotation when jax is importable, else a no-op
    (the obs layer must not force jax into pure-host tools)."""
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)
    except Exception:
        return _NULL_CONTEXT


#: Shared disabled tracer — the default for every producer, so span sites
#: cost one truthiness check when tracing is off.
NULL_TRACER = Tracer(enabled=False)
