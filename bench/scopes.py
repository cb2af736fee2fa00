"""Scope paths of device operations, and the program's host spans, from a
profiler trace (``.xplane.pb``).

``trace.py`` reads a trace through ``jax.profiler.ProfileData``, which
gives each event's name and times but not the statistics of its metadata.
Among those, a TPU operation's ``tf_op`` holds the ``op_name`` XLA kept
from the program, ``<jit and named_scope path>/<primitive>:<type>``
(``jit(island)/decode/layers/while/body/closed_call/attn/qkv/dot_general:``);
operations the compiler inserts may carry none. This module reads the
file with a small protobuf wire decoder of its own (the generated
``xplane_pb2`` ships only inside TensorFlow, whose import is heavy), keeps
the part of each device's timeline inside the ``bench/window`` span like
``trace.reduce_file`` does, and keeps every host span whose name starts
with one of ``HOST_PREFIXES``.

Readers find the trace that ``trace.Reduced`` was made from with
``for_reduced``: the run's trace directory is still there while they read.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace

HOST_PREFIXES = ("engine/", "host/", "train/")
#: the program's compute scopes (``serve/step.py``): a device operation
#: under none of them is the compiler's or the layer scan's own
_COMPUTE = re.compile(
    r"(?:^|/)(?:(decode|prefill)/(embed|head)|(attn)(?:/(qkv|kv_write|paged"
    r"|dense|out))?|(mlp|moe|mamba|mlstm|slstm))(?:/|$)")

Op = Tuple[int, int, str, str]        # start ns, end ns, name, scope


# -- protobuf wire format -------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varint and fixed
    fields, bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map(entries: List[bytes]) -> Dict[int, bytes]:
    out = {}
    for e in entries:
        f = dict(_fields(e))
        out[_signed(f.get(1, 0))] = f.get(2, b"")
    return out


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


@dataclasses.dataclass
class _Plane:
    name: str
    lines: List[bytes]
    event_metadata: Dict[int, bytes]
    stat_metadata: Dict[int, bytes]


def _planes(data: bytes) -> Iterator[_Plane]:
    for num, v in _fields(data):
        if num != 1:
            continue
        name, lines, ev, st = "", [], [], []
        for pn, pv in _fields(v):
            if pn == 2:
                name = _str(pv)
            elif pn == 3:
                lines.append(pv)
            elif pn == 4:
                ev.append(pv)
            elif pn == 5:
                st.append(pv)
        yield _Plane(name, lines, _map(ev), _map(st))


def _line(buf: bytes) -> Tuple[str, int, List[bytes]]:
    name, ts, events = "", 0, []
    for n, v in _fields(buf):
        if n == 2:
            name = _str(v)
        elif n == 3:
            ts = _signed(v)
        elif n == 4:
            events.append(v)
    return name, ts, events


def _event(buf: bytes) -> Tuple[int, int, int]:
    """(metadata id, offset ps, duration ps)."""
    f = {n: v for n, v in _fields(buf) if n in (1, 2, 3)}
    return _signed(f.get(1, 0)), _signed(f.get(2, 0)), _signed(f.get(3, 0))


def _metadata(plane: _Plane, want_stat: Optional[str]
              ) -> Dict[int, Tuple[str, str]]:
    """metadata id -> (event name, the ``want_stat`` statistic or '')."""
    stat_names = {k: _str(dict(_fields(v)).get(2, b""))
                  for k, v in plane.stat_metadata.items()}
    out = {}
    for mid, buf in plane.event_metadata.items():
        name, value = "", ""
        for n, v in _fields(buf):
            if n == 2:
                name = _str(v)
            elif n == 5 and want_stat is not None:
                s = dict(_fields(v))
                if stat_names.get(_signed(s.get(1, 0))) != want_stat:
                    continue
                if 5 in s:
                    value = _str(s[5])
                elif 7 in s:                  # a reference to a stat name
                    value = stat_names.get(s[7], "")
        out[mid] = (name, value)
    return out


def _spans(line: bytes) -> Iterator[Tuple[int, int, int]]:
    """(start ns, end ns, metadata id) of each event of a line."""
    _, ts, events = _line(line)
    for e in events:
        # whole nanoseconds, as ``jax.profiler.ProfileData`` gives them
        mid, off, dur = _event(e)
        start = ts + off // 1000
        yield start, start + dur // 1000, mid


# -- the timeline -----------------------------------------------------------

def scope_of(tf_op: str) -> str:
    """``jit(<lambda>)/dot_general:`` -> ``jit(<lambda>)/dot_general``."""
    return tf_op.rpartition(":")[0] if ":" in tf_op else tf_op


def compute_scope(scope: str) -> Optional[str]:
    """The program's compute scope an operation ran under
    (``decode/embed``, ``attn/paged``, ``mlp``, ...), or None."""
    m = _COMPUTE.search(scope)
    if m is None:
        return None
    phase, end, attn, part, mixer = m.groups()
    if phase:
        return f"{phase}/{end}"
    if attn:
        return f"attn/{part}" if part else "attn"
    return mixer


@dataclasses.dataclass
class Timeline:
    window: Tuple[int, int]
    devices: List[List[Op]]                  # per device, inside the window
    host: List[Tuple[int, int, str]]         # program spans, any time

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def has_scopes(self) -> bool:
        return any(compute_scope(op[3]) for ops in self.devices for op in ops)

    def scope_seconds(self) -> Dict[str, float]:
        """Device self seconds per compute scope, summed over devices;
        operations under none are ``unscoped``."""
        out: Dict[str, float] = {}
        for ops in self.devices:
            own_ns = trace._self_times([op[:3] for op in ops])
            for op, own in zip(ops, own_ns):
                k = compute_scope(op[3]) or "unscoped"
                out[k] = out.get(k, 0.0) + own * 1e-9
        return out

    def busy_s(self) -> float:
        return sum(trace._length(trace._union([(s, e) for s, e, *_ in ops]))
                   for ops in self.devices) * 1e-9

    def span_seconds(self, names) -> float:
        """Host seconds inside the window covered by spans named in
        ``names``."""
        w0, w1 = self.window
        iv = [(max(s, w0), min(e, w1)) for s, e, n in self.host
              if n in names and e > w0 and s < w1]
        return trace._length(trace._union(iv)) * 1e-9

    def idle_gaps(self) -> Dict[str, float]:
        """Idle device seconds (mean over devices) by the innermost host
        span around each gap's middle: the finer labels of
        ``trace.Reduced.breakdown``."""
        red = trace.Reduced(
            [trace.Device(str(i), self.window, [op[:3] for op in ops])
             for i, ops in enumerate(self.devices)], self.host)
        return dict(red.breakdown(top=1 << 30)["idle_gaps"])


def read(path, n_devices: int) -> Timeline:
    data = pathlib.Path(path).read_bytes()
    window = None
    host: List[Tuple[int, int, str]] = []
    devices: Dict[int, List[Op]] = {}
    for plane in _planes(data):
        if plane.name.startswith("/device:TPU:") and "Core" not in \
                plane.name.split("/device:TPU:")[1][1:]:
            meta = _metadata(plane, "tf_op")
            ops: List[Op] = []
            for line in plane.lines:
                if _line(line)[0] != trace.OPS_LINE:
                    continue
                for s, e, mid in _spans(line):
                    name, tf_op = meta.get(mid, ("", ""))
                    ops.append((s, e, trace.short_name(name),
                                scope_of(tf_op)))
            devices[int(plane.name.rsplit(":", 1)[1])] = ops
        elif plane.name.startswith("/host:"):
            meta = _metadata(plane, None)
            for line in plane.lines:
                for s, e, mid in _spans(line):
                    name = meta.get(mid, ("", ""))[0]
                    if name == trace.WINDOW:
                        window = (s, e)
                    elif name.startswith(HOST_PREFIXES):
                        host.append((s, e, name))
    if window is None:
        raise ValueError(f"{path}: no {trace.WINDOW!r} span in the trace")
    if len(devices) < n_devices:
        raise ValueError(f"{path}: {len(devices)} device planes, expected "
                         f"{n_devices}")
    w0, w1 = window
    kept = [[(max(s, w0), min(e, w1), n, sc) for s, e, n, sc in devices[k]
             if e > w0 and s < w1] for k in sorted(devices)[:n_devices]]
    return Timeline(window, kept, host)


def for_reduced(red: "trace.Reduced", bench_dir=None) -> Optional[Timeline]:
    """The timeline of the trace ``red`` was reduced from: the trace under
    the benchmark's run directories whose ``bench/window`` span is
    ``red``'s window. None where there is none (a reduction made by hand,
    a trace already deleted)."""
    if not red.devices:
        return None
    bench_dir = pathlib.Path(bench_dir or pathlib.Path(__file__).parent)
    want = red.devices[0].window
    for path in sorted((bench_dir / ".runs").glob("*/*/**/*.xplane.pb")):
        tl = read(path, len(red.devices))
        if all(abs(a - b) < 1.0 for a, b in zip(tl.window, want)):
            return tl
    return None
