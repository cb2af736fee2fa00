"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read.

The trace of a traced run holds one plane per TPU (``/device:TPU:<n>``),
whose ``XLA Ops`` line has one event per device operation, named by its
HLO text (``%copy.67 = bf16[...] copy(...)``), and host planes whose
lines hold the ``TraceAnnotation`` spans of the program and of the
benchmark (``bench/window``, ``engine/step``, ``train/step``, ...) on the
same clock. Operations nest: a ``while`` (a scan over layers) spans the
operations of its body. The reduction keeps the part of each device's
timeline inside the ``bench/window`` span and gives:

- busy time per device: the union of its operations' intervals;
- self time per operation (its duration less that of the operations
  nested in it), keyed by the HLO instruction name (``copy.67``);
- collective-permute time during which no other innermost operation of
  that device runs (exposed exchange);
- idle gaps, each labelled by the innermost host span around it.
"""

from __future__ import annotations

import dataclasses
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench/window"
#: host spans that label idle gaps (the innermost one around a gap wins)
HOST_SPANS = ("engine/prefill", "engine/decode", "engine/step",
              "train/data", "train/step")
OPS_LINE = "XLA Ops"


def _union(iv: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(iv: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in iv)


def _subtract(a: List[Tuple[int, int]],
              b: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Parts of the union ``a`` not covered by the union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def short_name(hlo: str) -> str:
    """``%copy.67 = bf16[...] copy(...)`` -> ``copy.67``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def _self_times(ops: Sequence[Tuple[int, int, str]]) -> List[int]:
    """Each operation's duration less that of the operations directly
    nested in it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [e - s for s, e, _ in ops]
    stack: List[int] = []
    for i in order:
        s, e, _ = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= min(e, ops[p][1]) - s
        stack.append(i)
    return own


@dataclasses.dataclass
class Device:
    name: str
    window: Tuple[int, int]
    ops: List[Tuple[int, int, str]]

    def __post_init__(self):
        self.self_ns = _self_times(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self) -> List[Tuple[int, int]]:
        return _union([(s, e) for s, e, _ in self.ops])

    def busy_s(self) -> float:
        return _length(self.busy()) * 1e-9

    def op_seconds(self) -> Dict[str, float]:
        """Self time per operation name."""
        out: Dict[str, float] = {}
        for (_, _, n), t in zip(self.ops, self.self_ns):
            out[n] = out.get(n, 0.0) + t * 1e-9
        return out

    def exposed_s(self, match: str) -> float:
        """Time in operations whose name contains ``match`` while no other
        innermost operation runs on this device."""
        leaves = [(s, e, n) for (s, e, n), t in zip(self.ops, self.self_ns)
                  if t == e - s]
        mine = _union([(s, e) for s, e, n in leaves if match in n])
        other = _union([(s, e) for s, e, n in leaves if match not in n])
        return _length(_subtract(mine, other)) * 1e-9

    def gaps(self) -> List[Tuple[int, int]]:
        return _subtract([self.window], self.busy())


@dataclasses.dataclass
class Reduced:
    devices: List[Device]
    host: List[Tuple[int, int, str]]      # labelled host spans

    def mean_busy_s(self) -> float:
        return sum(d.busy_s() for d in self.devices) / len(self.devices)

    def mean_window_s(self) -> float:
        return sum(d.window_s for d in self.devices) / len(self.devices)

    def idle_shares(self) -> List[float]:
        return [1.0 - d.busy_s() / d.window_s for d in self.devices]

    def op_seconds(self, match) -> float:
        """Device self seconds, summed over devices, of the operations
        whose name ``match`` accepts."""
        return sum(t for d in self.devices for n, t in d.op_seconds().items()
                   if match(n))

    def label(self, s: int, e: int) -> str:
        """The innermost labelled host span around the middle of [s, e)."""
        mid = (s + e) // 2
        best: Optional[Tuple[int, str]] = None
        for hs, he, n in self.host:
            if hs <= mid < he and (best is None or he - hs < best[0]):
                best = (he - hs, n)
        return best[1] if best else "host/other"

    def breakdown(self, top: int = 10) -> Dict:
        ops: Dict[str, float] = {}
        for d in self.devices:
            for n, t in d.op_seconds().items():
                ops[n] = ops.get(n, 0.0) + t / len(self.devices)
        gaps: Dict[str, float] = {}
        for d in self.devices:
            for s, e in d.gaps():
                lab = self.label(s, e)
                gaps[lab] = gaps.get(lab, 0.0) + (e - s) * 1e-9 / len(
                    self.devices)
        key = lambda kv: -kv[1]  # noqa: E731
        return {"device_ops": [[n, t] for n, t in
                               sorted(ops.items(), key=key)[:top]],
                "idle_gaps": [[n, t] for n, t in
                              sorted(gaps.items(), key=key)[:top]]}


def reduce_file(path, n_devices: int) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    window = None
    host: List[Tuple[int, int, str]] = []
    dev_ops: Dict[str, List[Tuple[int, int, str]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in \
                plane.name.split("/device:TPU:")[1][1:]:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_ops[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns,
                         short_name(e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in HOST_SPANS:
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span in the trace")
    names = sorted(dev_ops, key=lambda n: int(n.rsplit(":", 1)[1]))
    if len(names) < n_devices:
        raise ValueError(f"{path}: {len(names)} device planes, expected "
                         f"{n_devices}")
    w0, w1 = window
    devices = []
    for n in names[:n_devices]:
        ops = [(max(s, w0), min(e, w1), name) for s, e, name in dev_ops[n]
               if e > w0 and s < w1]
        devices.append(Device(n, window, ops))
    return Reduced(devices, host)


def reduce_dir(d, n_devices: int) -> Reduced:
    files = sorted(pathlib.Path(d).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    return reduce_file(files[-1], n_devices)
