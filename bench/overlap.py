"""Exposed collective time: the part of a device's timeline in which a
collective operation runs and no other operation does.

Collectives are found by the opcode in each operation's HLO text
(``%psum.3 = f32[8]{0} all-reduce(...)``), not by the instruction's name
or the scope it sits in: the program names collectives after its own
primitives (``psum``, ``pmax``, ``reduce_scatter``) and need give them no
scope. The opcodes are all-gather, all-reduce, reduce-scatter,
collective-permute and all-to-all, with their asynchronous ``-start`` and
``-done`` halves. Only innermost operations count (a loop spans the
operations of its body, and an operation's time less that of the
operations nested in it is time in which nothing else runs on that
device), so an asynchronous collective counts for its start and done
events: where the device issues the transfer or waits on it.

``trace.Reduced`` keeps operations by their short names, so this reads
the run's trace again (``reduce_file``'s window and device planes).
"""

from __future__ import annotations

import pathlib
import re
from typing import Iterable, List, Tuple

from bench import trace as trace_lib

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
_OPCODE = re.compile(r"(?<![\w-])([a-z][a-z0-9-]*)\(")


def opcode(hlo: str) -> str:
    """``%psum.3 = f32[8]{0} all-reduce(f32[8]{0} %x), ...`` ->
    ``all-reduce``; '' where the text holds none."""
    m = _OPCODE.search(hlo.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def is_collective(hlo: str) -> bool:
    op = opcode(hlo)
    for half in ("-start", "-done"):
        if op.endswith(half):
            op = op[:-len(half)]
    return op in COLLECTIVES


def exposed_ns(ops: Iterable[Tuple[int, int, str]]) -> int:
    """Nanoseconds in which a collective operation is the innermost one
    running. ``ops``: (start, end, HLO text) of one
    device's operations line, where operations run one after another or
    nest, so an operation's self time (its span less its children's) is
    time in which nothing else runs."""
    ops = list(ops)
    own = trace_lib._self_times(ops)
    return sum(t for (_, _, n), t in zip(ops, own) if is_collective(n))


def device_ops(path, n_devices: int
               ) -> Tuple[Tuple[int, int], List[List[Tuple[int, int, str]]]]:
    """The ``bench/window`` span and, per device, its operations inside it
    with their whole HLO text."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    window = None
    devs = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in \
                plane.name.split("/device:TPU:")[1][1:]:
            for line in plane.lines:
                if line.name == trace_lib.OPS_LINE:
                    devs[int(plane.name.rsplit(":", 1)[1])] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace_lib.WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None or len(devs) < n_devices:
        raise ValueError(f"{path}: no {trace_lib.WINDOW!r} span or fewer "
                         f"than {n_devices} device planes")
    w0, w1 = window
    return window, [[(max(s, w0), min(e, w1), n) for s, e, n in devs[k]
                     if e > w0 and s < w1]
                    for k in sorted(devs)[:n_devices]]


def exposed_shares(trace_dir, n_devices: int) -> List[float]:
    """Each device's exposed collective time over the traced window."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    (w0, w1), devs = device_ops(files[-1], n_devices)
    return [exposed_ns(ops) / (w1 - w0) for ops in devs]
