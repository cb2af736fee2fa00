#!/usr/bin/env python3
"""One run of a cell as ``run.py`` makes it, with a record of every engine
step that the result line has no room for.

    python3 bench/probe.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--out <file>]

Prints one JSON object (and writes it to ``--out``): the run's result,
and for the measured window

- ``slowest``: its six slowest ``Engine.step()`` calls, each with its host
  phases (``Engine.last_step_phases``), the garbage-collector pauses and
  collections and the XLA compiles during it (``obs.watch_host``);
- ``step_ms_median`` and the window's GC and compile totals;
- with ``--trace 1``: ``traced_step_ms_median`` (the steps of the traced
  part, to set beside ``step_ms_median`` of an untraced run: the cost of
  tracing), ``scope_seconds`` (device self time per compute scope,
  ``scopes.py``), ``unscoped_ops`` (the operations under none, with the
  most time) and ``idle_gaps`` labelled by every host span.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

# the TPU runtime writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run as bench_run  # noqa: E402
from bench import scopes, trace  # noqa: E402


def record_steps(engine_cls, steps: list) -> None:
    """Make every ``engine_cls.step()`` call append its record to
    ``steps``."""
    inner = engine_cls.step

    def step(self):
        h0, t0 = self.host.snapshot(), time.perf_counter()
        out = inner(self)
        t1, h1 = time.perf_counter(), self.host.snapshot()
        steps.append({
            "t": t0, "ms": 1e3 * (t1 - t0),
            "phases_ms": {k: 1e3 * v
                          for k, v in self.last_step_phases.items()},
            **{k: h1[k] - h0[k] for k in h0}})
        return out

    engine_cls.step = step


def summary(window: list, traced_s: float) -> dict:
    w0 = window[0]["t"]
    ranked = sorted(window, key=lambda s: -s["ms"])[:6]
    out = {"step_ms_median": statistics.median(s["ms"] for s in window),
           "slowest": [dict(s, at_s=s["t"] - w0,
                            index=window.index(s)) for s in ranked],
           "window_gc_s": sum(s["gc_s"] for s in window),
           "window_gc_gen2": sum(s["gc_gen2"] for s in window),
           "window_compiles": sum(s["compiles"] for s in window)}
    for s in out["slowest"]:
        del s["t"]
    if traced_s:
        out["traced_step_ms_median"] = statistics.median(
            s["ms"] for s in window if s["t"] - w0 < traced_s)
    return out


def unscoped_ops(tl: "scopes.Timeline", top: int = 12) -> list:
    """The operations under no compute scope with the most device self
    time: [name, scope path ('' for none), seconds]."""
    out: dict = {}
    for ops in tl.devices:
        own = trace._self_times([op[:3] for op in ops])
        for (_, _, name, scope), t in zip(ops, own):
            if scopes.compute_scope(scope) is None:
                out[name, scope] = out.get((name, scope), 0.0) + t * 1e-9
    ranked = sorted(out.items(), key=lambda kv: -kv[1])[:top]
    return [[n, sc, t] for (n, sc), t in ranked]


def probe(root: pathlib.Path, workload: str, seed: int, seconds: float,
          traced: bool, t_start: float, **run_kw) -> dict:
    """One run of the cell (``run.run_cell``) and the record of its
    window's steps; ``run_kw`` serves the tests."""
    from repro.engine.engine import Engine

    steps: list = []
    inner = Engine.step
    record_steps(Engine, steps)
    kept: list = []
    # keep the trace past run_cell for the reads below
    bench_run.shutil = types.SimpleNamespace(
        rmtree=lambda path, **kw: kept.append(path))
    try:
        result = bench_run.run_cell(root, workload, seed, seconds, traced,
                                    t_start=t_start, **run_kw)
    finally:
        Engine.step, bench_run.shutil = inner, shutil
    # the window's steps are the run's last ones (the check steps no engine)
    window = steps[-result["notes"]["window_steps"]:]
    cell = bench_run.harness.load_cell(root, workload)
    traced_s = float(cell.traffic["trace_seconds"]) if traced else 0.0
    out = {"result": result, **summary(window, traced_s)}
    if traced:
        red = trace.reduce_dir(kept[0], cell.chips)
        tl = scopes.for_reduced(red, cell.bench_dir)
        if tl is not None:
            out["scope_seconds"] = tl.scope_seconds()
            out["unscoped_ops"] = unscoped_ops(tl)
            out["idle_gaps"] = tl.idle_gaps()
            out["busy_s"], out["window_s"] = tl.busy_s(), tl.window_s
    for path in kept:
        shutil.rmtree(path, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    line = json.dumps(probe(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START))
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
