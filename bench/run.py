#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of a short window in this run gives its
per-layer metrics and ``breakdown``. Either way the run checks what the
timed path produced against the plain reference (``bench/reference.py``)
and prints each number compared beside its limit, as the last lines of
standard error and under ``checks``, the last key of the result.

The last line of standard output is the result, one JSON object. Without
a TPU, or with fewer chips than the cell asks for, the run prints no
result and exits 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
# the TPU runtime writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_chip: bool = True,
             fault=None, calibrate: bool = False, cache: bool = True
             ) -> dict:
    """One run of a cell; returns the result object (not printed).

    ``require_chip=False``, ``fault`` (a function handed the program's
    object before set-up drives it) and ``calibrate`` (also read the
    control and the planted faults) serve the tests and
    ``bench/calibrate.py``; a benchmark run uses none of them."""
    cell = harness.load_cell(root, workload)
    if cache:
        harness.enable_compile_cache(root)
    devs = harness.devices(cell.chips, require_chip)
    driver = harness.load_module(cell.driver_path(), "bench_driver_")
    out = driver.run(cell, seed, seconds, trace, t_start,
                     require_chip=require_chip, fault=fault,
                     calibrate=calibrate)

    metrics = {}
    device = dict(harness.device_info(devs),
                  memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if not trace:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics["setup_s"] = {"value": out["setup_s"], "unit": "s"}
        for name, unit in units.items():
            if name != "setup_s":
                metrics[name] = {"value": out["e2e"][name], "unit": unit}
    else:
        from bench import trace as trace_lib

        reduced = trace_lib.reduce_dir(out["trace_dir"], len(devs))
        facts = dict(out["facts"], peak=harness.peaks(devs[0].device_kind)
                     if require_chip else harness.peaks("TPU v5 lite"))
        for m in cell.per_layer:
            reader = harness.load_module(cell.metric_path(m["name"]),
                                         "bench_metric_")
            value = reader.read(reduced, facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.mean_busy_s()
        device["window_s"] = reduced.mean_window_s()
        result["breakdown"] = reduced.breakdown()
        shutil.rmtree(out["trace_dir"], ignore_errors=True)

    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in out["checks"].items()}
    # a limit not yet set from calibration readings fails the check
    result["correct"] = bool(out["failed"] == 0 and all(
        c["limit"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values()))
    result["notes"] = out.get("notes", {})
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"[bench] {e}; no result", file=sys.stderr, flush=True)
        return 3
    for name, c in result["checks"].items():
        print(f"[bench] check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"[bench] correct: {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
