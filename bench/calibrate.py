#!/usr/bin/env python3
"""Read what the limits of a cell's check are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in this one process: a run of the cell as the benchmark
makes it, then the control (the fp8 reference's first choice at each
served position, in place of the served token) against the same
reference. One JSON line per seed goes to standard output and to
``bench/.runs/calibrate/<cell>.jsonl``. PERF.md records the readings and
the limits set from them.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# the TPU runtime writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    out = ROOT / "bench" / ".runs" / "calibrate"
    out.mkdir(parents=True, exist_ok=True)
    t = T_START
    with open(out / f"{args.workload}.jsonl", "a") as f:
        for seed in args.seeds:
            res = bench_run.run_cell(ROOT, args.workload, seed, args.seconds,
                                     False, t_start=t, calibrate=True)
            line = json.dumps({"seed": seed, "correct": res["correct"],
                               "metrics": res["metrics"],
                               "device": res["device"],
                               "notes": res["notes"],
                               "checks": res["checks"]})
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
            gc.collect()
            t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
