#!/usr/bin/env python3
"""Read what a training cell's limits are set from, on the chips.

    python bench/calibrate_train.py --workload <cell> --seconds <s> \\
        --seeds 1 2 3 --control-seeds 4 5 6

In this one process: for each of ``--seeds``, a run of the cell as the
benchmark makes it (the program's readings, the lower ends); for each of
``--control-seeds``, the reference and, each put in the program's place,
the fp8 control and two planted faults: the loss taken over half of each
row's tokens, and attention only within each device's own chunk of
keys (the exchange between chips left out). Their readings are upper
ends; a step that leaves its state unchanged reads 1 on the gradient and
change gaps and needs no run. One JSON line per run goes to standard
output and to ``bench/.runs/calibrate/<cell>.jsonl``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# the TPU runtime writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, reference, reference_train  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench import train_workload  # noqa: E402


#: what is put in the program's place against the reference
CONTROLS = ("fp8_control", "half_batch", "exchange_left_out")


def controls(cell, seed: int, devs, which=CONTROLS):
    """The reference's readings and what the check makes of the control
    and of each planted fault (those named in ``which``) put in the
    program's place."""
    tr = cell.traffic
    arch = reference.Arch.from_config(cell.conf)
    k = int(tr["check"]["steps"])
    batches = [train_workload.batch(seed, i, int(tr["global_batch"]),
                                    int(tr["seq_len"]), arch.vocab_size)
               for i in range(k)]
    opt = reference_train.Adam(**tr["optimizer"])
    kw = dict(qblock=int(tr["check"]["qblock"]))
    ref = reference_train.run(arch, seed, opt, batches, devs, **kw)
    chunk = int(tr["seq_len"]) // (2 * int(tr["plan"]["sp_size"]))
    out = {"reference_seconds": ref["seconds"],
           "reference_losses": ref["losses"]}
    planted = {"fp8_control": dict(precision="fp8"),
               "half_batch": dict(fault=reference_train.Fault(
                   half_batch=True)),
               "exchange_left_out": dict(fault=reference_train.Fault(
                   local_chunk=chunk))}
    for name in which:
        got = reference_train.run(arch, seed, opt, batches, devs, **kw,
                                  **planted[name])
        out[name] = reference_train.gaps(got, ref)
        out[name + "_seconds"] = got["seconds"]
        harness.free_device_memory()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--controls", nargs="+", choices=CONTROLS,
                    default=list(CONTROLS),
                    help="what to put in the program's place on the "
                         "control seeds (default: all)")
    args = ap.parse_args(argv)
    out = ROOT / "bench" / ".runs" / "calibrate"
    out.mkdir(parents=True, exist_ok=True)
    t = T_START
    with open(out / f"{args.workload}.jsonl", "a") as f:
        def emit(rec):
            line = json.dumps(rec)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        for seed in args.seeds:
            res = bench_run.run_cell(ROOT, args.workload, seed, args.seconds,
                                     False, t_start=t)
            notes = {k: v for k, v in res["notes"].items()
                     if not k.startswith("window_")}
            emit({"seed": seed, "kind": "program", "correct": res["correct"],
                  "metrics": res["metrics"], "device": res["device"],
                  "notes": notes, "checks": res["checks"]})
            harness.free_device_memory()
            t = time.perf_counter()
        if args.control_seeds:
            cell = harness.load_cell(ROOT, args.workload)
            harness.enable_compile_cache(ROOT)
            devs = harness.devices(cell.chips)
            for seed in args.control_seeds:
                emit({"seed": seed, "kind": "controls",
                      **controls(cell, seed, devs, args.controls)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
