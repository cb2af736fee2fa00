"""End-to-end system tests.

The distributed checks need >1 device, so they run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (keeping this pytest
session on 1 device, as required for the smoke/bench paths). Checks are
batched per subprocess to amortise startup.
"""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

BATCHES = {
    "attention_correctness": [
        "topology", "ring_causal_zigzag", "ring_full_contig",
        "st2_causal_zigzag", "st2_causal_contig", "st2_full", "st2_window",
        "st2_window_skip", "st2_mha", "st2_mqa", "st2_bf16", "st2_r1",
    ],
    "attention_pallas_and_baselines": ["st2_pallas", "ulysses", "decode"],
    "spmd_model_equivalence": [
        "spmd_dense_swa", "spmd_dense_c1", "spmd_moe", "spmd_vlm",
        "spmd_encdec", "spmd_hybrid", "spmd_xlstm_runs",
    ],
    "spmd_train_and_serve": [
        "spmd_train_step", "serve_dense", "serve_moe", "serve_hybrid",
        "serve_xlstm", "serve_encdec",
    ],
    "engine_serving": [
        "greedy_tie", "engine_sampling", "engine_mixed", "engine_moe",
    ],
    "engine_paged_kernel": [
        "paged_decode_dist", "engine_paged_kernel", "chunked_prefill_dist",
    ],
    "gateway_serving": [
        "gateway_prefix_cow", "gateway_replicas", "gateway_disagg",
    ],
    "plan_and_microbatch": [
        "microbatch_equiv", "scheme_crosscheck", "ulysses_rejected",
        "plan_constructs", "commlog_c2", "init_sharded",
    ],
    "pipelined_scan": [
        "pipelined_bitexact", "bwd_skip_equiv",
    ],
}


BATCHES_16DEV = {
    "c4_and_16dev_rings": ["st4_p16", "st2_p16_r4", "st2_p16_window"],
}
BATCHES.update(BATCHES_16DEV)


@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_distributed(batch):
    env = dict(os.environ)
    n = 16 if batch in BATCHES_16DEV else 8
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.testing.dist_checks", *BATCHES[batch]],
        env=env, capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, (
        f"distributed batch {batch} failed:\n{proc.stdout[-4000:]}\n"
        f"{proc.stderr[-2000:]}")


def test_dryrun_one_cell():
    """The 512-device dry-run machinery works (fast cell)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)  # dryrun sets its own 512-device flag
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "h2o-danube-1.8b", "--shape", "decode_32k"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert "[ok]" in proc.stdout


def test_train_driver_end_to_end(tmp_path):
    """launch.train runs, checkpoints, and restores in a fresh process;
    the jsonl metrics stream carries every step (the trainer buffers
    metrics on-device between log boundaries — the stream must not)."""
    import json

    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    metrics = tmp_path / "metrics.jsonl"
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "h2o-danube-1.8b", "--smoke", "--devices", "8", "--data", "2",
            "--c", "2", "--steps", "6", "--ckpt-dir", str(tmp_path),
            "--metrics", str(metrics)]
    p1 = subprocess.run(args, env=env, capture_output=True, text=True,
                        timeout=1200)
    assert p1.returncode == 0, p1.stdout[-3000:] + p1.stderr[-2000:]
    args[args.index("6")] = "8"
    p2 = subprocess.run(args, env=env, capture_output=True, text=True,
                        timeout=1200)
    assert p2.returncode == 0, p2.stdout[-3000:] + p2.stderr[-2000:]
    assert "restored step 6" in p2.stdout
    recs = [json.loads(l) for l in metrics.read_text().splitlines()]
    assert [r["step"] for r in recs] == list(range(1, 7)) + [7, 8]
    assert all("loss" in r and "grad_norm" in r for r in recs)
    # per-phase wall-time breakdown from the obs span layer (host
    # perf_counter only — no per-step device sync): every record carries
    # data/step/ckpt seconds, and the ckpt launch cost lands on boundaries
    assert all({"data_s", "step_s", "ckpt_s"} <= r.keys() for r in recs)
    assert all(r["data_s"] >= 0 and r["step_s"] > 0 for r in recs)
    ckpt_steps = [r["step"] for r in recs if r["ckpt_s"] > 0]
    assert ckpt_steps and set(ckpt_steps) <= {3, 6, 4, 8}, ckpt_steps
