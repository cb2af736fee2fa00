"""A tiny copy of the benchmark, to drive on the CPU in tests."""

import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY_CONF = {
    "arch": "h2o-danube-1.8b", "source": "tiny", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "sliding_window": 16, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "float32"}

TINY_SERVE = {
    "kind": "serve", "clients": 3,
    "prompt": {"dist": "uniform", "lo": 8, "hi": 24},
    "output": {"dist": "uniform", "lo": 6, "hi": 12}, "greedy": True,
    "requests": 2000,
    "size_seed": 3, "page_size": 8, "max_slots": 3, "pool_pages": 15,
    "max_len": 40,
    "prefill_chunk": 0, "kernels": "ref", "trace_seconds": 1,
    "check": {"max_requests": 16, "min_tokens": 150},
    "limits": {"served_gap": 1e-3}}

TINY_TRAIN = {
    "kind": "train", "seq_len": 64, "global_batch": 1, "microbatches": 1,
    "plan": {"scheme": "startrail", "c": 2, "r": 1, "sp_size": 4,
             "placement": "team_inner", "seq_scheme": "zigzag"},
    "kernels": "pallas",
    "optimizer": {"learning_rate": 2e-3, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-8, "weight_decay": 0.1, "grad_clip": 1.0,
                  "warmup_steps": 0, "decay_steps": 1000,
                  "min_lr_ratio": 0.1, "state_dtype": "float32"},
    "trace_seconds": 1, "check": {"steps": 3, "qblock": 16},
    "limits": {"train_loss_gap": 1e-4, "grad_leaf_gap": 1e-3,
               "change_leaf_gap": 1e-3}}

#: each benchmark cell's tiny stand-in
TINY_CELLS = {"serve.danube.decode": "tiny.serve",
              "train.danube.p4.startrail": "tiny.train"}


def make_tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-like tree whose BENCHMARK.json names tiny cells, with the
    benchmark's drivers and metric readers copied in."""
    bench = tmp / "bench"
    for sub in ("drivers", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, bench / sub)
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY_CONF))
    (bench / "traffic" / "tiny_serve.json").write_text(json.dumps(TINY_SERVE))
    (bench / "traffic" / "tiny_train.json").write_text(json.dumps(TINY_TRAIN))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "tiny",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "tiny"}]
    spec["workloads"] = [
        {"name": "tiny.serve", "config": "tiny", "traffic": "tiny_serve",
         "chips": 1, "why": "tiny"},
        {"name": "tiny.train", "config": "tiny", "traffic": "tiny_train",
         "chips": 4, "why": "tiny"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY_CELLS[w] for w in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
