#!/usr/bin/env python3
"""Chip smoke test: train and serve h2o-danube-1.8b once on a TPU, at its
published widths with seed-0 random weights, and check what comes out.

    python chip_smoke.py            # one chip: kernel parity, serve, train
    python chip_smoke.py --chips 4  # four chips: ring vs StarTrail training

Every phase runs in this one process (a chip belongs to one process) and
raises on a failed check, so any failure exits nonzero. Without a TPU the
script prints the platform it found and exits nonzero before any phase.
The last line of stdout is the JSON result
``{"ok": true, "device": {"platform", "kind", "count"}}``. Times printed
along the way include compilation and are informational, not benchmarks.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import tempfile
import time
from typing import List, Optional, Tuple

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "h2o-danube-1.8b"

# kernel parity runs f32 on both sides at the highest matmul precision, so
# only the summation order differs (online softmax over K/V tiles, MXU
# accumulation): ~1e-6 of each output's scale. The bound leaves 100x room.
KERNEL_TOL = 1e-4
# pallas vs ref training step on bf16 weights and activations: the kernel
# and the XLA reference round the attention probabilities at different
# points of the p.V product, ~1e-3 relative in attention outputs; the
# step-1 loss (~ln 32000 = 10.4) may move by this much (absolute)
TRAIN_LOSS_TOL = 1e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs. ``FULL`` is the chip run; the tier-1 test runs
    the same phases at ``SMOKE`` size on the CPU."""

    smoke: bool                 # the registry's reduced config
    impl: str                   # the kernels the plans must resolve to
    kernel_seq: int             # kernel parity: sequence / prefix length
    page_size: int
    requests: int               # serve: requests, longest prompt, budget
    prompt_len: int
    gen: int
    train_layers: int           # train: depth cut of the one-chip run
    train_seq: int
    train_steps: int
    scheme_seq: int             # --chips 4: full depth, ring vs StarTrail
    scheme_steps: int


FULL = Sizes(smoke=False, impl="pallas", kernel_seq=2048, page_size=16,
             requests=8, prompt_len=2048, gen=64, train_layers=8,
             train_seq=4096, train_steps=5, scheme_seq=8192, scheme_steps=3)
SMOKE = Sizes(smoke=True, impl="ref", kernel_seq=64, page_size=8,
              requests=8, prompt_len=32, gen=8, train_layers=2,
              train_seq=64, train_steps=5, scheme_seq=64, scheme_steps=3)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _config(sizes: Sizes):
    from repro.configs import registry

    return registry.get_smoke(ARCH) if sizes.smoke else registry.get(ARCH)


def _widths(cfg) -> str:
    return (f"d_model={cfg.d_model} heads={cfg.num_heads}/"
            f"{cfg.num_kv_heads} head_dim={cfg.head_dim_} d_ff={cfg.d_ff} "
            f"vocab={cfg.vocab_size} window={cfg.window}")


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

def _max_err(got, want) -> float:
    """Max abs error over outputs, each scaled by 1 + its reference's
    largest magnitude; dead rows (lse = -inf) are compared by the mask."""
    import numpy as np

    from repro.core.combine import NEG_INF

    worst = 0.0
    for g, w in zip(got, want):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        dead = w <= NEG_INF / 2
        if not np.array_equal(dead, g <= NEG_INF / 2):
            return float("inf")
        live = ~dead
        if live.any():
            err = np.max(np.abs(g[live] - w[live]))
            worst = max(worst, err / (1.0 + np.max(np.abs(w[live]))))
    return worst


def _kernel_cases(sizes: Sizes):
    """(name, kernel call, reference call) at the config's widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import dispatch, paged_decode, paged_prefill, ref
    from repro.kernels import flash_attention as fa

    cfg = _config(sizes)
    hq, hkv, d, window = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
                          cfg.window)
    S, ps = sizes.kernel_seq, sizes.page_size
    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    rnd = lambda i, shape: jax.random.normal(ks[i], shape, jnp.float32)
    q, k, v, do = (rnd(0, (1, S, hq, d)), rnd(1, (1, S, hkv, d)),
                   rnd(2, (1, S, hkv, d)), rnd(3, (1, S, hq, d)))
    pos = jnp.arange(S, dtype=jnp.int32)
    attn = dict(causal=True, window=window)
    o_ref, lse_ref = ref.block_attention(q, k, v, pos, pos, **attn)
    delta = jnp.einsum("bshd,bshd->bhs", do, o_ref)

    # two ragged rows: one full, one whose tail keys are pushed past every
    # query (the engine's validity encoding)
    B = 2
    qb, kb, vb = (rnd(4, (B, S, hq, d)), rnd(5, (B, S, hkv, d)),
                  rnd(6, (B, S, hkv, d)))
    lens = jnp.asarray([S, S // 2 + 3], jnp.int32)
    idx = jnp.arange(S, dtype=jnp.int32)
    pos_k = jnp.where(idx[None] < lens[:, None], idx[None], 2 * S)
    pos_q = (lens - 1)[:, None] + idx[None] - (S - 1)
    pos_q = jnp.maximum(pos_q, 0).astype(jnp.int32)

    # a paged pool: B rows of W pages, shuffled page ids, ragged lengths
    rng = np.random.default_rng(0)
    W = S // ps
    pages = B * W
    table = jnp.asarray(rng.permutation(pages).reshape(B, W), jnp.int32)
    pool_k = rnd(7, (pages, ps, hkv, d))
    pool_v = rnd(8, (pages, ps, hkv, d))
    cache_len = jnp.asarray([S - 1, S // 3], jnp.int32)
    page_kw = dict(sp=1, page_size=ps, window=window)
    sq = min(S, 128)
    q_suffix = qb[:, :sq]
    rank = jnp.int32(0)

    return [
        ("flash_attention_fwd",
         lambda: (fa.flash_attention_fwd(q, k, v, pos, pos, **attn)
                  + fa.flash_attention_fwd(q, k, v, pos, pos, o_ref, lse_ref,
                                           **attn)),
         lambda: ((o_ref, lse_ref)
                  + ref.block_attention_merge(q, k, v, o_ref, lse_ref, pos,
                                              pos, **attn))),
        ("flash_attention_bwd",
         lambda: fa.flash_attention_bwd(q, k, v, do, lse_ref, delta, pos,
                                        pos, **attn),
         lambda: ref.block_attention_bwd(q, k, v, do, lse_ref, delta, pos,
                                         pos, **attn)),
        ("ragged_prefill_fwd",
         lambda: fa.flash_attention_fwd(qb, kb, vb, pos_q, pos_k, **attn),
         lambda: ref.block_attention(qb, kb, vb, pos_q, pos_k, **attn)),
        ("paged_decode_attention",
         lambda: paged_decode.paged_decode_attention(
             qb[:, :1], pool_k, pool_v, table, cache_len, rank, **page_kw),
         lambda: dispatch.paged_decode(
             qb[:, :1], pool_k, pool_v, table, cache_len, rank, impl="ref",
             **page_kw)),
        ("paged_prefill_attention",
         lambda: paged_prefill.paged_prefill_attention(
             q_suffix, pool_k, pool_v, table, cache_len, rank, **page_kw),
         lambda: dispatch.paged_prefill(
             q_suffix, pool_k, pool_v, table, cache_len, rank, impl="ref",
             **page_kw)),
    ]


def phase_kernel_parity(sizes: Sizes) -> None:
    import jax

    cfg = _config(sizes)
    log(f"kernel parity: {_widths(cfg)} seq={sizes.kernel_seq} "
        f"page_size={sizes.page_size} float32, matmul precision highest; "
        f"tolerance {KERNEL_TOL} of each output's scale (f32 both sides, "
        f"only the summation order differs)")
    with jax.default_matmul_precision("highest"):
        for name, kernel, oracle in _kernel_cases(sizes):
            t0 = time.perf_counter()
            got = jax.block_until_ready(kernel())
            t1 = time.perf_counter()
            want = jax.block_until_ready(oracle())
            err = _max_err(got, want)
            log(f"  {name}: max_abs_err={err:.3e} tol={KERNEL_TOL:.0e} "
                f"(compile+run {t1 - t0:.2f}s, informational)")
            if not err <= KERNEL_TOL:
                raise AssertionError(
                    f"{name}: error {err} exceeds {KERNEL_TOL}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(sizes: Sizes) -> None:
    from repro.configs import registry
    from repro.launch import serve
    from repro.plan import ExecutionPlan

    cfg = _config(sizes)
    max_len = sizes.prompt_len + sizes.gen
    # a pool that holds every request at full length at once
    pages = sizes.requests * -(-max_len // sizes.page_size)
    with tempfile.TemporaryDirectory() as tmp:
        plan_path = str(pathlib.Path(tmp) / "serve_plan.json")
        argv = ["--arch", ARCH, "--requests", str(sizes.requests),
                "--prompt-len", str(sizes.prompt_len),
                "--gen", str(sizes.gen), "--max-slots", str(sizes.requests),
                "--page-size", str(sizes.page_size),
                "--max-len", str(max_len), "--pages-per-shard", str(pages),
                "--save-plan", plan_path]
        if sizes.smoke:
            argv.append("--smoke")
        log(f"serve: {_widths(cfg)} layers={cfg.num_layers} (all) "
            f"requests={sizes.requests} prompts "
            f"{max(1, sizes.prompt_len // 4)}..{sizes.prompt_len} greedy, "
            f"{sizes.gen} new tokens each, pool={pages} pages of "
            f"{sizes.page_size}")
        t0 = time.perf_counter()
        out = serve.main(argv)
        wall = time.perf_counter() - t0
        plan = ExecutionPlan.load(plan_path)
    log(f"serve: plan block_impl={plan.block_impl} "
        f"kernel_impl={plan.kernel_impl} slots={plan.decode_batch} "
        f"capacity={plan.seq_len}; wall {wall:.1f}s (compile + run, "
        f"informational)")
    if (plan.block_impl, plan.kernel_impl) != (sizes.impl, sizes.impl):
        raise AssertionError(f"serve plan resolved {plan.block_impl}/"
                             f"{plan.kernel_impl}, expected {sizes.impl}")
    if registry.for_plan(plan) != cfg:
        raise AssertionError("serve plan is not on the expected config")
    if len(out) != sizes.requests:
        raise AssertionError(f"{len(out)} of {sizes.requests} requests "
                             f"finished")
    for uid, toks in sorted(out.items()):
        if len(toks) != sizes.gen:
            raise AssertionError(f"{uid}: {len(toks)} of {sizes.gen} tokens")
        if not all(0 <= t < cfg.vocab_size for t in toks):
            raise AssertionError(f"{uid}: token outside the vocabulary")
    log(f"serve: {len(out)} requests, each {sizes.gen} tokens")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _train(cfg, plan, steps: int) -> Tuple[List[dict], float]:
    """Run ``trainer.train`` as ``launch.train`` does; returns the per-step
    metric records (loss, ...) and the call's wall seconds."""
    from repro.models.factory import build_model
    from repro.optim import adamw
    from repro.train import trainer as trainer_lib

    model = build_model(cfg)
    adam_cfg = adamw.AdamWConfig(learning_rate=1e-3, warmup_steps=5,
                                 decay_steps=max(steps, 10),
                                 state_dtype=cfg.opt_dtype)
    with tempfile.TemporaryDirectory() as tmp:
        path = pathlib.Path(tmp) / "metrics.jsonl"
        tcfg = trainer_lib.TrainerConfig(num_steps=steps, ckpt_every=steps,
                                         metrics_path=str(path), log_every=1)
        t0 = time.perf_counter()
        trainer_lib.train(model, plan, adam_cfg, tcfg)
        wall = time.perf_counter() - t0
        recs = [json.loads(line) for line in path.read_text().splitlines()]
    return recs, wall


def _report_steps(tag: str, recs, wall: float) -> None:
    import jax

    # the trainer's step_s is host time (dispatch + a wait on the previous
    # step), so the run is reported by its wall time instead
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peak = ("n/a" if None in peaks else
            f"{max(peaks) / 1e9:.2f} GB")
    log(f"{tag}: losses {[round(r['loss'], 5) for r in recs]}; "
        f"first step {recs[0]['step_s']:.1f}s (compile included), "
        f"train() wall {wall:.1f}s for {len(recs)} steps with init "
        f"(informational); peak device memory so far, largest over "
        f"devices: {peak}")


def _check_losses(tag: str, recs, steps: int) -> None:
    import math

    if len(recs) != steps or not all(math.isfinite(r["loss"])
                                     for r in recs):
        raise AssertionError(f"{tag}: losses {recs}")


def phase_train(sizes: Sizes) -> None:
    from repro.configs.base import ShapeConfig
    from repro.launch.train import device_plan

    full = _config(sizes)
    cfg = dataclasses.replace(full, num_layers=sizes.train_layers)
    shape = ShapeConfig(f"seq{sizes.train_seq}", seq_len=sizes.train_seq,
                        global_batch=1, kind="train")
    plan = device_plan(cfg, shape, arch=ARCH)
    log(f"train: {_widths(cfg)} depth cut {full.num_layers} -> "
        f"{cfg.num_layers} layers to fit one chip; seq={sizes.train_seq} "
        f"global_batch=1 steps={sizes.train_steps}; plan scheme="
        f"{plan.scheme} P={plan.sp_size} block_impl={plan.block_impl}")
    if plan.block_impl != sizes.impl:
        raise AssertionError(f"train plan resolved {plan.block_impl}, "
                             f"expected {sizes.impl}")
    recs, wall = _train(cfg, plan, sizes.train_steps)
    _report_steps("train", recs, wall)
    _check_losses("train", recs, sizes.train_steps)
    gc.collect()
    ref_recs, _ = _train(cfg, dataclasses.replace(plan, block_impl="ref"), 1)
    diff = abs(recs[0]["loss"] - ref_recs[0]["loss"])
    log(f"train: step-1 loss {sizes.impl} {recs[0]['loss']:.6f} vs ref "
        f"{ref_recs[0]['loss']:.6f}: |diff|={diff:.3e} "
        f"tol={TRAIN_LOSS_TOL:.0e}")
    if not diff <= TRAIN_LOSS_TOL:
        raise AssertionError(f"train: step-1 loss differs from ref by {diff}")


def phase_schemes(sizes: Sizes) -> None:
    """Full-depth training across the chips: ring (C=1) vs StarTrail
    (C=2, R=P/4), same seed and data, step-1 losses within the scheme
    equivalence tolerance."""
    from repro.configs.base import ShapeConfig
    from repro.launch.train import device_plan
    from repro.testing.dist_checks import SCHEME_LOSS_TOL

    cfg = _config(sizes)
    shape = ShapeConfig(f"seq{sizes.scheme_seq}", seq_len=sizes.scheme_seq,
                        global_batch=1, kind="train")
    first = {}
    for scheme, c in (("ring", 1), ("startrail", 2)):
        plan = device_plan(cfg, shape, arch=ARCH, scheme=scheme, c=c)
        log(f"{scheme}: {_widths(cfg)} layers={cfg.num_layers} (all) "
            f"seq={sizes.scheme_seq} global_batch=1 "
            f"steps={sizes.scheme_steps}; plan P={plan.sp_size} C={plan.c} "
            f"R={plan.r} block_impl={plan.block_impl}")
        if plan.block_impl != sizes.impl:
            raise AssertionError(f"{scheme} plan resolved {plan.block_impl}"
                                 f", expected {sizes.impl}")
        recs, wall = _train(cfg, plan, sizes.scheme_steps)
        _report_steps(scheme, recs, wall)
        _check_losses(scheme, recs, sizes.scheme_steps)
        first[scheme] = recs[0]["loss"]
        gc.collect()
    diff = abs(first["ring"] - first["startrail"])
    log(f"ring vs startrail step-1 loss: {first['ring']:.6f} vs "
        f"{first['startrail']:.6f}: |diff|={diff:.3e} "
        f"tol={SCHEME_LOSS_TOL:.0e}")
    if not diff <= SCHEME_LOSS_TOL:
        raise AssertionError(f"ring vs startrail differ by {diff}")


# ---------------------------------------------------------------------------

def _tpu() -> Optional[object]:
    import jax

    dev = jax.devices()[0]
    return dev if dev.platform == "tpu" else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the ring vs StarTrail training across "
                         "four chips")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    dev = _tpu()
    if dev is None:
        log(f"JAX found platform {jax.devices()[0].platform!r}, not a TPU; "
            f"no phase run")
        return 1
    n = len(jax.devices())
    if n < args.chips:
        log(f"--chips {args.chips} needs {args.chips} chips, found {n}")
        return 1
    log(f"device {dev.device_kind} x{n}; compile cache {cache}")
    phases = ([phase_schemes] if args.chips == 4 else
              [phase_kernel_parity, phase_serve, phase_train])
    for phase in phases:
        t0 = time.perf_counter()
        phase(FULL)
        # the engine and trainer state of a finished phase sit in reference
        # cycles; free their device memory before the next phase
        gc.collect()
        log(f"{phase.__name__} passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
