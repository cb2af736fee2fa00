import os
import sys

if "--autotune" in sys.argv:
    # the autotune path measures real steps on the CPU smoke mesh
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
else:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each cell
the full production step (train: fwd+bwd+AdamW; prefill / decode: serve
step) is lowered with ShapeDtypeStruct stand-ins (zero allocation) onto the
16x16 single-pod mesh and the 2x16x16 multi-pod mesh, compiled, and the
compiled artifact's memory/cost analyses + collective schedule are recorded
for the roofline analysis (results/dryrun/*.json). Each cell is described
by an ``repro.plan.ExecutionPlan`` (``--plan FILE`` replays a persisted
one).

``--autotune`` instead runs the measured arrangement search on the 8-device
CPU smoke mesh (short jitted steps over the analytical top-k plus the
analytical worst), persists the winner to ``results/PLAN_<arch>_smoke.json``
and fails if the chosen plan does not beat the worst candidate — the CI
`plan-smoke` job runs exactly this.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch minitron-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--c 2]
  PYTHONPATH=src python -m repro.launch.dryrun --autotune [--arch ...]
"""

import argparse
import dataclasses as dc
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import SHAPES
from repro.models.factory import build_model
from repro.optim import adamw
from repro.plan import ExecutionPlan, make_plan
from repro.roofline import hlo as hlo_lib

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _compile_once(model, mesh, run_cfg, shape, cfg):
    """Lower + compile the step for this shape kind; returns (lowered, compiled)."""
    from repro.serve import kv_cache, step as serve_step
    from repro.train import step as train_step

    if shape.kind == "train":
        acfg = adamw.AdamWConfig(state_dtype=cfg.opt_dtype)
        jstep, _ = train_step.build_train_step(model, mesh, run_cfg, shape,
                                               acfg)
        params = model.abstract()
        opt = adamw.abstract_state(params, acfg)
        batch = model.input_specs(shape)
        lowered = jstep.lower(params, opt, batch)
    elif shape.kind == "prefill":
        jstep, _ = serve_step.build_prefill_step(model, mesh, run_cfg, shape)
        params = model.abstract()
        batch = {k: v for k, v in model.input_specs(shape).items()
                 if k != "labels"}
        if cfg.encdec and "frontend_emb" not in batch:
            batch["frontend_emb"] = jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len, cfg.d_model),
                jnp.dtype(cfg.param_dtype))
        lowered = jstep.lower(params, batch)
    else:  # decode
        jstep, _ = serve_step.build_decode_step(model, mesh, run_cfg, shape)
        params = model.abstract()
        cache = kv_cache.cache_spec(cfg, shape.global_batch, shape.seq_len)
        tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        lowered = jstep.lower(params, cache, tokens)
    return lowered, lowered.compile()


def _costs(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    coll = hlo_lib.collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_total": float(coll["total_bytes"]),
        "coll_by_kind": coll["bytes_by_kind"],
        "coll_counts": coll["count_by_kind"],
    }


def plan_for_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                  c: int = 2, rules: str = "default",
                  remat: str = "attn_out",
                  placement: str = "team_inner") -> ExecutionPlan:
    """The production ExecutionPlan for one dry-run cell."""
    cfg = registry.get(arch)
    shape = SHAPES[shape_name]
    pod = 2 if multi_pod else 1
    # microbatches resolved by the plan (auto for production train shapes:
    # this is what lets train_4k's global_batch=256 compile honestly)
    return make_plan(cfg, shape, arch=arch, n_devices=256 * pod, data=16,
                     pod=pod, c=c, placement=placement, remat=remat,
                     sharding_rules=rules, mesh_kind="production")


def lower_cell(arch: str, shape_name: str, **plan_kw):
    """Lower + compile one cell; exact cost accounting via two-point depth
    extrapolation.

    XLA's cost_analysis counts while-loop bodies once (not x trip count),
    so the full-depth compile proves compile/memory while per-step costs
    come from two shallow compiles (1 and 2 layer-periods) with all inner
    scans (rings, vocab-CE chunks) unrolled:

        cost(L) = cost(1) + (cost(2) - cost(1)) * (n_periods - 1)

    which is exact for homogeneous periods (true by construction).
    """
    from repro.models import transformer

    plan = plan_kw.pop("plan", None)
    if plan is not None:
        # replay: the plan carries the shape (incl. non-registry ones like
        # 'smoke') and whether it was tuned on the reduced config
        cfg = registry.for_plan(plan)
        shape = plan.shape_config()
    else:
        cfg = registry.get(arch)
        shape = SHAPES[shape_name]
    ok, why = registry.shape_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    if plan is None:
        plan = plan_for_cell(arch, shape_name, **plan_kw)
    mesh = plan.build_mesh()
    run_cfg = plan.run_config()

    # ---- full-depth compile: proves the cell + memory analysis ----
    model = build_model(cfg)
    t0 = time.time()
    lowered, compiled = _compile_once(model, mesh, run_cfg, shape, cfg)
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    raw = _costs(compiled)

    # ---- shallow unrolled compiles for exact per-step costs ----
    period = len(transformer.layer_pattern(cfg))
    n_periods = cfg.num_layers // period
    run_u = dc.replace(run_cfg, unroll_scans=True)
    shallow = []
    for k in (1, 2):
        kcfg = dc.replace(cfg, num_layers=k * period)
        if cfg.encdec:
            kcfg = dc.replace(kcfg, num_encoder_layers=k)
        _, comp_k = _compile_once(build_model(kcfg), mesh, run_u, shape, kcfg)
        shallow.append(_costs(comp_k))
    c1, c2 = shallow

    def extrap(key):
        return c1[key] + (c2[key] - c1[key]) * (n_periods - 1)

    coll_by_kind = {}
    for kind in set(c1["coll_by_kind"]) | set(c2["coll_by_kind"]):
        a = c1["coll_by_kind"].get(kind, 0)
        b = c2["coll_by_kind"].get(kind, 0)
        coll_by_kind[kind] = a + (b - a) * (n_periods - 1)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if plan.pod > 1 else "16x16",
        "kind": shape.kind,
        "plan": plan.to_dict(),
        "c": plan.c,
        "rules": plan.sharding_rules,
        "remat": plan.remat,
        "placement": plan.placement,
        "devices": plan.n_devices,
        "n_periods": n_periods,
        "compile_s": round(t_compile, 1),
        "flops_per_device": extrap("flops"),
        "bytes_accessed_per_device": extrap("bytes"),
        "collectives": {
            "total_bytes": extrap("coll_total"),
            "bytes_by_kind": coll_by_kind,
            "count_by_kind_one_period": c1["coll_counts"],
        },
        "raw_full_depth": raw,
        "shallow": {"k1": c1, "k2": c2},
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
        },
    }
    return rec


def run_and_save(arch, shape_name, **kw):
    RESULTS.mkdir(parents=True, exist_ok=True)
    tag = "multi" if kw.get("multi_pod") else "single"
    c = kw.get("c", 2)
    name = f"{arch}__{shape_name}__{tag}__c{c}"
    if kw.get("rules", "default") != "default":
        name += f"__{kw['rules']}"
    if kw.get("placement", "team_inner") != "team_inner":
        name += f"__{kw['placement']}"
    if kw.get("remat", "attn_out") != "attn_out":
        name += f"__remat_{kw['remat']}"
    out = RESULTS / f"{name}.json"
    try:
        rec = lower_cell(arch, shape_name, **kw)
        rec["status"] = "skipped" if rec.get("skipped") else "ok"
    except Exception as e:  # noqa: BLE001
        kw_rec = {k: (v.to_dict() if isinstance(v, ExecutionPlan) else v)
                  for k, v in kw.items()}
        rec = {"arch": arch, "shape": shape_name, "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-3000:], **kw_rec}
    out.write_text(json.dumps(rec, indent=2))
    status = rec["status"]
    extra = ""
    if status == "ok":
        gb = rec["memory"]["peak_bytes_per_device"] / 2**30
        extra = (f" peak={gb:.2f}GiB/dev flops={rec['flops_per_device']:.3g}"
                 f" compile={rec['compile_s']}s")
    print(f"[{status}] {name}{extra}", flush=True)
    return rec


def run_autotune(arch: str, *, seq_len: int = 64, batch: int = 4,
                 data: int = 1, steps: int = 3):
    """Measured arrangement search on the CPU smoke mesh (CI `plan-smoke`).

    Fails (SystemExit) unless the chosen plan beats the worst measured
    candidate — i.e. the tuner must never hand back the slowest
    arrangement of the ones it timed.
    """
    from repro.configs.base import ShapeConfig
    from repro.plan import autotune as autotune_lib

    cfg = registry.get_smoke(arch)
    n_devices = jax.device_count()
    shape = ShapeConfig("smoke", seq_len=seq_len, global_batch=batch,
                        kind="train")
    out = autotune_lib.autotune(cfg, shape, arch=arch, n_devices=n_devices,
                                data=data, mesh_kind="local", steps=steps)
    for e in out["measured"]:
        print(f"[autotune] {e['arrangement'].key:24s} "
              f"measured={e['measured_s'] * 1e3:8.2f}ms "
              f"analytical={e['analytical_s'] * 1e6:8.1f}us", flush=True)
    best, worst = out["measured"][0], out["measured"][-1]
    print(f"[autotune] winner={best['arrangement'].key} -> {out['path']}")
    # the in-memory winner is measured-best by construction, so assert the
    # things that can actually break: the *persisted* plan must round-trip
    # to that winner, and it must strictly beat the analytical-worst anchor
    # (a tie means the timing harness degenerated)
    if ExecutionPlan.load(out["path"]) != best["plan"]:
        raise SystemExit("persisted plan is not the measured winner")
    if len(out["measured"]) > 1 and \
            not best["measured_s"] < worst["measured_s"]:
        raise SystemExit(
            "autotuned pick does not beat the worst measured candidate "
            f"({best['measured_s']:.6f}s vs {worst['measured_s']:.6f}s)")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--c", type=int, default=2)
    ap.add_argument("--rules", default="default")
    ap.add_argument("--remat", default="attn_out")
    ap.add_argument("--placement", default="team_inner")
    ap.add_argument("--plan", default=None,
                    help="replay a persisted ExecutionPlan json for the cell")
    ap.add_argument("--autotune", action="store_true",
                    help="measured arrangement search on the CPU smoke mesh")
    args = ap.parse_args()

    if args.autotune:
        run_autotune(args.arch or "h2o-danube-1.8b")
        return

    plan = ExecutionPlan.load(args.plan) if args.plan else None
    cells = []
    if args.all:
        for a in registry.ASSIGNED_ARCHS:
            for sname in SHAPES:
                cells.append((a, sname))
    else:
        cells.append((args.arch or (plan and plan.arch),
                      args.shape or (plan and plan.shape)))

    meshes_to_run = [args.multi_pod]
    if args.both_meshes:
        meshes_to_run = [False, True]

    n_bad = 0
    for mp in meshes_to_run:
        for a, sname in cells:
            kw = dict(plan=plan) if plan else dict(
                multi_pod=mp, c=args.c, rules=args.rules, remat=args.remat,
                placement=args.placement)
            rec = run_and_save(a, sname, **kw)
            if rec.get("status") == "error":
                n_bad += 1
    if n_bad:
        raise SystemExit(f"{n_bad} cells failed")


if __name__ == "__main__":
    main()
