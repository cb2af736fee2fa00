"""End-to-end training driver.

Every run is described by an ``repro.plan.ExecutionPlan``: either loaded
from a plan file (``--plan results/PLAN_<arch>_<shape>.json``), autotuned
on the spot (``--autotune``: measure the analytical top-k arrangements,
persist + use the winner), or resolved from the CLI knobs by the analytical
cost model (leave ``--c``/``--scheme`` unset to let the model pick).

The plan spans every device this process sees: the chips of a TPU host
(published widths unless --smoke), or on CPU the host devices --devices
forces, typically with the reduced config:

  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
      --smoke --devices 8 --data 2 --c 1 --steps 20

  python -m repro.launch.train --arch h2o-danube-1.8b --seq-len 8192 \
      --batch 1 --steps 3 --scheme startrail --c 2   # four v5e chips
"""

import argparse
import os


def device_plan(cfg, shape, *, arch, **knobs):
    """The training plan over every device this process sees; knobs left
    unset are the cost model's and the backend's picks (``make_plan``)."""
    import jax

    from repro.plan import make_plan

    return make_plan(cfg, shape, arch=arch, n_devices=jax.device_count(),
                     **knobs)


def main(argv=None):
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture id (optional with --plan)")
    ap.add_argument("--smoke", action="store_true",
                    help="the registry's reduced config (CPU-runnable)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU)")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--c", type=int, default=None,
                    help="StarTrail C (default: cost-model pick)")
    ap.add_argument("--scheme", default=None,
                    choices=["startrail", "ring", "ulysses"],
                    help="attention scheme (default: cost-model pick)")
    ap.add_argument("--placement", default=None,
                    choices=["team_inner", "ring_inner"])
    ap.add_argument("--microbatches", type=int, default=None,
                    help="grad-accumulation microbatches (default: plan)")
    ap.add_argument("--comm-chunks", type=int, default=None,
                    help="ring-transfer sub-chunks (default: overlap-model "
                         "pick; must divide the team seq length C*N/P)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="disable the double-buffered ring scan (debug A/B;"
                         " bit-identical either way)")
    ap.add_argument("--plan", default=None,
                    help="load a persisted ExecutionPlan json")
    ap.add_argument("--autotune", action="store_true",
                    help="measure the analytical top-k and use the winner")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--rules", default="default")
    # observability (repro.obs; all off by default = near-zero overhead)
    ap.add_argument("--metrics-dump", default=None,
                    help="write the obs registry (incl. per-collective "
                         "comm_bytes_total from the plan's arrangement) "
                         "here after the run (Prometheus text; .json "
                         "suffix -> JSON dump)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace-format JSON timeline of "
                         "train/data, train/step and train/ckpt spans here")
    args = ap.parse_args(argv)
    if not args.plan and not args.arch:
        ap.error("--arch is required (unless --plan carries it)")

    if args.plan and not args.devices:
        # a local-mesh plan records its forced-host device count; read it
        # from the raw json (before anything can initialise the backend)
        import json

        rec = json.loads(open(args.plan).read())
        rec = rec.get("plan", rec)
        if rec.get("mesh_kind") == "local":
            args.devices = int(rec["n_devices"])
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax

    from repro.configs import registry
    from repro.configs.base import ShapeConfig
    from repro.models.factory import build_model
    from repro.optim import adamw
    from repro.plan import ExecutionPlan, autotune as autotune_lib
    from repro.train import trainer as trainer_lib

    if args.plan:
        plan = ExecutionPlan.load(args.plan)
        cfg = registry.for_plan(plan)
        print(f"[train] loaded plan {args.plan}: scheme={plan.scheme} "
              f"C={plan.c} R={plan.r} microbatches={plan.microbatches}")
    else:
        cfg = (registry.get_smoke(args.arch) if args.smoke
               else registry.get(args.arch))
        shape = ShapeConfig("smoke" if args.smoke else f"seq{args.seq_len}",
                            seq_len=args.seq_len, global_batch=args.batch,
                            kind="train")
        if args.autotune:
            tuned = autotune_lib.autotune(
                cfg, shape, arch=args.arch, n_devices=jax.device_count(),
                data=args.data, microbatches=args.microbatches)
            plan = tuned["plan"]
            print(f"[train] autotuned plan -> {tuned['path']}: "
                  f"scheme={plan.scheme} C={plan.c} R={plan.r}")
        else:
            plan = device_plan(
                cfg, shape, arch=args.arch,
                data=args.data, scheme=args.scheme, c=args.c,
                placement=args.placement, microbatches=args.microbatches,
                sharding_rules=args.rules,
                pipeline_scan=not args.no_pipeline,
                comm_chunks=args.comm_chunks)
    print(f"[train] plan: P_sp={plan.sp_size} scheme={plan.scheme} "
          f"C={plan.c} R={plan.r} data={plan.data} "
          f"microbatches={plan.microbatches} block_impl={plan.block_impl}")

    model = build_model(cfg)
    adam_cfg = adamw.AdamWConfig(learning_rate=args.lr, warmup_steps=5,
                                 decay_steps=max(args.steps, 10),
                                 state_dtype=cfg.opt_dtype)
    tcfg = trainer_lib.TrainerConfig(
        num_steps=args.steps, ckpt_every=max(args.steps // 2, 1),
        ckpt_dir=args.ckpt_dir, metrics_path=args.metrics, log_every=5)

    from repro import obs

    obs_registry = obs.Registry() if args.metrics_dump else None
    # annotate=True wraps each host span in jax.profiler.TraceAnnotation,
    # so train/step lines up with the in-graph ring_permute_issue /
    # ring_block_compute scopes when a device profile is captured alongside
    tracer = (obs.Tracer(enabled=True, annotate=True)
              if args.trace_out else None)
    metrics = trainer_lib.train(model, plan, adam_cfg, tcfg,
                                tracer=tracer, registry=obs_registry)
    if args.metrics_dump:
        fmt = "json" if args.metrics_dump.endswith(".json") else "prometheus"
        obs_registry.dump(args.metrics_dump, fmt=fmt)
        print(f"[train] metrics dump -> {args.metrics_dump} ({fmt})")
    if args.trace_out:
        tracer.dump(args.trace_out)
        print(f"[train] trace ({len(tracer.events())} events) -> "
              f"{args.trace_out}")
    print(f"[train] done: {metrics}")
    return metrics


if __name__ == "__main__":
    main()
