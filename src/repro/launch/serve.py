"""Serving driver on top of ``repro.engine``: continuous batching with a
paged, SP-sharded KV cache, compiled once per length bucket.

Every serve run — engine and ``--legacy`` alike — is described by a
``kind='decode'`` ``ExecutionPlan`` (the serving face: decode slots, page
size, paged-decode ``kernel_impl``), exactly like ``launch.train``: load a
persisted one with ``--plan``, or let ``make_serve_plan`` resolve the CLI
knobs (leave ``--c`` unset for the cost-model pick; ``--kernel`` defaults
to the backend: Pallas on TPU, the jnp reference on CPU). ``--save-plan``
persists the resolved plan for replay / CI artifacts.

CPU-runnable reduced mode (the default serves ``--requests`` requests with
prompt lengths spread over ``[--prompt-len / 4, --prompt-len]`` and a
``--gen`` token budget each through the engine, and prints per-request
generations + engine metrics):

  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
      --smoke --devices 8 --c 1 --requests 8 --prompt-len 16 --gen 8

**Gateway mode** (``--replicas N`` and/or ``--prefix-cache``) serves the
workload through ``repro.gateway``: N engine replicas on disjoint device
submeshes (``--devices`` is the total; the plan records the per-replica
count), prefix-aware + load-aware routing with session affinity, and a
shared ``--system-prompt-len``-token prefix on every request so the
block-hash prefix cache has something to hit — per-request streams and the
gateway's hit-rate/eviction/routing metrics are printed:

  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
      --smoke --devices 8 --replicas 2 --prefix-cache --requests 8

``--host-tier-bytes N`` adds the pinned-host KV tier under the prefix
cache (evictions spill, later hits reload — `engine.kv_connector`);
``--roles prefill,decode`` disaggregates the gateway into one engine per
role on disjoint submeshes, with finished prompts' KV handed from the
prefill replica to a decode replica through the connector:

  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
      --smoke --devices 8 --roles prefill,decode --prefix-cache \
      --host-tier-bytes 268435456 --requests 8

``--legacy`` keeps the pre-engine static-batch greedy path (one fixed batch,
capacity-sized contiguous cache) — with the decode step compiled ONCE before
the token loop, not per token.

**HTTP front-end mode** (``--http``) starts the process-separated
``repro.frontend`` stack instead of running a canned workload: ``--workers``
engine processes (the device count is split evenly across them; ``0`` keeps
a single in-process replica), an async HTTP/SSE server streaming tokens
per request, priority classes (``--priority-classes``, highest first) with
optional per-class preemption (``--preempt``) and SLO-priced admission
(``--slo-ttft-ms``). SIGTERM drains gracefully: in-flight streams finish,
host-tier spills flush, workers join.

  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
      --smoke --devices 2 --workers 2 --http --port 8080 \
      --max-slots 2 --page-size 4 --max-len 64
"""

import argparse
import os


def _legacy_main(args, plan, cfg):
    """Static-batch greedy decode (pre-engine path, compile hoisted)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import ShapeConfig
    from repro.models.factory import build_model
    from repro.serve import kv_cache, step as serve_step

    model = build_model(cfg)
    run_cfg = plan.run_config()
    mesh = plan.build_mesh()
    sp = plan.sp_size

    capacity = args.prompt_len + args.gen
    capacity = ((capacity + sp - 1) // sp) * sp  # pad to SP multiple

    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size, jnp.int32)

    # prefill at prompt length (its own SP-divisible length), then copy the
    # prefix of each shard-sharded cache into the capacity-sized cache
    shape_p = ShapeConfig("serve", seq_len=args.prompt_len,
                          global_batch=args.batch, kind="prefill")
    jprefill, _ = serve_step.build_prefill_step(model, mesh, run_cfg, shape_p)
    batch = {"tokens": tokens}
    if cfg.frontend_stub is not None:
        batch["frontend_emb"] = jnp.zeros(
            (args.batch, args.prompt_len, cfg.d_model),
            jnp.dtype(cfg.param_dtype))
    tok, cache_p = jprefill(params, batch)

    # expand attention caches to capacity (host-side, example-scale)
    cache = kv_cache.init_cache(cfg, args.batch, capacity)
    def merge(dst, src):
        out = {}
        for k in dst:
            if isinstance(dst[k], dict):
                out[k] = merge(dst[k], src[k])
            elif dst[k].ndim >= 3 and dst[k].shape[2] == capacity:
                pad = np.zeros(dst[k].shape, dst[k].dtype)
                pad[:, :, :src[k].shape[2]] = np.asarray(src[k])
                out[k] = jnp.asarray(pad)
            else:
                out[k] = src[k]
        return out
    cache = {"stack": merge(cache["stack"], cache_p["stack"])}

    # compile ONCE (static capacity-1 cache_len), then loop the executable
    shape_d = ShapeConfig("serve", seq_len=capacity,
                          global_batch=args.batch, kind="decode")
    jdecode, _ = serve_step.build_decode_step(model, mesh, run_cfg, shape_d)
    generated = [np.asarray(tok)]
    for _ in range(args.gen - 1):
        # NOTE example-scale: cache_len is static per compile; the engine
        # path passes per-sequence lengths as traced operands instead.
        tok, cache = jdecode(params, cache, tok)
        generated.append(np.asarray(tok))
    out = np.concatenate(generated, axis=1)
    print(f"[serve --legacy] prompt {tokens.shape} -> generated {out.shape}:")
    print(out)
    return out


def _engine_main(args, plan, cfg, registry=None, tracer=None):
    import numpy as np

    from repro.engine import Engine, EngineConfig, Request
    from repro.models.factory import build_model

    model = build_model(cfg)
    engine = Engine(model, plan,
                    EngineConfig(pages_per_shard=args.pages_per_shard,
                                 prefill_chunk=args.prefill_chunk),
                    registry=registry, tracer=tracer)
    rng = np.random.default_rng(args.seed)
    vocab = engine.cfg.vocab_size
    reqs = []
    lo = max(1, args.prompt_len // 4)
    for i in range(args.requests):
        # staggered workload: prompt lengths spread evenly over
        # [prompt_len / 4, prompt_len], a --gen token budget each
        plen = lo + (args.prompt_len - lo) * i // max(args.requests - 1, 1)
        reqs.append(Request(
            uid=f"req{i}", tokens=rng.integers(0, vocab, plen).tolist(),
            max_new_tokens=args.gen, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p, seed=args.seed + i))
    for r in reqs:
        engine.add_request(r)
    out = engine.run()
    for r in reqs:
        print(f"[serve] {r.uid}: prompt_len={r.prompt_len} "
              f"-> {out[r.uid]}")
    stats = engine.metrics.to_dict()
    print("[serve] metrics: " + ", ".join(
        f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in sorted(stats.items())))
    return out


def _gateway_main(args, plan, cfg, registry=None, tracer=None, plans=None):
    import numpy as np

    from repro.engine import EngineConfig, Request
    from repro.gateway import Gateway
    from repro.models.factory import build_model
    from repro.plan import cost as plan_cost

    model = build_model(cfg)
    gw = Gateway(model, plan,
                 EngineConfig(pages_per_shard=args.pages_per_shard,
                              prefill_chunk=args.prefill_chunk),
                 registry=registry, tracer=tracer, plans=plans)
    rng = np.random.default_rng(args.seed)
    vocab = cfg.vocab_size
    sys_len = args.system_prompt_len
    # two request families with distinct shared system prompts: prefix-aware
    # routing steers each family to the replica holding its pages, so with
    # --replicas 2 both replicas serve and both tries hit
    shared = [rng.integers(0, vocab, sys_len).tolist() if sys_len else []
              for _ in range(2)]
    reqs = []
    for i in range(args.requests):
        tail = max(1, args.prompt_len // 2 + (i * 3) % (args.prompt_len + 1))
        gen = max(1, args.gen // 2 + i % (args.gen + 1))
        reqs.append(Request(
            uid=f"req{i}",
            tokens=shared[i % 2] + rng.integers(0, vocab, tail).tolist(),
            max_new_tokens=gen, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p, seed=args.seed + i))
    # each family is one "session" to exercise affinity too
    for i, r in enumerate(reqs):
        gw.add_request(r, session=f"sess{i % 2}" if sys_len else None)
        gw.step()           # stream as we go (prints drain incrementally)
    out = gw.run()
    for r in reqs:
        print(f"[gateway] {r.uid} (replica {gw._owner[r.uid]}): "
              f"prompt_len={r.prompt_len} -> {out[r.uid]}")
    stats = gw.stats()
    tier = stats.pop("host_tier")
    per = stats.pop("per_replica")
    print("[gateway] metrics: " + ", ".join(
        f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in sorted(stats.items())))
    for i, m in enumerate(per):
        print(f"[gateway]   replica {i} ({gw.roles[i]}): "
              f"tokens={m['tokens_out']} "
              f"hit_rate={m['prefix_hit_rate']:.3g} "
              f"occupancy={m['occupancy']:.3g}")
    if tier["enabled"]:
        tier.pop("per_replica")
        print("[gateway] host tier: " + ", ".join(
            f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in sorted(tier.items())))
    if plan.prefix_cache and sys_len:
        roi = plan_cost.prefix_cache_value(
            cfg, prompt_len=sys_len + args.prompt_len, shared_len=sys_len,
            requests=max(args.requests // plan.replicas, 2),
            sp=plan.sp_size, page_size=plan.page_size,
            pages_per_shard=args.pages_per_shard, max_len=args.gen)
        print(f"[gateway] analytical cache value/replica: "
              f"hit_rate~{roi['hit_rate']:.2f} "
              f"saved_tokens~{roi['saved_tokens']} "
              f"cache_pages={roi['cache_pages']} fits={roi['fits']}")
    return out


def _frontend_main(args, plan, cfg, registry=None, tracer=None):
    """JetStream-style process-separated serving: spawn ``--workers``
    engine processes behind the orchestrator, serve HTTP/SSE until
    SIGTERM, then drain."""
    import dataclasses

    from repro.engine import EngineConfig
    from repro.frontend.orchestrator import Orchestrator
    from repro.frontend.protocol import make_worker_spec
    from repro.frontend.server import run_server
    from repro.frontend.slo import SLOAdmission, parse_classes
    from repro.frontend.worker import LocalReplica, ProcReplica

    # each worker is a single-engine replica of the per-worker plan
    spec = make_worker_spec(
        plan=dataclasses.replace(plan, replicas=1),
        eng=EngineConfig(pages_per_shard=args.pages_per_shard,
                         prefill_chunk=args.prefill_chunk),
        init_seed=0, trace=bool(args.trace_out))
    workers = max(args.workers, 0)
    if workers:
        print(f"[serve] spawning {workers} worker processes "
              f"({plan.n_devices} devices each)...", flush=True)
        replicas = [ProcReplica(i, spec) for i in range(workers)]
    else:
        print("[serve] --workers 0: single in-process replica", flush=True)
        replicas = [LocalReplica(0, spec)]
    classes = parse_classes(args.priority_classes,
                            slo_ttft_ms=args.slo_ttft_ms,
                            budget_tokens=args.class_budget_tokens)
    slo = None
    if args.slo_ttft_ms > 0:
        slo = SLOAdmission(cfg, sp=plan.sp_size, page_size=plan.page_size,
                           decode_batch=plan.decode_batch,
                           kernel=plan.kernel_impl,
                           calibration=args.slo_calibration)
    orch = Orchestrator(replicas, classes=classes, slo=slo,
                        preempt=bool(args.preempt), registry=registry,
                        tracer=tracer)
    run_server(orch, host=args.host, port=args.port, worker_spec=spec,
               workers=workers)
    return {}


def _resolve_plan(args):
    """Returns ``(plan, plans, cfg)`` — ``plans`` is the per-role list in
    disaggregated mode (``--roles`` or a multi-plan json), else None."""
    import json

    from repro.configs import registry
    from repro.plan import (ExecutionPlan, make_role_plans, make_serve_plan)

    def _cfg_for(plan):
        if not plan.arch or plan.arch not in registry.ASSIGNED_ARCHS:
            raise SystemExit(
                f"[serve] plan {args.plan} names unknown arch "
                f"{plan.arch!r}; known: {sorted(registry.ASSIGNED_ARCHS)}")
        return registry.for_plan(plan)

    if args.plan:
        rec = json.loads(open(args.plan).read())
        if "plans" in rec:                      # disaggregated role plans
            plans = [ExecutionPlan.from_dict(d) for d in rec["plans"]]
            plan = plans[0]
            print(f"[serve] loaded {len(plans)} role plans {args.plan}: "
                  f"roles={[p.role for p in plans]} "
                  f"host_tier={plan.host_tier_bytes}")
            return plan, plans, _cfg_for(plan)
        plan = ExecutionPlan.load(args.plan)
        print(f"[serve] loaded plan {args.plan}: scheme={plan.scheme} "
              f"C={plan.c} R={plan.r} kernel={plan.kernel_impl} "
              f"slots={plan.decode_batch} page={plan.page_size} "
              f"replicas={plan.replicas} prefix_cache={plan.prefix_cache} "
              f"host_tier={plan.host_tier_bytes}")
        return plan, None, _cfg_for(plan)
    import jax

    cfg = registry.get_smoke(args.arch) if args.smoke else registry.get(args.arch)
    # the plan spans the devices this process sees (TPU chips, or forced
    # host devices); with --replicas/--roles its n_devices is the
    # per-replica share of them
    if args.roles:
        roles = [r.strip() for r in args.roles.split(",") if r.strip()]
        n_dev = len(jax.devices()) // len(roles)
        plans = make_role_plans(
            cfg, roles=roles, n_devices=n_dev, arch=args.arch,
            data=args.data, c=args.c, decode_batch=args.max_slots,
            page_size=args.page_size, max_len=args.max_len,
            kernel_impl=args.kernel, prefix_cache=bool(args.prefix_cache),
            host_tier_bytes=args.host_tier_bytes)
        return plans[0], plans, cfg
    replicas = max(args.replicas, 1)
    n_dev = len(jax.devices()) // replicas
    plan = make_serve_plan(
        cfg, arch=args.arch, n_devices=n_dev, data=args.data,
        c=args.c, decode_batch=args.max_slots, page_size=args.page_size,
        max_len=args.max_len, kernel_impl=args.kernel, replicas=replicas,
        prefix_cache=bool(args.prefix_cache), host_tier_bytes=args.host_tier_bytes)
    return plan, None, cfg


def main(argv=None):
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture id (optional with --plan)")
    ap.add_argument("--smoke", action="store_true",
                    help="the registry's reduced config (CPU-runnable)")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--c", type=int, default=None,
                    help="StarTrail C (default: cost-model pick)")
    ap.add_argument("--plan", default=None,
                    help="load a persisted serve ExecutionPlan json")
    ap.add_argument("--save-plan", default=None,
                    help="persist the resolved serve plan to this path")
    ap.add_argument("--kernel", default=None, choices=["ref", "pallas"],
                    help="paged-decode kernel (default: backend pick — "
                         "pallas on TPU, ref on CPU)")
    ap.add_argument("--legacy", action="store_true",
                    help="pre-engine static-batch greedy path")
    ap.add_argument("--batch", type=int, default=2, help="legacy batch size")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    # engine knobs
    ap.add_argument("--requests", type=int, default=8)
    # gateway knobs
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas (gateway mode when > 1); "
                         "--devices is split evenly across them")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="block-hash prefix cache with COW page reuse "
                         "(gateway mode)")
    ap.add_argument("--host-tier-bytes", type=int, default=0,
                    help="pinned-host KV tier capacity per engine, bytes "
                         "(0 = off; prefix-cache evictions spill here and "
                         "later trie hits reload instead of re-prefilling; "
                         "needs --prefix-cache)")
    ap.add_argument("--roles", default=None,
                    help="comma-separated replica roles for disaggregated "
                         "serving, e.g. 'prefill,decode' — one engine per "
                         "role on disjoint submeshes; overrides --replicas")
    ap.add_argument("--system-prompt-len", type=int, default=32,
                    help="shared prompt prefix length in gateway mode "
                         "(0 = fully independent prompts)")
    # HTTP front-end knobs (repro.frontend; --http switches modes)
    ap.add_argument("--http", action="store_true",
                    help="serve an async HTTP/SSE front end "
                         "(repro.frontend) instead of a canned workload")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--workers", type=int, default=0,
                    help="engine worker *processes* behind the front end "
                         "(--devices is split evenly across them; 0 = one "
                         "in-process replica)")
    ap.add_argument("--priority-classes", default="interactive,batch",
                    help="comma-separated priority classes, highest "
                         "first; classes after the first are preemptible")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="TTFT SLO for the highest class, priced at "
                         "admission from plan.cost (0 = no SLO gate)")
    ap.add_argument("--class-budget-tokens", type=int, default=0,
                    help="outstanding-token budget for the highest class "
                         "(0 = unlimited)")
    ap.add_argument("--slo-calibration", type=float, default=1.0,
                    help="scale analytical seconds to this machine "
                         "(measured_step_s / analytical_step_s)")
    ap.add_argument("--preempt", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="spill the worst preemptible stream when a "
                         "higher-priority request is stuck queued")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--pages-per-shard", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="split long prompts into ~this many tokens per "
                         "driver step (rounded up to a compile bucket), "
                         "interleaved with decode; 0 = monolithic prefill")
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    # observability (repro.obs; all off by default = near-zero overhead)
    ap.add_argument("--metrics-dump", default=None,
                    help="write the obs registry here after the run "
                         "(Prometheus text; .json suffix -> JSON dump)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace-format JSON timeline of "
                         "request/engine/gateway spans here")
    ap.add_argument("--comm-report", default=None,
                    help="compile the plan's attention island, parse its "
                         "HLO collectives, and write the measured-vs-"
                         "analytical comm-volume report (JSON) here")
    args = ap.parse_args(argv)
    if not args.plan and not args.arch:
        ap.error("--arch is required (unless --plan carries it)")
    if args.http and args.workers > 1:
        # the device count is split across worker processes exactly like
        # gateway replicas; the resolved plan is then per worker
        args.replicas = args.workers

    if args.plan and not args.devices:
        # a local-mesh plan records its forced-host device count; read it
        # from the raw json (before anything can initialise the backend).
        # n_devices is per replica — the gateway needs the product.
        import json

        rec = json.loads(open(args.plan).read())
        if "plans" in rec:                      # disaggregated role plans
            if rec["plans"] and rec["plans"][0].get("mesh_kind") == "local":
                args.devices = sum(int(d["n_devices"]) for d in rec["plans"])
        else:
            rec = rec.get("plan", rec)
            if rec.get("mesh_kind") == "local":
                args.devices = \
                    int(rec["n_devices"]) * int(rec.get("replicas", 1))
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    if args.http and args.workers > 0:
        import jax

        if jax.default_backend() == "tpu":
            # this process now holds the chips, and a spawned worker could
            # not get one: serve them from in-process replicas instead
            raise SystemExit(
                "[serve] --workers spawns engine processes, but a TPU chip "
                "belongs to one process and this one holds them; serve "
                "in-process replicas instead: --workers 0 for one, or the "
                "gateway's --replicas N")

    plan, plans, cfg = _resolve_plan(args)
    print(f"[serve] plan: P_sp={plan.sp_size} scheme={plan.scheme} "
          f"C={plan.c} R={plan.r} data={plan.data} "
          f"block_impl={plan.block_impl} "
          f"kernel={plan.kernel_impl} slots={plan.decode_batch} "
          f"page={plan.page_size} capacity={plan.seq_len} "
          f"replicas={len(plans) if plans else plan.replicas} "
          f"roles={[p.role for p in plans] if plans else [plan.role]} "
          f"prefix_cache={plan.prefix_cache} "
          f"host_tier={plan.host_tier_bytes}")
    if args.save_plan:
        if plans:
            import json as _json
            import pathlib

            path = pathlib.Path(args.save_plan)
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(_json.dumps(
                {"plans": [p.to_dict() for p in plans]}, indent=2))
        else:
            path = plan.save(args.save_plan)
        print(f"[serve] plan saved -> {path}")

    from repro import obs

    registry = obs.Registry()
    tracer = obs.Tracer(enabled=bool(args.trace_out))
    if args.http:
        out = _frontend_main(args, plan, cfg, registry=registry,
                             tracer=tracer)
    elif args.legacy:
        out = _legacy_main(args, plan, cfg)
    elif plans or plan.replicas > 1 or plan.prefix_cache:
        out = _gateway_main(args, plan, cfg, registry=registry,
                            tracer=tracer, plans=plans)
    else:
        out = _engine_main(args, plan, cfg, registry=registry,
                           tracer=tracer)

    if args.metrics_dump:
        fmt = "json" if args.metrics_dump.endswith(".json") else "prometheus"
        registry.dump(args.metrics_dump, fmt=fmt)
        print(f"[serve] metrics dump -> {args.metrics_dump} ({fmt})")
    if args.trace_out:
        tracer.dump(args.trace_out)
        print(f"[serve] trace ({len(tracer.events())} events) -> "
              f"{args.trace_out}")
    if args.comm_report:
        from repro.obs import commlog

        rep = commlog.comm_report(cfg, plan)
        commlog.dump_report(rep, args.comm_report)
        ratios = {k: v["ratio"] for k, v in rep["per_collective"].items()}
        print(f"[serve] comm report -> {args.comm_report} "
              f"within_tolerance={rep['within_tolerance']} ratios={ratios}")
    return out


if __name__ == "__main__":
    main()
