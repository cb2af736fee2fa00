"""Training loop: checkpoint/restart, straggler detection, metrics.

The loop is deliberately thin — all heavy lifting is in the jitted step —
but carries the production concerns: restore-on-start, periodic async
checkpoints, deterministic data resume, straggler watermark, and a jsonl
metrics stream. The run itself is described by an
``repro.plan.ExecutionPlan``: the trainer builds its mesh, runtime and
microbatching from the plan, never from hand-assembled pieces.

Metrics stay on-device between log boundaries: converting a jax scalar to
``float`` blocks the host on the step *and* transfers it, so the loop
buffers the (tiny, replicated) metric arrays and materialises them only on
``log_every`` / checkpoint boundaries and at exit — the jsonl stream still
carries every step, just written in batches (a hard kill can lose at most
the un-flushed tail; Python-level failures flush in ``finally``). The loop
still waits on the *previous* step before dispatching past it (a one-deep
async pipeline): the device keeps computing while the host prepares the
next batch, run-ahead stays bounded, and the straggler detector keeps
measuring real step durations rather than dispatch time.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import pathlib
import time
from typing import Dict, List, Optional, Tuple

import jax

from repro import obs
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.dist import checkpoint, elastic
from repro.models.factory import Model
from repro.optim import adamw
from repro.plan.plan import ExecutionPlan


@dataclasses.dataclass
class TrainerConfig:
    num_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    log_every: int = 10
    metrics_path: Optional[str] = None
    seed: int = 0


def train(model: Model, plan: ExecutionPlan, adam_cfg: adamw.AdamWConfig,
          tcfg: TrainerConfig, data_source=None, params=None,
          mesh=None, tracer: Optional[obs.Tracer] = None,
          registry: Optional[obs.Registry] = None) -> Dict:
    """Run the loop; returns final metrics. Restores from ckpt_dir if a
    checkpoint exists (fault-tolerant restart).

    ``tracer`` spans the loop phases (train/data, train/step, train/ckpt);
    ``registry`` additionally gets per-step analytical comm-volume
    counters (``obs.commlog.CommLog``). Both default to disabled/off.
    """
    tracer = tracer if tracer is not None else obs.NULL_TRACER
    mesh = mesh if mesh is not None else plan.build_mesh()
    shape = plan.shape_config()
    jstep, sh = plan.build_train_step(model, adam_cfg, mesh=mesh)
    rt = sh["rt"]
    sp_size = 1
    for a in rt.sp_axes:
        sp_size *= mesh.shape[a]

    if data_source is None:
        data_source = SyntheticLM(model.cfg, shape, seed=tcfg.seed,
                                  seq_scheme=rt.st_cfg.seq_scheme,
                                  sp_size=sp_size)

    start = 0
    # state is created in its sharded layout (no full copy on one device)
    if params is None:
        params = model.init_sharded(sh["params"], tcfg.seed)
    else:
        params = jax.device_put(params, sh["params"])
    opt = jax.jit(functools.partial(adamw.init_state, cfg=adam_cfg),
                  out_shardings=sh["opt"])(params)
    if tcfg.ckpt_dir:
        # common step across params + opt trees: a crash between the two
        # writes leaves them one step apart, and only a step present in
        # both is a consistent restore point
        last = checkpoint.latest_common_step(
            tcfg.ckpt_dir, pathlib.Path(tcfg.ckpt_dir) / "opt")
        if last is not None:
            params = checkpoint.restore(tcfg.ckpt_dir, last, params,
                                        sh["params"])
            opt = checkpoint.restore(
                pathlib.Path(tcfg.ckpt_dir) / "opt", last, opt, sh["opt"])
            start = last
            print(f"[trainer] restored step {last}")

    prefetch = Prefetcher(data_source, start_step=start)
    detector = elastic.StragglerDetector()
    metrics_f = open(tcfg.metrics_path, "a") if tcfg.metrics_path else None
    pending_ckpt = None
    last_metrics: Dict = {}
    commlog = None
    if registry is not None:
        from repro.obs.commlog import CommLog

        commlog = CommLog(registry, model.cfg, plan)
    # (step_i, on-device metrics, straggler flag, host phase timings)
    # buffered between flushes — float() conversion of the *device*
    # metrics is the only host sync in the loop; the phase timings are
    # plain perf_counter floats (the step phase measures dispatch + the
    # wait on the previous step's loss, i.e. the one-deep pipeline's
    # steady-state step duration shifted by one step — no extra sync)
    pending_metrics: List[Tuple[int, Dict, bool, Dict[str, float]]] = []

    def flush_metrics() -> Dict:
        nonlocal last_metrics
        for si, dev_m, straggling, phases in pending_metrics:
            m = {k: float(v) for k, v in dev_m.items()}
            if straggling:
                m["straggler_flag"] = 1.0
            last_metrics = {"step": si + 1, **m, **phases}
            if metrics_f:
                metrics_f.write(json.dumps(last_metrics) + "\n")
        if metrics_f and pending_metrics:
            metrics_f.flush()
        pending_metrics.clear()
        return last_metrics

    prev_loss = None
    try:
        for step_i in range(start, tcfg.num_steps):
            detector.step_start()
            t0 = time.perf_counter()
            with tracer.span("train/data", cat="train", step=step_i + 1):
                _, batch_np = prefetch.next()
                batch = jax.device_put(batch_np, sh["batch"])
            t1 = time.perf_counter()
            with tracer.span("train/step", cat="train", step=step_i + 1):
                params, opt, metrics = jstep(params, opt, batch)
                # one-deep pipeline: dispatch is async, so wait on the
                # *previous* step's (on-device, transfer-free) loss — the
                # device is already busy with this step, and the detector's
                # window sees real step durations (shifted by one step)
                if prev_loss is not None:
                    jax.block_until_ready(prev_loss)
            t2 = time.perf_counter()
            prev_loss = metrics["loss"]
            straggling = detector.step_end()
            if commlog is not None:
                commlog.record_step()
            phases = {"data_s": t1 - t0, "step_s": t2 - t1, "ckpt_s": 0.0}
            pending_metrics.append((step_i, metrics, straggling, phases))
            ckpt_boundary = (tcfg.ckpt_dir
                             and (step_i + 1) % tcfg.ckpt_every == 0)
            if ckpt_boundary:
                # before the boundary flush, so the launch cost (join the
                # previous pair + snapshot-to-host) lands in this step's
                # jsonl record
                t3 = time.perf_counter()
                with tracer.span("train/ckpt", cat="train",
                                 step=step_i + 1):
                    for t in pending_ckpt or ():
                        t.join()
                    # both writes async: save() snapshots to host in this
                    # thread before returning, and restore takes the latest
                    # step common to both trees, so a crash mid-write only
                    # costs the torn step, never consistency
                    pending_ckpt = [
                        checkpoint.save(tcfg.ckpt_dir, step_i + 1, params,
                                        blocking=False),
                        checkpoint.save(pathlib.Path(tcfg.ckpt_dir) / "opt",
                                        step_i + 1, opt, blocking=False),
                    ]
                phases["ckpt_s"] = time.perf_counter() - t3
            if ((step_i + 1) % tcfg.log_every == 0 or step_i == start
                    or ckpt_boundary or step_i + 1 == tcfg.num_steps):
                m = flush_metrics()
                if (step_i + 1) % tcfg.log_every == 0 or step_i == start:
                    print(f"[trainer] step {step_i + 1} "
                          f"loss={m['loss']:.4f} "
                          f"gnorm={m['grad_norm']:.3f}", flush=True)
    finally:
        prefetch.stop()
        flush_metrics()
        for t in pending_ckpt or ():
            t.join()
        if metrics_f:
            metrics_f.close()
    return last_metrics
