"""Training driver: the program's compiled training step, fed and paced as
``repro.train.trainer.train`` does, over a sequence no one chip trains.

Set-up builds one object: the plan the traffic file fixes (checked field
by field once resolved), the program's jitted step
(``ExecutionPlan.build_train_step``) with its weights (the benchmark's,
``reference.init_params``, made on the device in the plan's layout) and
AdamW state, and the trainer's feed: the program's ``Prefetcher`` over
the benchmark's batches (``train_workload``), laid out along the sequence
as the plan asks. Set-up drives that object through its first
``check.steps`` steps, which compile it (or load it from the cache) and
are the steps the check follows: after the first the benchmark reads each
weight's gradient norm from the optimizer's first moment, after the last
each weight's change from the seed's weights. The window then drives the
same object on: steps are dispatched one ahead, each waiting on the
previous step's loss, as the trainer's loop does, and the window closes
on the first step that ends past ``--seconds``, with the metrics of
every step read back (the trainer's flush, a host sync).

``train_tokens_per_s`` is every token of every step in the window over
its wall time. After the window the program's arrays are freed and the
reference (``reference_train``) follows the same steps on the same
chips; ``reference_train.gaps`` gives the numbers compared.

``fault`` (tests and calibration only) is handed the ``Trainer`` before
set-up drives it, and may replace its ``step_fn``.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List

import numpy as np

from bench import harness, reference, reference_train, train_workload


def adam(tr: Dict) -> reference_train.Adam:
    return reference_train.Adam(**tr["optimizer"])


class Trainer:
    """The program's step, state and feed, as ``trainer.train`` holds
    them, and the loop that drives them."""

    def __init__(self, cell, seed: int, devs, tracer=None):
        import jax

        from repro import obs
        from repro.configs.base import ShapeConfig
        from repro.core import zigzag
        from repro.data.pipeline import Prefetcher
        from repro.launch.train import device_plan
        from repro.models.factory import build_model
        from repro.optim import adamw

        self.cell = cell
        conf, tr = cell.conf, cell.traffic
        cfg = harness.model_config(conf)
        self.seq_len, self.batch = int(tr["seq_len"]), int(tr["global_batch"])
        shape = ShapeConfig(f"seq{self.seq_len}", seq_len=self.seq_len,
                            global_batch=self.batch, kind="train")
        want = tr["plan"]
        plan = device_plan(cfg, shape, arch=conf["arch"],
                           scheme=want["scheme"], c=want["c"],
                           placement=want["placement"],
                           microbatches=int(tr["microbatches"]))
        got = {"scheme": plan.scheme, "c": plan.c, "r": plan.r,
               "sp_size": plan.sp_size, "placement": plan.placement,
               "seq_scheme": plan.seq_scheme}
        wrong = {k: (got[k], v) for k, v in want.items() if got[k] != v}
        if devs[0].platform == "tpu" and plan.block_impl != tr["kernels"]:
            wrong["block_impl"] = (plan.block_impl, tr["kernels"])
        if wrong:
            raise RuntimeError(f"the plan resolved otherwise than the traffic "
                               f"file fixes (got, wanted): {wrong}")
        self.plan = plan
        model = build_model(cfg)
        adam_cfg = adamw.AdamWConfig(**tr["optimizer"])
        mesh = plan.build_mesh()
        self.step_fn, sh = plan.build_train_step(model, adam_cfg, mesh=mesh)
        self.batch_sharding = sh["batch"]
        self.params = reference.nest(reference.init_params(
            reference.Arch.from_config(conf), seed,
            reference.flatten(sh["params"])))
        self.opt = jax.jit(functools.partial(adamw.init_state, cfg=adam_cfg),
                           out_shardings=sh["opt"])(self.params)
        sp = int(np.prod([mesh.shape[a] for a in sh["rt"].sp_axes]))
        self.perm = zigzag.make_positions(self.seq_len, sp,
                                          plan.seq_scheme).reshape(-1)
        self.prefetch = Prefetcher(train_workload.Source(
            seed, self.batch, self.seq_len, cfg.vocab_size, self.perm))
        self.tracer = tracer or obs.NULL_TRACER
        self.steps = 0
        self.prev = None
        self.pending: List[Dict] = []
        self.step_s: List[float] = []

    def step(self) -> None:
        """One step as the trainer's loop makes it: the next batch onto
        the devices, the step dispatched, then a wait on the previous
        step's loss."""
        import jax

        n = self.steps + 1
        t0 = time.perf_counter()
        with self.tracer.span("train/data", cat="train", step=n):
            _, b = self.prefetch.next()
            batch = jax.device_put(b, self.batch_sharding)
        with self.tracer.span("train/step", cat="train", step=n):
            self.params, self.opt, m = self.step_fn(self.params, self.opt,
                                                    batch)
            if self.prev is not None:
                jax.block_until_ready(self.prev)
        self.prev = m["loss"]
        self.pending.append(m)
        self.steps = n
        self.step_s.append(time.perf_counter() - t0)

    def flush(self) -> List[Dict[str, float]]:
        """Every pending step's metrics, read back (waits for the last)."""
        out = [{k: float(v) for k, v in m.items()} for m in self.pending]
        self.pending = []
        return out

    def close(self) -> None:
        self.prefetch.stop()
        self.prefetch.thread.join(timeout=5)
        self.params = self.opt = self.prev = None
        self.pending = []


def first_moment_norms(opt) -> Dict[str, float]:
    import jax

    mu = reference.flatten(opt["mu"])
    return {n: float(v) for n, v in
            jax.jit(reference_train.leaf_norms)(mu).items()}


def change_norms(params, a: reference.Arch, seed: int) -> Dict[str, float]:
    """Each weight's 2-norm distance from the seed's weights, one weight
    at a time, in the weight's own layout."""
    import jax
    import jax.numpy as jnp

    flat = reference.flatten(params)
    layout = reference.param_layout(a)
    names = list(layout)
    out = {}
    for name, w in flat.items():
        shape, init, std = layout[name]
        i = names.index(name)

        # reference.init_params' rule for this one weight
        def dist(w, key, i=i, shape=shape, init=init, std=std):
            if init == "ones":
                w0 = jnp.ones(shape, w.dtype)
            else:
                z = jax.random.normal(jax.random.split(key, len(names))[i],
                                      shape, jnp.float32)
                z = jax.lax.optimization_barrier(z)
                w0 = (z * std).astype(w.dtype)
            d = w.astype(jnp.float32) - w0.astype(jnp.float32)
            return jnp.sqrt(jnp.sum(jnp.square(d)))

        out[name] = float(jax.jit(dist)(w, jax.random.PRNGKey(seed)))
    return out


def collective_seconds(trace_dir, n_devices: int) -> Dict[str, float]:
    """Device seconds per collective opcode in the traced window, summed
    over the devices (for the notes: the breakdown keeps ten ops)."""
    from bench import overlap

    files = sorted(trace_dir.rglob("*.xplane.pb"))
    if not files:
        return {}
    _, devs = overlap.device_ops(files[-1], n_devices)
    out: Dict[str, float] = {}
    for ops in devs:
        for s, e, hlo in ops:
            if overlap.is_collective(hlo):
                op = overlap.opcode(hlo)
                out[op] = out.get(op, 0.0) + (e - s) * 1e-9
    return out


def program_reading(losses: List[float], grad_norm: float, mu: Dict,
                    change: Dict, tr: Dict) -> Dict:
    """The program's side of the comparison, shaped like
    ``reference_train.run``'s: the raw first gradient of each weight is
    its first moment after one step over (1 - b1) and the clip scale the
    step applied."""
    opt = tr["optimizer"]
    clip = opt["grad_clip"]
    scale = 1.0 if clip is None else min(1.0, clip / max(grad_norm, 1e-9))
    return {"losses": losses, "grad_norm": grad_norm, "change": change,
            "grad": {n: v / ((1 - opt["b1"]) * scale)
                     for n, v in mu.items()}}


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True, fault=None, calibrate: bool = False
        ) -> Dict:
    import jax
    from jax.profiler import TraceAnnotation

    from bench import counts
    from repro import obs

    tr = cell.traffic
    devs = harness.devices(cell.chips, require_chip)
    tracer = obs.Tracer(enabled=True, annotate=True) if trace else None
    t = Trainer(cell, seed, devs, tracer)
    if fault is not None:
        fault(t)
    arch = reference.Arch.from_config(cell.conf)
    k = int(tr["check"]["steps"])

    # the checked steps, through the window's own loop and feed
    t.step()
    first = t.flush()
    mu = first_moment_norms(t.opt)
    for _ in range(k - 1):
        t.step()
    checked = first + t.flush()
    change = change_norms(t.params, arch, seed)
    prog = program_reading([m["loss"] for m in checked],
                           checked[0]["grad_norm"], mu, change, tr)

    traced = float(tr["trace_seconds"]) if trace else 0.0
    trace_dir = cell.run_dir() / f"trace-{seed}" if trace else None
    if trace:
        harness.start_trace(trace_dir)
    t0 = time.perf_counter()
    n0 = t.steps
    window: List[Dict[str, float]] = []
    facts = None
    collectives = None
    if trace:
        with TraceAnnotation("bench/window"):
            while time.perf_counter() - t0 < traced:
                t.step()
            window += t.flush()
        t_traced = time.perf_counter() - t0
        jax.profiler.stop_trace()
        tokens = (t.steps - n0) * t.batch * t.seq_len
        fl = counts.flash_train_counts(cell.conf, t.seq_len, t.batch)
        chips = len(devs)
        facts = {"kind": "train", "conf": cell.conf, "chips": chips,
                 "trace_dir": str(trace_dir),
                 "window_s": t_traced, "steps": t.steps - n0,
                 "tokens": tokens,
                 "step_flops": counts.train_step_flops(cell.conf, t.seq_len,
                                                       t.batch),
                 "flash_per_chip": {
                     kind: {q: v / chips for q, v in c.items()}
                     for kind, c in fl.items()}}
    while time.perf_counter() - t0 < seconds:
        t.step()
    window += t.flush()
    t1 = time.perf_counter()
    steps = t.steps - n0
    window_s = t1 - t0
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    step_ms = [1e3 * x for x in t.step_s[n0:]]
    plan = {f: getattr(t.plan, f) for f in (
        "scheme", "c", "r", "sp_size", "placement", "seq_scheme",
        "block_impl", "remat", "microbatches", "comm_chunks")}
    e2e = {"train_tokens_per_s": steps * t.batch * t.seq_len / window_s}
    if trace:
        collectives = collective_seconds(trace_dir, len(devs))
    t.close()
    del t
    harness.free_device_memory()

    batches = [train_workload.batch(seed, i, int(tr["global_batch"]),
                                    int(tr["seq_len"]),
                                    arch.vocab_size) for i in range(k)]
    qblock = int(tr["check"]["qblock"])
    ref = reference_train.run(arch, seed, adam(tr), batches, devs,
                              qblock=qblock)
    gaps = reference_train.gaps(prog, ref)
    limits = tr["limits"]
    checks = {name: (gaps[name], limits[name]) for name in gaps}
    readings = {}
    if calibrate:
        ctl = reference_train.run(arch, seed, adam(tr), batches, devs,
                                  qblock=qblock, precision="fp8")
        readings = {"program": gaps, "control": reference_train.gaps(ctl,
                                                                     ref),
                    "control_seconds": ctl["seconds"]}
    return {"setup_s": t0 - t_start, "e2e": e2e, "attempted": steps,
            "failed": 0, "checks": checks,
            "memory_peak_bytes": max(peaks), "window_s": window_s,
            "trace_dir": trace_dir, "facts": facts,
            "notes": {"plan": plan, "window_steps": steps,
                      "step_ms_median": harness.percentile(step_ms, 50),
                      "step_ms_slowest": sorted(step_ms)[-3:],
                      "checked_losses": prog["losses"],
                      "reference_losses": ref["losses"],
                      "leaf_gaps": reference_train.leaf_gaps(prog, ref),
                      "grad_norm": prog["grad_norm"],
                      "reference_grad_norm": ref["grad_norm"],
                      "window_losses": [m["loss"] for m in window],
                      "window_grad_norms": [m["grad_norm"] for m in window],
                      "memory_peak_bytes_per_device": peaks,
                      "reference_seconds": ref["seconds"],
                      **({"collective_s": collectives} if trace else {}),
                      **({"readings": readings} if calibrate else {})}}
