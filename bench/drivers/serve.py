"""Serving driver: a closed loop of clients over the program's ``Engine``.

Set-up builds the engine on the seed's weights, warms every prefill bucket
and decode width the run's requests reach (and no other), resets the
engine, sends each client's first request (which starts mid-stream, see
``workload.serve_requests``) and runs the engine until every one of them
is decoding. The window then drives ``Engine.step()``: each
(uid, token) pair it returns is stamped with the host clock when the step
returns; a client whose request has all its tokens sends its next one at
once. The window closes on the first step that ends past ``--seconds``.

Every emitted token in the window counts towards ``serve_tokens_per_s``;
every gap between two tokens of one request that ends in the window is an
inter-token sample (``itl_p95_ms``).

After the window: the engine is freed, and the reference runs over a
sample of the served requests drawn from the seed, the longest among
them (``sample_served``), and reads the widest gap by which a served
token's logit lies below the reference's best at its position.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from bench import harness, reference, workload


def build(cell, seed: int, devs, tracer=None):
    from repro.engine.engine import Engine, EngineConfig
    from repro.models.factory import build_model
    from repro.plan import make_serve_plan

    import jax
    from jax.sharding import NamedSharding

    conf, tr = cell.conf, cell.traffic
    cfg = harness.model_config(conf)
    model = build_model(cfg)
    plan = make_serve_plan(
        cfg, arch=conf["arch"], n_devices=len(devs), c=1,
        decode_batch=int(tr["max_slots"]), page_size=int(tr["page_size"]),
        max_len=int(tr["max_len"]), kernel_impl=tr.get("kernels"),
        block_impl=tr.get("kernels"))
    mesh = plan.build_mesh()
    pages = int(tr["pool_pages"])
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            model.partition(plan.sharding_rules))
    params = reference.nest(reference.init_params(
        reference.Arch.from_config(conf), seed,
        reference.flatten(param_sh)))
    eng = Engine(model, plan, EngineConfig(
        pages_per_shard=pages // plan.sp_size,
        prefill_chunk=int(tr.get("prefill_chunk", 0))),
        params=params, mesh=mesh, tracer=tracer, seed=seed)
    return eng


def warm_up(eng, reqs: List[workload.Req]) -> Dict:
    """Compile every prefill bucket and decode width the run's requests
    reach: one request per prefill bucket, as long as the shortest prompt
    in it, then each decode width on an idle batch. The engine is reset
    afterwards."""
    from repro.engine.scheduler import Request, bucket_pow2

    shortest: Dict[int, int] = {}
    for r in reqs:
        b = eng._prefill_bucket(len(r.prompt))
        shortest[b] = min(shortest.get(b, len(r.prompt)), len(r.prompt))
    for b, p in sorted(shortest.items()):
        eng.add_request(Request(uid=f"warm{b}", tokens=[1] * p,
                                max_new_tokens=2))
    eng.run()
    sched = eng.scheduler
    ps, sp = eng.eng.page_size, eng.sp
    lo = min(len(r.prompt) for r in reqs)
    hi = max(len(r.prompt) + r.max_new for r in reqs)
    widths = sorted({min(bucket_pow2(math.ceil((c // ps + 1) / sp)),
                         sched.table_width)
                     for c in range(lo, hi)})
    B = eng.eng.max_slots
    for w in widths:
        fn = eng._decode_fn(w, False)
        _, eng.pools = fn(
            eng.params, eng.pools, np.zeros((B, 1), np.int32),
            np.zeros((B,), np.int32),
            np.ascontiguousarray(sched.table[:, :, :w]),
            np.zeros((B,), np.float32), np.zeros((B,), np.int32),
            np.ones((B,), np.float32), np.zeros((B, 2), np.uint32),
            np.zeros((B,), bool))
    eng.reset()
    return {"prefill_buckets": sorted(shortest), "decode_widths": widths,
            "compiles": eng.xla_compiles()}


class Loop:
    """The closed loop's clients and the samples they see."""

    def __init__(self, eng, reqs: List[workload.Req]):
        self.eng, self.reqs, self.next = eng, reqs, 0
        self.recs: Dict[str, Dict] = {}
        self.failed = 0
        self.t0 = None
        self.gaps: List[float] = []
        self.tokens = 0
        self.prefill_lens: List[int] = []
        self.decode_rows: List[List[int]] = []
        self.step_s: List[float] = []
        self.prefill_steps = 0

    def submit(self) -> None:
        from repro.engine.scheduler import Request

        if self.next >= len(self.reqs):
            raise RuntimeError("the traffic file holds too few requests for "
                               "this window; raise its 'requests'")
        r = self.reqs[self.next]
        self.next += 1
        rej = self.eng.add_request(Request(uid=r.uid, tokens=r.prompt,
                                           max_new_tokens=r.max_new))
        if rej is not None:
            self.failed += 1
            return
        self.recs[r.uid] = {"req": r, "first": None,
                            "last": None, "out": []}

    def step(self) -> None:
        eng = self.eng
        t = time.perf_counter()
        emitted = eng.step()
        now = time.perf_counter()
        measuring = self.t0 is not None
        rows = []
        if measuring:
            self.step_s.append(now - t)
            self.prefill_steps += bool(eng.last_step_prefills)
            self.prefill_lens.extend(e - s for s, e in
                                     eng.last_step_prefills)
        for uid, tok in emitted:
            rec = self.recs[uid]
            if rec["first"] is None:
                rec["first"] = now
            elif measuring:
                self.gaps.append(now - rec["last"])
                # a decode row: the previous token sat at this position
                rows.append(len(rec["req"].prompt) + len(rec["out"]) - 1)
            rec["last"] = now
            rec["out"].append(int(tok))
            if measuring:
                self.tokens += 1
            if len(rec["out"]) == rec["req"].max_new:
                rec["done"] = now
                self.submit()
        if rows:
            self.decode_rows.append(rows)

    def snapshot(self) -> Dict:
        """What the window has counted so far (for a traced part)."""
        return {"decode_rows": list(self.decode_rows),
                "prefill_lens": list(self.prefill_lens),
                "tokens": self.tokens}


def sample_served(recs: List[Dict], check: Dict, seed: int) -> List[Dict]:
    """The requests whose served tokens are checked: the one with the
    longest context of all that served a token (every token it streamed
    is final, finished or not), then others drawn from the seed until
    ``min_tokens`` served tokens or ``max_requests``: from the finished
    requests, or, where they hold fewer than ``min_tokens`` served
    tokens, from all."""
    served = [r for r in recs if r["out"]]
    if not served:
        return []
    key = lambda r: (len(r["req"].prompt) + len(r["out"]), r["req"].uid)
    longest = max(served, key=key)
    done = [r for r in served if "done" in r]
    pool = done if sum(len(r["out"]) for r in done) >= int(
        check["min_tokens"]) else served
    rest = sorted((r for r in pool if r is not longest), key=key)
    picked = [longest]
    rng = np.random.Generator(np.random.Philox(key=[seed, 7]))
    for i in rng.permutation(len(rest)):
        if (len(picked) >= int(check["max_requests"])
                or sum(len(r["out"]) for r in picked)
                >= int(check["min_tokens"])):
            break
        picked.append(rest[i])
    return picked


def past_window(positions, window) -> int:
    """How many of the decode rows at ``positions`` have keys outside the
    sliding window."""
    return 0 if window is None else sum(p >= window for p in positions)


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True, fault=None, calibrate: bool = False
        ) -> Dict:
    import jax
    from jax.profiler import TraceAnnotation

    from repro import obs

    tr = cell.traffic
    if not tr["greedy"]:
        raise ValueError("the serving check compares greedy tokens only")
    devs = harness.devices(cell.chips, require_chip)
    tracer = obs.Tracer(enabled=True, annotate=True) if trace else None
    eng = build(cell, seed, devs, tracer)
    if fault is not None:
        fault(eng)
    vocab = cell.conf["vocab_size"]
    reqs = workload.serve_requests(tr, vocab, seed)
    warm = warm_up(eng, reqs)
    loop = Loop(eng, reqs)
    for _ in range(int(tr["clients"])):
        loop.submit()
    while eng.scheduler.queue or any(
            st.cache_len == 0 for st in eng.scheduler.active()):
        loop.step()

    # a traced run traces the first ``trace_seconds`` of the same window,
    # and its facts are counted over that part alone
    traced = float(tr["trace_seconds"]) if trace else 0.0
    trace_dir = cell.run_dir() / f"trace-{seed}" if trace else None
    if trace:
        harness.start_trace(trace_dir)
    m0 = eng.metrics.to_dict()
    compiles0 = eng.xla_compiles()
    t0 = time.perf_counter()
    loop.t0 = t0
    facts_at = None
    if trace:
        with TraceAnnotation("bench/window"):
            while time.perf_counter() - t0 < traced:
                loop.step()
        facts_at = (time.perf_counter() - t0, loop.snapshot(),
                    eng.metrics.to_dict())
        jax.profiler.stop_trace()
    while time.perf_counter() - t0 < seconds:
        loop.step()
    t1 = time.perf_counter()
    window_s = t1 - t0
    m1 = eng.metrics.to_dict()
    compiles = [b - a for a, b in zip(compiles0, eng.xla_compiles())]
    peak = harness.memory_peak(devs)
    recs = list(loop.recs.values())
    attempted, failed = loop.next, loop.failed
    del eng, loop.eng
    harness.free_device_memory()

    check = tr["check"]
    picked = sample_served(recs, check, seed)
    arch = reference.Arch.from_config(cell.conf)
    gaps = reference.served_gaps(
        arch, seed, [(r["req"].prompt, r["out"]) for r in picked])
    limits = tr["limits"]
    served_gap = max(gaps) if gaps else float("inf")
    checks = {"served_gap": (served_gap, limits["served_gap"])}
    readings = {}
    if calibrate:
        # the control: the fp8 reference's own first choice at each position
        ctl = reference.served_gaps(
            arch, seed, [(r["req"].prompt, r["out"]) for r in picked],
            control=True)
        control = max(ctl) if ctl else float("inf")
        readings = {"served_gap": {
            "program": served_gap, "control": control,
            "control_fails": not control <= limits["served_gap"]}}
    conf = cell.conf
    window = conf.get("sliding_window")
    rows = [q for step in loop.decode_rows for q in step]
    checked_rows = [len(r["req"].prompt) + j - 1
                    for r in picked for j in range(1, len(r["out"]))]
    steps_ms = sorted(1e3 * x for x in loop.step_s)
    decode_steps = m1["decode_steps"] - m0["decode_steps"]
    facts = None
    if trace:
        t_traced, snap, mt = facts_at
        facts = {"kind": "serve", "conf": conf, "chips": len(devs),
                 "window_s": t_traced, **snap,
                 "decode_steps": mt["decode_steps"] - m0["decode_steps"],
                 "occupancy_sum": mt["occupancy_sum"] - m0["occupancy_sum"]}
    e2e = {"serve_tokens_per_s": loop.tokens / window_s,
           "itl_p95_ms": 1e3 * harness.percentile(loop.gaps, 95)}
    return {"setup_s": t0 - t_start, "e2e": e2e, "attempted": attempted,
            "failed": failed, "checks": checks,
            "memory_peak_bytes": peak, "window_s": window_s,
            "trace_dir": trace_dir, "facts": facts,
            "notes": {"gaps": len(loop.gaps), "tokens": loop.tokens,
                      "finished": sum("done" in r for r in recs),
                      "window_steps": len(steps_ms),
                      "prefill_steps": loop.prefill_steps,
                      "step_ms_median": harness.percentile(steps_ms, 50),
                      "step_ms_slowest": steps_ms[-6:],
                      "rows_past_window": past_window(rows, window)
                      / max(len(rows), 1),
                      "checked_requests": len(picked),
                      "checked_tokens": sum(len(r["out"]) for r in picked),
                      "checked_rows_past_window": past_window(checked_rows,
                                                             window),
                      "per_request_gap": gaps,
                      "compiles_in_window": compiles,
                      "warm": warm, "decode_steps": decode_steps,
                      **({"readings": readings} if calibrate else {})}}
