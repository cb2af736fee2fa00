"""The serving gateway: a multi-replica front end over ``repro.engine``.

One ``Gateway`` owns N ``Engine`` replicas, each on its own submesh slice
of the available devices (the replica's mesh shape — data x (C, R, C)
refinement — comes from one shared ``kind='decode'`` ``ExecutionPlan``
whose ``replicas``/``prefix_cache`` serving knobs this module consumes).
Requests enter through prefix-aware, load-aware routing with session
affinity (``gateway.router``), are served by the replicas' continuous
batching, and stream back per request: ``step()`` returns the (uid, token)
pairs emitted that tick and ``take(uid)`` drains a request's stream
incrementally, so callers can forward tokens while decode is still
running.

All replicas share one set of model parameters (initialised once, placed
per-replica by each engine's jits) and each runs its own prefix cache over
its own SP-sharded page pool — the router's job is to keep shared-prefix
traffic landing where its pages already are.

**Disaggregated serving** (``plans=[...]``): instead of N clones of one
plan, the gateway can run one engine per *role plan*
(`plan.make_role_plans`) on disjoint submeshes — ``role='prefill'``
replicas take new requests, run the prompt through prefill, emit the
first token and stop; the gateway then exports the prompt KV through the
replica's `engine.kv_connector` (device → host → device on the smoke
path; an RDMA fabric would replace the middle hop), injects it into the
least-loaded ``role='decode'`` replica and lets decode continue there.
The streams stay bit-identical to a unified replica because the decode
engine resumes from the exact pages the prefill wrote and sampling is
keyed by (seed, position), not by which engine draws
(``dist_checks.check_gateway_disagg`` proves this against the unified
baseline).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple, Union

import numpy as np

from repro import obs
from repro.engine import Engine, EngineConfig, Rejection, Request
from repro.gateway.router import Router


def submeshes(plans):
    """One refined ``(data, sp_grp, sp_ring, sp_team)`` mesh per plan,
    over disjoint slices of the local device list (each plan's
    ``n_devices`` is that replica's device count)."""
    import jax
    from jax.sharding import Mesh

    from repro.dist.sharding import SP_AXES

    if any(p.mesh_kind != "local" for p in plans):
        raise NotImplementedError(
            "multi-replica gateways currently build local (forced-host) "
            "meshes; production multi-host replicas are future work")
    devs = jax.devices()
    need = sum(p.n_devices for p in plans)
    if len(devs) < need:
        raise ValueError(
            f"gateway needs {need} devices for {len(plans)} replicas of "
            f"{[p.n_devices for p in plans]} but only {len(devs)} are "
            f"available")
    out, off = [], 0
    for p in plans:
        grid = np.array(devs[off:off + p.n_devices])
        off += p.n_devices
        grid = grid.reshape(p.data, p.c, p.r, p.c)
        out.append(Mesh(grid, ("data",) + SP_AXES))
    return out


def replica_meshes(plan, replicas: int):
    """One mesh per replica of a single shared plan (homogeneous case)."""
    return submeshes([plan] * replicas)


class Gateway:
    """add_request / step / take / collect driver over N engine replicas."""

    def __init__(self, model, plan, eng: EngineConfig = EngineConfig(),
                 params=None, registry: Optional[obs.Registry] = None,
                 tracer: Optional[obs.Tracer] = None, plans=None,
                 seed: int = 0):
        if plans:
            self.plans = list(plans)
            plan = plan if plan is not None else self.plans[0]
            key = {(p.page_size, p.decode_batch, p.seq_len, p.kernel_impl,
                    p.arch) for p in self.plans}
            if len(key) != 1:
                raise ValueError(
                    "disaggregated role plans must agree on page_size/"
                    "decode_batch/seq_len/kernel (the KV handoff is only "
                    f"bit-exact between identical engines); got {key}")
        else:
            replicas = max(int(getattr(plan, "replicas", 1)), 1)
            if getattr(plan, "role", "unified") != "unified":
                raise ValueError(
                    "a single-plan gateway is role='unified'; build one "
                    "plan per role (plan.make_role_plans) and pass "
                    "plans=[...] to disaggregate")
            self.plans = [plan] * replicas
        self.plan = plan
        self.replicas = len(self.plans)
        self.roles = [getattr(p, "role", "unified") for p in self.plans]
        # prefill/unified replicas take new requests; handoffs land on
        # decode replicas (or unified ones when none are dedicated)
        self._entry = [i for i, r in enumerate(self.roles)
                       if r in ("prefill", "unified")]
        self._decode_targets = \
            [i for i, r in enumerate(self.roles) if r == "decode"] or \
            [i for i, r in enumerate(self.roles) if r == "unified"]
        if not self._entry:
            raise ValueError("no prefill or unified replica to admit "
                             "requests")
        if "prefill" in self.roles and not self._decode_targets:
            raise ValueError("prefill replicas need a decode (or unified) "
                             "replica to hand finished prompts to")
        # one shared registry; replicas write the same metric families
        # under distinguishing {replica=i} labels
        self.registry = registry if registry is not None else obs.Registry()
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        if self.replicas == 1:
            meshes = [plan.build_mesh()]
        else:
            meshes = submeshes(self.plans)
        self.engines: List[Engine] = [
            Engine(model, p, eng, params, mesh=m,
                   registry=self.registry, labels={"replica": str(i)},
                   tracer=self.tracer, seed=seed)
            for i, (p, m) in enumerate(zip(self.plans, meshes))]
        self.cfg = self.engines[0].cfg
        self.router = Router(self.engines,
                             prefix_aware=bool(plan.prefix_cache),
                             eligible=self._entry)
        self._owner: Dict[str, int] = {}
        self._streams: Dict[str, List[int]] = {}
        self._cursor: Dict[str, int] = {}
        # disaggregation state: original request by uid while its 1-token
        # prefill twin runs, and uid -> decode replica after the handoff
        self._pending_handoff: Dict[str, Request] = {}
        self._handoff_dst: Dict[str, int] = {}
        self.handoffs = 0
        self.wall_s = 0.0
        self.max_steps = eng.max_steps
        self.draining = False

    # ---- request lifecycle ---------------------------------------------
    def add_request(self, req: Request, session: Optional[str] = None,
                    replica: Optional[int] = None) -> Union[int, Rejection]:
        """Route and enqueue; returns the replica index, or a typed
        :class:`Rejection` when admission fails (a draining gateway or an
        unserveable request — never a raise, so the HTTP layer can answer
        429/503 instead of 500). ``replica`` pins the choice (the
        benchmark replays recorded placements so cache-on and cache-off
        phases compare the same per-replica workloads)."""
        if self.draining:
            return Rejection("draining",
                             "gateway is draining: not accepting requests")
        if not self.router.live_eligible():
            return Rejection("no_live_replica",
                             "no live replica can admit requests",
                             retry_after_steps=1)
        with self.tracer.span("gateway/route", cat="gateway", uid=req.uid):
            i = self.router.route(req, session) if replica is None \
                else replica
        if replica is not None:
            self.router.routed[i] += 1
        if self.roles[i] == "prefill":
            # the prefill replica runs a 1-token twin; the original budget
            # and sampling state resume on the decode replica at handoff
            twin = dataclasses.replace(req, max_new_tokens=1, handoff=True)
            rej = self.engines[i].add_request(twin)
            if rej is not None:
                return rej
            self._pending_handoff[req.uid] = req
        else:
            rej = self.engines[i].add_request(req)
            if rej is not None:
                return rej
        self.registry.counter(
            "gateway_requests_routed_total",
            "Requests routed to each replica").inc(replica=str(i))
        self._owner[req.uid] = i
        self._streams[req.uid] = []
        self._cursor[req.uid] = 0
        return i

    def preempt(self, uid: str) -> Optional[Request]:
        """Evict ``uid`` from whichever replica holds it and return the
        resume request (``Engine.preempt`` semantics: re-admitting it —
        anywhere — continues the stream bit-identically)."""
        i = self._owner.get(uid)
        if i is None:
            return None
        return self.engines[i].preempt(uid)

    def _drain_handoffs(self) -> None:
        """Move every finished prefill-role prompt to a decode replica:
        export its KV pages to host, inject into the least-loaded decode
        target, release the prefill slot. Export strictly precedes
        release — releasing first could recycle the pages mid-copy."""
        for i, engine in enumerate(self.engines):
            if self.roles[i] != "prefill":
                continue
            for st in engine.take_handoffs():
                uid = st.req.uid
                orig = self._pending_handoff.pop(uid)
                with self.tracer.span("gateway/handoff", cat="gateway",
                                      uid=uid):
                    if orig.max_new_tokens <= 1:
                        # nothing left to decode; the prefill stream is
                        # already the whole response
                        engine.release_handoff(st)
                        continue
                    blocks = engine.export_kv(st)
                    j = min(self._decode_targets,
                            key=lambda k: (self.router.load(k), k))
                    self.engines[j].add_prefilled(orig, st.out[0], blocks)
                    engine.release_handoff(st)
                self._handoff_dst[uid] = j
                self._owner[uid] = j
                self.handoffs += 1
                self.registry.counter(
                    "gateway_handoffs_total",
                    "Prefill->decode KV handoffs").inc(replica=str(j))

    def step(self) -> List[Tuple[str, int]]:
        """One tick: step every replica with work; returns this tick's
        (uid, token) emissions (also appended to the per-request streams)."""
        t0 = time.monotonic()
        emitted: List[Tuple[str, int]] = []
        with self.tracer.span("gateway/step", cat="gateway"):
            for engine in self.engines:
                if not engine.idle():
                    emitted.extend(engine.step())
            self._drain_handoffs()
        for uid, tok in emitted:
            self._streams[uid].append(tok)
        self.wall_s += time.monotonic() - t0
        self.registry.gauge(
            "gateway_wall_seconds",
            "Host wall time spent inside gateway.step()").set(self.wall_s)
        return emitted

    def take(self, uid: str) -> List[int]:
        """Drain the tokens streamed for ``uid`` since the last take."""
        cur = self._cursor.get(uid, 0)
        out = self._streams.get(uid, [])[cur:]
        self._cursor[uid] = cur + len(out)
        return out

    def idle(self) -> bool:
        return all(e.idle() for e in self.engines)

    def run(self, max_steps: Optional[int] = None) -> Dict[str, List[int]]:
        limit = max_steps or self.max_steps
        n = 0
        while not self.idle():
            emitted = self.step()
            if not emitted and not any(
                    e.scheduler.active() or e.scheduler.prefilled
                    for e in self.engines):
                # nothing decoding and nothing admissible: eviction was
                # already tried, so no future step can make progress
                raise RuntimeError(
                    "gateway stalled: queued requests cannot be admitted "
                    "(pool exhausted by live sequences?)")
            n += 1
            if n > limit:
                raise RuntimeError(f"gateway did not drain in {limit} steps")
        return self.collect()

    def collect(self) -> Dict[str, List[int]]:
        out: Dict[str, List[int]] = {}
        for engine in self.engines:
            out.update(engine.collect())
        # a handed-off uid finishes on both sides: the prefill replica's
        # 1-token twin and the decode replica's full stream — the decode
        # side wins regardless of replica index order
        for uid, j in self._handoff_dst.items():
            done = self.engines[j].collect()
            if uid in done:
                out[uid] = done[uid]
        return out

    def reset(self) -> None:
        """Drop requests, pools and prefix caches on every replica; keep
        compiled fns and the router's affinity map cleared."""
        for engine in self.engines:
            engine.reset()
        self.router = Router(self.engines,
                             prefix_aware=bool(self.plan.prefix_cache),
                             eligible=self._entry)
        self._owner.clear()
        self._streams.clear()
        self._cursor.clear()
        self._pending_handoff.clear()
        self._handoff_dst.clear()
        self.handoffs = 0
        self.wall_s = 0.0
        self.draining = False

    def shutdown(self, drain: bool = True,
                 max_steps: Optional[int] = None) -> Dict[str, List[int]]:
        """Stop accepting requests and wind the gateway down.

        ``drain=True`` finishes every in-flight request first (same loop
        as :meth:`run`) and then flushes each replica's staged host-tier
        spills so nothing committed to the host tier is torn; ``False``
        abandons in-flight work. Returns the finished streams. Idempotent
        — a second call is a no-op returning the collected streams."""
        self.draining = True
        if drain and not self.idle():
            self.run(max_steps)
        for engine in self.engines:
            engine.connector.flush()
        return self.collect()

    # ---- metrics --------------------------------------------------------
    def compiles(self) -> Tuple[int, int]:
        """(prefill, decode) bucket-compile counters summed over replicas."""
        return (sum(e.metrics.prefill_compiles for e in self.engines),
                sum(e.metrics.decode_compiles for e in self.engines))

    def xla_compiles(self) -> Tuple[int, int]:
        pf = dc = 0
        for e in self.engines:
            a, b = e.xla_compiles()
            pf, dc = pf + a, dc + b
        return pf, dc

    def pallas_fallbacks(self) -> Dict[str, int]:
        """Trace-time pallas->ref fallback counts summed over the replica
        engines (each engine filters the dispatch layer's labeled counters
        by its own obs scope, so fallbacks traced by other engines or
        earlier tests in the process never leak in)."""
        out: Dict[str, int] = {}
        for e in self.engines:
            for k, v in e.pallas_fallbacks().items():
                out[k] = out.get(k, 0) + v
        return out

    def latency_quantiles(self) -> Dict[str, float]:
        """Gateway-wide p50/p95/p99 TTFT and inter-token gap: the replicas
        share one registry, so histogram quantiles with no label filter
        aggregate every replica's buckets."""
        out: Dict[str, float] = {}
        for short, metric in (("ttft", "serve_ttft_seconds"),
                              ("intertoken", "serve_intertoken_seconds")):
            h = self.registry.get(metric)
            for q in (0.5, 0.95, 0.99):
                out[f"{short}_p{int(q * 100)}_s"] = h.quantile(q)
            out[f"{short}_count"] = h.count()
        return out

    def metrics_dict(self) -> Dict[str, object]:
        per = [e.metrics.to_dict() for e in self.engines]
        tokens = sum(m["tokens_out"] for m in per)
        computed = sum(m["prefill_tokens_computed"] for m in per)
        cached = sum(m["prefill_tokens_cached"] for m in per)
        prompt = computed + cached
        return {
            "replicas": self.replicas,
            "roles": list(self.roles),
            "tokens_out": tokens,
            "wall_s": self.wall_s,
            "tokens_per_s": tokens / self.wall_s if self.wall_s > 0 else 0.0,
            "prefill_tokens_computed": computed,
            "prefill_tokens_cached": cached,
            "prefix_hit_rate": cached / prompt if prompt else 0.0,
            "prefix_evictions": sum(m["prefix_evictions"] for m in per),
            "routed": list(self.router.routed),
            "affinity_hits": self.router.affinity_hits,
            "handoffs": self.handoffs,
            "pallas_fallbacks": self.pallas_fallbacks(),
            "per_replica": per,
        }

    def stats(self) -> Dict[str, object]:
        """`metrics_dict` plus the aggregated host-tier section. Also
        refreshes the ``gateway_host_tier_hit_rate`` gauge so the tier's
        effectiveness lands in every Prometheus scrape / --metrics-dump,
        not only in callers of this method."""
        d = self.metrics_dict()
        per = [e.connector.stats() for e in self.engines]
        agg = {k: sum(t[k] for t in per) for k in (
            "resident_pages", "resident_bytes", "spill_pages",
            "spill_bytes", "reload_pages", "reload_bytes",
            "handoff_out_pages", "handoff_in_pages", "spills_skipped",
            "host_evicted_pages", "hit_tokens", "lookup_tokens")}
        agg["enabled"] = any(e.connector.enabled for e in self.engines)
        agg["hit_rate"] = agg["hit_tokens"] / agg["lookup_tokens"] \
            if agg["lookup_tokens"] else 0.0
        self.registry.gauge(
            "gateway_host_tier_hit_rate",
            "Fraction of non-device-cached lookup tokens served from the "
            "pinned-host KV tier, over all replicas").set(agg["hit_rate"])
        d["host_tier"] = {**agg, "per_replica": per}
        return d


def build_gateway(arch: str, *, smoke: bool = True, c: Optional[int] = 1,
                  data: int = 1, replicas: int = 1,
                  prefix_cache: bool = True, host_tier_bytes: int = 0,
                  roles=None,
                  eng: EngineConfig = EngineConfig(), params=None,
                  init_seed: int = 0, kernel: Optional[str] = None,
                  plan=None, plans=None,
                  registry: Optional[obs.Registry] = None,
                  tracer: Optional[obs.Tracer] = None) -> Gateway:
    """Convenience constructor mirroring ``engine.build_engine``: resolve a
    serve plan whose ``n_devices`` is the per-replica share of the local
    devices, then build the gateway on it. ``roles=['prefill','decode']``
    builds a disaggregated gateway (one plan per role via
    `plan.make_role_plans`, overriding ``replicas``); ``host_tier_bytes``
    sizes the per-engine pinned-host KV tier (needs ``prefix_cache``)."""
    import jax

    from repro.configs import registry as arch_registry
    from repro.models.factory import build_model
    from repro.plan import make_role_plans, make_serve_plan

    cfg = arch_registry.get_smoke(arch) if smoke else arch_registry.get(arch)
    model = build_model(cfg)
    if plan is None and plans is None:
        if roles:
            n_dev = len(jax.devices()) // len(roles)
            plans = make_role_plans(
                cfg, roles=roles, n_devices=n_dev, arch=arch, data=data,
                c=c, decode_batch=eng.max_slots, page_size=eng.page_size,
                max_len=eng.max_len, mesh_kind="local", kernel_impl=kernel,
                prefix_cache=prefix_cache, host_tier_bytes=host_tier_bytes)
        else:
            n_dev = len(jax.devices()) // max(replicas, 1)
            plan = make_serve_plan(
                cfg, arch=arch, n_devices=n_dev, data=data, c=c,
                decode_batch=eng.max_slots, page_size=eng.page_size,
                max_len=eng.max_len, mesh_kind="local", kernel_impl=kernel,
                replicas=replicas, prefix_cache=prefix_cache,
                host_tier_bytes=host_tier_bytes)
    return Gateway(model, plan, eng, params, registry=registry,
                   tracer=tracer, plans=plans, seed=init_seed)
