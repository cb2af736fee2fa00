"""The serving engine: continuous batching over the paged, SP-sharded KV
cache, compiled once per length bucket.

The engine owns three kinds of state:

  * **device** — the page pools (``paged_cache.zeroed_pools_fn``) and the
    model params, both living in the refined
    ``(data, sp_grp, sp_ring, sp_team)`` mesh's shardings — the mesh, the
    (C, R) refinement and the paged-decode ``kernel_impl`` all come from
    one ``ExecutionPlan`` serve plan
    (``plan.make_serve_plan`` / ``launch.serve --plan``);
  * **host** — the ``Scheduler`` (slots, page free lists, page table,
    FIFO queue);
  * **compiled** — two jit caches: prefill keyed by the padded prompt
    length bucket, decode keyed by the per-shard page-table width bucket
    ``W`` (powers of two). Per-sequence ``cache_len`` is a *traced operand*
    of the decode step, so generation never recompiles: a decode fn only
    recompiles when the longest active sequence crosses a power-of-two
    block-count boundary. ``metrics.decode_compiles`` counts exactly these
    cache misses — the "compiles at most once per bucket" guarantee is
    testable.

``step()`` is one driver iteration in the JetStream style: admit queued
requests into free slots (each admission = one prefill + paged insert +
first sampled token), then run a single decode step for every active slot,
then evict finished requests. Outputs are **bit-identical to serving each
request alone** (for batch-decoupled archs — MoE capacity couples tokens
across the batch): attention/MLP/sampling are all row-independent, page
content is per-slot, and sampling noise is keyed by (request seed, token
position), never by slot or step index.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.dist.sharding import SP_AXES
from repro.engine import kv_connector, paged_cache, sampling as sampling_lib
from repro.engine.scheduler import (Rejection, Request, Scheduler, SlotState,
                                    bucket_pow2)
from repro.models import transformer
from repro.models.factory import Model

_ENGINE_IDS = itertools.count()

#: host phases of a driver step, timed on every step (``perf_counter``)
#: and, when tracing, each an ``engine/<phase>`` span: admission, a
#: prefill's input building and launch, its blocking first-token read, the
#: decode step's inputs, its launch (until the jitted call returns) and its
#: blocking token read, and the emitted tokens' bookkeeping
PHASES = ("admit", "prefill_launch", "prefill_wait", "decode_inputs",
          "decode_launch", "decode_wait", "emit")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 4          # decode batch width (slots)
    page_size: int = 8          # tokens per KV page
    pages_per_shard: int = 128  # pool capacity per SP shard
    max_len: int = 512          # max prompt_len + max_new_tokens
    max_top_k: int = 64         # static top-k candidate bound
    max_steps: int = 100_000    # runaway guard for run()
    prefill_chunk: int = 0      # 0 = monolithic prefill; > 0 = split long
    #                             prompts into chunks of ~this many tokens
    #                             (rounded up to a compile bucket), one
    #                             chunk per driver step, interleaved with
    #                             decode so a long prompt never stalls the
    #                             decoding batch
    host_tier_bytes: int = 0    # pinned-host KV tier capacity; 0 = off
    #                             (the plan's host_tier_bytes, when set,
    #                             is authoritative — like the rest of the
    #                             serving face)
    transfer_bucket: int = 4    # pages per host-link transfer launch (one
    #                             fixed shape -> the read/write islands
    #                             compile exactly once)


class EngineMetrics:
    """Registry-backed engine metrics.

    Keeps the attribute interface the driver and every existing consumer
    use (``m.steps += 1``, ``to_dict()``, ``reset(keep_compiles=True)``)
    while each field lives as a labeled series in an ``obs.Registry`` —
    one scrape of the registry sees every engine (gateway replicas share
    a registry, distinguished by a ``replica`` label). Counter-kind
    fields render as ``engine_*_total`` counters, level-kind fields as
    gauges; the TTFT / inter-token latency histograms ride in the same
    registry (observed by the engine driver, not through this class).
    """

    # attribute -> (metric name, kind, python type)
    _SPECS = {
        "steps": ("engine_steps_total", "counter", int),
        "decode_steps": ("engine_decode_steps_total", "counter", int),
        "prefills": ("engine_prefills_total", "counter", int),
        "finished": ("engine_requests_finished_total", "counter", int),
        "tokens_out": ("engine_tokens_out_total", "counter", int),
        # device prefill launches (>= prefills when chunking is on)
        "prefill_chunks": ("engine_prefill_chunks_total", "counter", int),
        "prefill_compiles": ("engine_prefill_compiles_total", "counter",
                             int),
        "decode_compiles": ("engine_decode_compiles_total", "counter", int),
        # host-link page transfer islands (read/write, one shape each)
        "transfer_compiles": ("engine_transfer_compiles_total", "counter",
                              int),
        "occupancy_sum": ("engine_occupancy_sum", "gauge", float),
        "peak_pages": ("engine_peak_pages", "gauge", int),
        "pages_total": ("engine_pages_total", "gauge", int),
        "wall_s": ("engine_wall_seconds", "gauge", float),
        # prefix-cache accounting (zero when the cache is off)
        "prefill_tokens_computed": ("engine_prefill_tokens_computed_total",
                                    "counter", int),
        "prefill_tokens_cached": ("engine_prefill_tokens_cached_total",
                                  "counter", int),
        # of the cached tokens, those reloaded from the pinned-host tier
        "prefill_tokens_host": ("engine_prefill_tokens_host_total",
                                "counter", int),
        "prefix_evictions": ("engine_prefix_evictions", "gauge", int),
        # disaggregated prefill->decode handoffs (out: prefill-role side,
        # in: decode-role side)
        "handoffs_out": ("engine_handoffs_out_total", "counter", int),
        "handoffs_in": ("engine_handoffs_in_total", "counter", int),
        # priority preemptions (spill + re-admit; frontend-driven)
        "preemptions": ("engine_preemptions_total", "counter", int),
    }
    _HISTOGRAMS = ("serve_ttft_seconds", "serve_intertoken_seconds")
    _PHASES = "engine_phase_seconds_total"

    def __init__(self, registry: Optional[obs.Registry] = None,
                 labels: Optional[Dict[str, str]] = None, **initial):
        reg = registry if registry is not None else obs.Registry()
        object.__setattr__(self, "registry", reg)
        object.__setattr__(self, "labels", dict(labels or {}))
        for name, (metric, kind, _) in self._SPECS.items():
            if kind == "counter":
                reg.counter(metric)
            else:
                reg.gauge(metric)
        reg.histogram("serve_ttft_seconds",
                      "Request admission -> first emitted token",
                      buckets=obs.TTFT_BUCKETS)
        reg.histogram("serve_intertoken_seconds",
                      "Gap between consecutive emitted tokens of a request",
                      buckets=obs.INTERTOKEN_BUCKETS)
        reg.counter(self._PHASES, "Host seconds in each phase of a step")
        for name, v in initial.items():
            setattr(self, name, v)

    def __getattr__(self, name):
        spec = self._SPECS.get(name)
        if spec is None:
            raise AttributeError(name)
        metric, _, typ = spec
        return typ(self.registry.get(metric).value(**self.labels))

    def __setattr__(self, name, value) -> None:
        spec = self._SPECS.get(name)
        if spec is None:
            raise AttributeError(f"EngineMetrics has no field {name!r}")
        metric, _, typ = spec
        self.registry.get(metric).set(typ(value), **self.labels)

    def reset(self, keep_compiles: bool = True) -> None:
        pc, dc, tc = (self.prefill_compiles, self.decode_compiles,
                      self.transfer_compiles)
        for name in self._SPECS:
            setattr(self, name, 0)
        for name in self._HISTOGRAMS + (self._PHASES,):
            self.registry.get(name).reset(**self.labels)
        if keep_compiles:
            self.prefill_compiles, self.decode_compiles = pc, dc
            self.transfer_compiles = tc

    def to_dict(self) -> Dict[str, float]:
        d = {name: getattr(self, name) for name in self._SPECS}
        d["occupancy"] = (self.occupancy_sum / self.decode_steps
                          if self.decode_steps else 0.0)
        d["page_utilization"] = (self.peak_pages / self.pages_total
                                 if self.pages_total else 0.0)
        d["tokens_per_s"] = (self.tokens_out / self.wall_s
                             if self.wall_s > 0 else 0.0)
        prompt = self.prefill_tokens_computed + self.prefill_tokens_cached
        d["prefix_hit_rate"] = (self.prefill_tokens_cached / prompt
                                if prompt else 0.0)
        return d

    def add_phases(self, seconds: Dict[str, float]) -> None:
        c = self.registry.get(self._PHASES)
        for phase, s in seconds.items():
            c.inc(s, phase=phase, **self.labels)

    def phase_seconds(self) -> Dict[str, float]:
        c = self.registry.get(self._PHASES)
        return {p: c.value(phase=p, **self.labels) for p in PHASES}

    # latency histograms (driver-facing)
    def observe_ttft(self, seconds: float) -> None:
        self.registry.get("serve_ttft_seconds").observe(
            seconds, **self.labels)

    def observe_intertoken(self, seconds: float) -> None:
        self.registry.get("serve_intertoken_seconds").observe(
            seconds, **self.labels)

    def latency_quantiles(self) -> Dict[str, float]:
        """p50/p95/p99 TTFT and inter-token gap from the fixed buckets."""
        out = {}
        for short, metric in (("ttft", "serve_ttft_seconds"),
                              ("intertoken", "serve_intertoken_seconds")):
            h = self.registry.get(metric)
            for q in (0.5, 0.95, 0.99):
                out[f"{short}_p{int(q * 100)}_s"] = \
                    h.quantile(q, **self.labels)
            out[f"{short}_count"] = h.count(**self.labels)
        return out


class Engine:
    """Continuous-batching serving engine (add_request / step / collect).

    Construction is plan-driven: the ``ExecutionPlan`` (a ``kind='decode'``
    plan with the serving face filled in — see ``plan.make_serve_plan``) is
    the single source of the mesh refinement, the attention scheme, the
    decode slot count / page size, and the paged-decode ``kernel_impl``.
    """

    def __init__(self, model: Model, plan,
                 eng: EngineConfig = EngineConfig(), params=None, mesh=None,
                 registry: Optional[obs.Registry] = None,
                 labels: Optional[Dict[str, str]] = None,
                 tracer: Optional[obs.Tracer] = None, seed: int = 0):
        import jax
        import dataclasses as dc
        from jax.sharding import NamedSharding

        cfg = model.cfg
        ok, why = paged_cache.supported(cfg)
        if not ok:
            raise NotImplementedError(f"repro.engine: {cfg.name}: {why}")
        if not plan.decode_batch or not plan.page_size:
            raise ValueError(
                "engine plans need the serving face (decode_batch/page_size "
                "> 0) — build them with plan.make_serve_plan or --plan a "
                "persisted serve plan")
        # the plan is authoritative for the serving shape; EngineConfig
        # keeps only the pool-capacity and sampling/driver knobs
        eng = dc.replace(
            eng, max_slots=plan.decode_batch, page_size=plan.page_size,
            max_len=plan.seq_len,
            host_tier_bytes=int(getattr(plan, "host_tier_bytes", 0)
                                or eng.host_tier_bytes))
        run_cfg = plan.run_config()
        mesh = mesh if mesh is not None else plan.build_mesh()
        self.model, self.mesh, self.run_cfg, self.eng = model, mesh, run_cfg, eng
        self.plan = plan
        self.cfg = cfg
        self.sp = 1
        for a in SP_AXES:
            self.sp *= mesh.shape[a]
        if self.sp != plan.sp_size:
            raise ValueError(f"mesh SP degree {self.sp} != plan "
                             f"sp_size {plan.sp_size}")
        self.rt = serve_runtime(model, plan)
        self.kernel_impl = plan.kernel_impl
        self._param_specs = model.partition(run_cfg.sharding_rules)
        # params live in the layout the step islands read them in: created
        # there from ``seed``, or moved there once if the caller built them
        param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                self._param_specs)
        self.params = (model.init_sharded(param_sh, seed) if params is None
                       else jax.device_put(params, param_sh))
        self._pool_part = paged_cache.pool_partition(cfg)
        self._sc = sampling_lib.SamplingConfig(max_top_k=eng.max_top_k)
        self._prefill_base = math.lcm(self.sp, eng.page_size)
        # chunked prefill: the chunk is itself a compile bucket (a multiple
        # of lcm(sp, page_size) so every chunk boundary is page-aligned on
        # every shard and intermediate chunks need no padding)
        self._chunk = 0
        if eng.prefill_chunk > 0:
            self._chunk = bucket_pow2(
                max(eng.prefill_chunk, self._prefill_base),
                self._prefill_base)
            if any(mlp == "moe"
                   for _, mlp in transformer.layer_pattern(cfg)):
                # same coupling that forbids prefix caching: a chunk's
                # tokens compete for expert capacity without the rest of
                # the prompt, so chunked != monolithic for MoE stacks
                raise NotImplementedError(
                    f"repro.engine: {cfg.name}: chunked prefill is unsound "
                    "for MoE stacks (expert capacity couples a chunk's "
                    "tokens to the rest of the prompt)")
        self._prefilling: List[SlotState] = []
        self.last_step_prefills: List[Tuple[int, int]] = []
        self.last_step_phases: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        # every step runs under this obs scope, so trace-time events in the
        # process-global registry (dispatch's pallas->ref fallbacks) carry
        # a scope label attributing them to this engine instance
        self.obs_scope = f"engine{next(_ENGINE_IDS)}"
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        self.host = obs.watch_host(self.tracer)
        self._arrival: Dict[str, float] = {}     # uid -> enqueue time
        self._last_emit: Dict[str, float] = {}   # uid -> last token time
        self._req_spans: Dict[str, Optional[str]] = {}
        # the pools are made on the devices, by one jitted fn: every pool
        # entering a step fn is a jit output — device_put arrays carry a
        # differently-typed sharding and would retrace the first call
        # after each reset()
        self._new_pools = paged_cache.zeroed_pools_fn(
            cfg, mesh, self.sp * eng.pages_per_shard, eng.page_size)
        self.pools = self._new_pools()
        self.prefix_caching = bool(getattr(plan, "prefix_cache", False))
        if self.prefix_caching and any(
                mlp == "moe" for _, mlp in transformer.layer_pattern(cfg)):
            # MoE expert capacity couples tokens *within* a sequence: a
            # prefix token's hidden state depends on the suffix competing
            # for expert slots, so cached prefix KV is not reusable.
            raise NotImplementedError(
                f"repro.engine: {cfg.name}: prefix caching is unsound for "
                "MoE stacks (capacity couples prefix KV to the suffix)")
        if eng.host_tier_bytes > 0 and not self.prefix_caching:
            raise ValueError(
                "host_tier_bytes > 0 needs prefix_cache=True: the host "
                "tier is fed by PrefixCache.evict and hit through the "
                "same chain hashes")
        self._prefill_fns: Dict[int, object] = {}
        self._suffix_fns: Dict[int, object] = {}
        self._decode_fns: Dict[int, object] = {}
        self._base_keys: Dict[int, np.ndarray] = {}
        self.metrics = EngineMetrics(
            registry, labels, pages_total=self.sp * eng.pages_per_shard)
        self.registry = self.metrics.registry
        # host-link transfer islands + the connector (spill/reload/handoff)
        self._read_pages_fn = None
        self._write_pages_fn = None
        self._cost_memo: Dict[int, float] = {}
        self._spill_memo: Dict[int, bool] = {}
        page_bytes = sum(
            int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(
                paged_cache.pool_spec(cfg, 1, eng.page_size)))
        self.connector = kv_connector.KVConnector(
            read_fn=self._read_kv, write_fn=self._write_kv,
            bucket=eng.transfer_bucket, page_size=eng.page_size,
            pages_per_shard=eng.pages_per_shard, page_bytes=page_bytes,
            capacity_bytes=eng.host_tier_bytes,
            spill_fn=self._spill_worthwhile,
            registry=self.registry, labels=labels)
        self._handoff_ready: List[SlotState] = []
        self.scheduler = self._new_scheduler()

    def _new_scheduler(self) -> Scheduler:
        sched = Scheduler(
            max_slots=self.eng.max_slots, page_size=self.eng.page_size,
            sp=self.sp, pages_per_shard=self.eng.pages_per_shard,
            max_len=self.eng.max_len)
        sched.connector = self.connector
        if self.prefix_caching:
            from repro.gateway.prefix_cache import PrefixCache

            sched.prefix_cache = PrefixCache(
                sched.pool, page_size=self.eng.page_size, sp=self.sp,
                cost_fn=self._recompute_cost,
                connector=(self.connector if self.connector.enabled
                           else None))
        return sched

    # ---- host-link transfers (spill / reload / handoff) -----------------
    def _io_fns(self):
        """The page gather/scatter islands, built lazily and compiled
        exactly once — the transfer bucket is a single fixed shape, so a
        second trace here is operand-provenance drift, not a new bucket."""
        if self._read_pages_fn is None:
            import jax
            from jax.sharding import PartitionSpec as P

            rt = self.rt
            self._read_pages_fn = jax.jit(jax.shard_map(
                lambda pools, idx: paged_cache.read_pages(rt, pools, idx),
                mesh=self.mesh, in_specs=(self._pool_part, P()),
                out_specs=P(), check_vma=False))
            self._write_pages_fn = jax.jit(jax.shard_map(
                lambda pools, idx, data: paged_cache.write_pages(
                    rt, pools, idx, data),
                mesh=self.mesh, in_specs=(self._pool_part, P(), P()),
                out_specs=self._pool_part, check_vma=False),
                donate_argnums=(0,))
            self.metrics.transfer_compiles += 2
        return self._read_pages_fn, self._write_pages_fn

    def _read_kv(self, idx: np.ndarray):
        read, _ = self._io_fns()
        return read(self.pools, idx)

    def _write_kv(self, idx: np.ndarray, data) -> None:
        _, write = self._io_fns()
        self.pools = write(self.pools, idx, data)

    def transfer_xla_compiles(self) -> int:
        """XLA trace count of the transfer islands (2 once used; more
        means silent retracing — same contract as ``xla_compiles``)."""
        n = 0
        for fn in (self._read_pages_fn, self._write_pages_fn):
            if fn is not None:
                size = getattr(fn, "_cache_size", None)
                n += size() if callable(size) else 1
        return n

    def _recompute_cost(self, chain_tokens: int) -> float:
        """Eviction ranking: seconds to re-prefill a chain cold."""
        c = self._cost_memo.get(chain_tokens)
        if c is None:
            from repro.plan import cost as plan_cost

            c = plan_cost.prefill_step_cost(
                self.cfg, prompt_len=chain_tokens, sp=self.sp,
                page_size=self.eng.page_size)["total_s"]
            self._cost_memo[chain_tokens] = c
        return c

    def _spill_worthwhile(self, chain_tokens: int) -> bool:
        """Under host-tier pressure: does the transfer round-trip beat
        recomputing this chain (plan.cost.spill_decision)?"""
        v = self._spill_memo.get(chain_tokens)
        if v is None:
            from repro.plan import cost as plan_cost

            v = bool(plan_cost.spill_decision(
                self.cfg, chain_tokens=chain_tokens, sp=self.sp,
                page_size=self.eng.page_size)["spill"])
            self._spill_memo[chain_tokens] = v
        return v

    @property
    def prefix_cache(self):
        return self.scheduler.prefix_cache

    # ---- request lifecycle ---------------------------------------------
    def add_request(self, req: Request) -> Optional[Rejection]:
        """Queue ``req``. Returns ``None`` on success or a typed
        :class:`Rejection` (never raises for unserveable requests — the
        HTTP layer maps ``reason`` to a status code)."""
        rej = self.scheduler.validate(req)
        if rej is not None:
            return rej
        self.scheduler.queue.append(req)
        self._arrival[req.uid] = time.monotonic()
        self._req_spans[req.uid] = self.tracer.async_begin(
            "request", uid=req.uid, prompt_len=req.prompt_len,
            max_new=req.max_new_tokens)
        return None

    def preempt(self, uid: str) -> Optional[Request]:
        """Evict ``uid`` from the engine, preserving its progress, and
        return the *resume request* to re-admit later (here or on another
        replica). ``None`` if the uid is not queued or active here.

        The resume request's prompt is ``tokens + out`` with the remaining
        token budget: re-admission prefills it like any other prompt, and
        because sampling is keyed by (seed, absolute position) — prefill
        folds at ``prompt_len``, decode at ``cache_len + 1`` — the resumed
        stream continues bit-identically to the uninterrupted one. With a
        prefix cache attached, the preempted slot's complete valid KV
        blocks are registered in the trie first, so the resume prefill is
        mostly (often entirely) a cache hit instead of a recompute; under
        memory pressure those blocks are spillable to the host tier like
        any cached chain.
        """
        for i, r in enumerate(self.scheduler.queue):
            if r.uid == uid:                 # still queued: nothing started
                del self.scheduler.queue[i]
                self._arrival.pop(uid, None)
                self.tracer.async_end(
                    "request", self._req_spans.pop(uid, None), preempted=True)
                self.metrics.preemptions += 1
                return r
        st = next((s for s in self.scheduler.active() if s.req.uid == uid),
                  None)
        if st is None or st.req.handoff:
            # handoff slots pin exported KV — preempting one mid-export
            # would tear the transfer; the gateway owns their lifecycle
            return None
        req = st.req
        seq = list(req.tokens) + [int(t) for t in st.out]
        # KV valid through max(cache_len, prefill_pos): decode keeps
        # cache_len, a mid-chunk prefill only prefill_pos. pending_reload
        # blocks hold garbage until _advance_prefill lands them, so a slot
        # that never ran a chunk registers nothing new (its device-hit
        # prefix is already in the trie).
        valid = max(st.cache_len, st.prefill_pos)
        pc = self.prefix_cache
        if pc is not None and not st.pending_reload:
            full = valid // self.eng.page_size
            if full > 0:
                pc.insert(pc.hashes(seq)[:full], st.pages[:full])
        if st in self._prefilling:
            self._prefilling.remove(st)
        remaining = req.max_new_tokens - len(st.out)
        self.scheduler.finish(st.slot, self.metrics.steps)
        self.scheduler.finished.pop(uid, None)    # not finished: preempted
        self.metrics.preemptions += 1
        self._arrival.pop(uid, None)
        self._last_emit.pop(uid, None)
        self.tracer.async_end("request", self._req_spans.pop(uid, None),
                              preempted=True, tokens=len(st.out))
        if not st.out:
            return req
        return dataclasses.replace(req, tokens=seq,
                                   max_new_tokens=remaining)

    def _finish_request(self, st: SlotState) -> None:
        """Bookkeeping common to every finish site (prefill or decode)."""
        self.scheduler.finish(st.slot, self.metrics.steps)
        self.metrics.finished += 1
        uid = st.req.uid
        self._arrival.pop(uid, None)
        self._last_emit.pop(uid, None)
        self.tracer.async_end("request", self._req_spans.pop(uid, None),
                              tokens=len(st.out))

    def collect(self) -> Dict[str, List[int]]:
        """uid -> generated tokens, for every finished request."""
        return {uid: list(st.out)
                for uid, st in self.scheduler.finished.items()}

    def reset(self) -> None:
        """Drop all requests and cache contents (including the prefix
        cache and the host tier — the pools are zeroed); keep compiled
        fns."""
        import jax

        # free the pools before making their zeroed successor, so the
        # devices never hold two
        for leaf in jax.tree.leaves(self.pools):
            leaf.delete()
        self.pools = self._new_pools()
        self.connector.reset()
        self.scheduler = self._new_scheduler()
        self._prefilling = []
        self._handoff_ready = []
        self.last_step_prefills = []
        self.last_step_phases = dict.fromkeys(PHASES, 0.0)
        self._arrival.clear()
        self._last_emit.clear()
        self._req_spans.clear()
        self.metrics.reset(keep_compiles=True)
        self.metrics.pages_total = self.scheduler.pages_total()

    def pallas_fallbacks(self) -> Dict[str, int]:
        """Trace-time pallas->ref fallback counts attributable to *this*
        engine: the dispatch layer's labeled registry counters, filtered
        by this engine's ``obs.scope`` (every ``step()`` runs under it) —
        a fresh engine has a fresh scope, so it never inherits fallbacks
        earlier engines or tests traced."""
        from repro.kernels import dispatch as _dispatch

        return _dispatch.pallas_fallbacks(scope=self.obs_scope)

    # ---- compiled-step caches ------------------------------------------
    def _prefill_bucket(self, prompt_len: int) -> int:
        return bucket_pow2(prompt_len, self._prefill_base)

    def _prefill_fn(self, bucket_len: int, sampled: bool):
        """One jit per (padded prompt length, any-sampling). All-greedy
        requests skip the top-k/top-p/gumbel kernel entirely; the sampled
        variant's greedy branch produces the identical token for T<=0 rows,
        so the split never changes outputs."""
        import jax
        import dataclasses as dc
        from jax.sharding import PartitionSpec as P

        from repro.serve import step as serve_step

        fn = self._prefill_fns.get((bucket_len, sampled))
        if fn is not None:
            return fn
        cfg, eng = self.cfg, self.eng
        rt = dc.replace(self.rt, st_cfg=dc.replace(self.rt.st_cfg,
                                                   seq_len=bucket_len))
        pat = transformer.layer_pattern(cfg)

        def island(params, tokens, prompt_len, pools, table_row,
                   temp, top_k, top_p, key):
            last, cache = serve_step.lm_prefill(
                rt, params, {"tokens": tokens}, cfg,
                prompt_len=prompt_len, return_hidden=True)
            subs = {}
            with jax.named_scope("prefill/attn/kv_write"):
                for i in range(len(pat)):
                    subs[f"sub{i}"] = paged_cache.insert_prompt(
                        rt, pools["stack"][f"sub{i}"],
                        cache["stack"][f"sub{i}"]["k"],
                        cache["stack"][f"sub{i}"]["v"],
                        table_row, prompt_len[0], eng.page_size)
            tok = self._first_token(rt, params, last, prompt_len, sampled,
                                    temp, top_k, top_p, key)
            return tok, {"stack": subs}

        fn = jax.jit(jax.shard_map(
            island, mesh=self.mesh,
            in_specs=(self._param_specs, P(None, SP_AXES), P(),
                      self._pool_part, P(), P(), P(), P(), P()),
            out_specs=(P(), self._pool_part), check_vma=False),
            donate_argnums=(3,))
        self._prefill_fns[(bucket_len, sampled)] = fn
        self.metrics.prefill_compiles += 1
        return fn

    def _suffix_fn(self, bucket_len: int, sampled: bool):
        """One jit per (padded *suffix* length, any-sampling): the
        prefix-cached prefill. The page-table row keeps its full static
        width (one prefill per request — no width bucketing needed)."""
        import jax
        import dataclasses as dc
        from jax.sharding import PartitionSpec as P

        from repro.serve import step as serve_step

        fn = self._suffix_fns.get((bucket_len, sampled))
        if fn is not None:
            return fn
        cfg, eng = self.cfg, self.eng
        rt = dc.replace(self.rt, st_cfg=dc.replace(self.rt.st_cfg,
                                                   seq_len=bucket_len))

        def island(params, tokens, prompt_len, cached_len, pools, table_row,
                   temp, top_k, top_p, key):
            last, new_pools = serve_step.lm_prefill_paged(
                rt, params, {"tokens": tokens}, cfg,
                prompt_len=prompt_len, cached_len=cached_len, pools=pools,
                table_row=table_row, page_size=eng.page_size)
            tok = self._first_token(rt, params, last, prompt_len, sampled,
                                    temp, top_k, top_p, key)
            return tok, new_pools

        fn = jax.jit(jax.shard_map(
            island, mesh=self.mesh,
            in_specs=(self._param_specs, P(None, SP_AXES), P(), P(),
                      self._pool_part, P(), P(), P(), P(), P()),
            out_specs=(P(), self._pool_part), check_vma=False),
            donate_argnums=(4,))
        self._suffix_fns[(bucket_len, sampled)] = fn
        self.metrics.prefill_compiles += 1
        return fn

    def _first_token(self, rt, params, last, prompt_len, sampled: bool,
                     temp, top_k, top_p, key):
        """A prefill island's first token from the last prompt position's
        hidden state: greedy, or sampled with the fold the decode step
        continues from (request seed, position ``prompt_len``)."""
        import jax

        head = params.get("lm_head", params["embed"])
        with jax.named_scope("prefill/head"):
            if not sampled:
                return sampling_lib.greedy(rt, head, last, self.cfg)
            k1 = jax.random.fold_in(key, prompt_len[0])
            return sampling_lib.sample(
                rt, head, last, self.cfg, temperature=temp, top_k=top_k,
                top_p=top_p, keys=k1[None], sc=self._sc)

    def _decode_fn(self, width: int, sampled: bool):
        """One jit per (table-width bucket, any-active-request-samples)."""
        fn = self._decode_fns.get((width, sampled))
        if fn is not None:
            return fn
        fn = decode_step_fn(self.cfg, self.rt, self.mesh, self._param_specs,
                            self.eng.page_size, sampled, self._sc)
        self._decode_fns[(width, sampled)] = fn
        self.metrics.decode_compiles += 1
        return fn

    def xla_compiles(self) -> Tuple[int, int]:
        """(prefill, decode) XLA-level trace counts summed over the bucket
        fns. Unlike the bucket-miss counters this catches *silent*
        retracing (dtype/weak-type drift in the host-assembled operands):
        every bucket fn should hold exactly one cache entry."""
        def total(fns):
            n = 0
            for fn in fns.values():
                size = getattr(fn, "_cache_size", None)
                n += size() if callable(size) else 1
            return n
        return (total(self._prefill_fns) + total(self._suffix_fns),
                total(self._decode_fns))

    def _base_key(self, seed: int) -> np.ndarray:
        key = self._base_keys.get(seed)
        if key is None:
            import jax

            key = np.asarray(jax.random.PRNGKey(seed))
            self._base_keys[seed] = key
        return key

    # ---- driver ---------------------------------------------------------
    def _advance_prefill(self, st: SlotState):
        """Run one prefill chunk for ``st`` (the whole remaining prompt
        when chunking is off). Returns the first sampled token when the
        prompt completes, else None.

        A leading chunk (``prefill_pos == 0``) runs the dense full-forward
        prefill; every later chunk is a *suffix* prefill with
        ``cached_len = prefill_pos`` — the pages earlier chunks (or the
        prefix cache) populated are read in place, so one jit bucket
        serves prefix hits and chunk continuations alike. Only the final
        chunk's token is kept; its sampling fold (request seed, position
        ``prompt_len``) is the same as the monolithic path's, so chunking
        never changes the emitted stream.
        """
        t0 = time.perf_counter()
        req = st.req
        m = self.metrics
        if st.pending_reload:
            # host-tier hits: land their KV in the freshly-allocated pool
            # pages before any forward reads them
            with self.tracer.span("engine/host_reload", cat="engine",
                                  uid=req.uid, blocks=len(st.pending_reload)):
                self.connector.reload(st.pending_reload)
            m.prefill_tokens_host += st.host_len
            st.pending_reload = []
        start = st.prefill_pos
        end = req.prompt_len if not self._chunk \
            else min(start + self._chunk, req.prompt_len)
        final = end == req.prompt_len
        sampled = final and req.temperature > 0.0
        sampling_args = (
            np.asarray([req.temperature], np.float32),
            np.asarray([req.top_k], np.int32),
            np.asarray([req.top_p], np.float32),
            self._base_key(req.seed))
        if start:
            suffix = end - start
            bucket = self._prefill_bucket(suffix)
            fn = self._suffix_fn(bucket, sampled)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :suffix] = req.tokens[start:end]
            tok, self.pools = fn(
                self.params, tokens, np.asarray([end], np.int32),
                np.asarray([start], np.int32), self.pools,
                self.scheduler.table[st.slot].copy(), *sampling_args)
        else:
            bucket = self._prefill_bucket(end)
            fn = self._prefill_fn(bucket, sampled)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :end] = req.tokens[:end]
            tok, self.pools = fn(
                self.params, tokens, np.asarray([end], np.int32),
                self.pools, self.scheduler.table[st.slot].copy(),
                *sampling_args)
        st.prefill_pos = end
        m.prefill_tokens_computed += end - start
        m.prefill_chunks += 1
        self.last_step_prefills.append((start, end))
        phases = self.last_step_phases
        t1 = time.perf_counter()
        phases["prefill_launch"] += t1 - t0
        if not final:
            return None
        with self.tracer.span("engine/prefill_wait", cat="engine"):
            first = int(np.asarray(tok)[0, 0])
        phases["prefill_wait"] += time.perf_counter() - t1
        return first

    def _complete_prefill(self, st: SlotState, tok: int, emitted) -> None:
        t0 = time.perf_counter()
        with self.tracer.span("engine/emit", cat="engine"):
            self._emit_first(st, tok, emitted)
        self.last_step_phases["emit"] += time.perf_counter() - t0

    def _emit_first(self, st: SlotState, tok: int, emitted) -> None:
        m = self.metrics
        self._prefilling.remove(st)
        self.scheduler.register_prefix(st)
        m.prefill_tokens_cached += st.cached_len
        st.cache_len = st.req.prompt_len
        st.out.append(tok)
        st.first_token_step = m.steps
        emitted.append((st.req.uid, tok))
        m.prefills += 1
        m.tokens_out += 1
        now = time.monotonic()
        arrived = self._arrival.get(st.req.uid)
        if arrived is not None:
            m.observe_ttft(now - arrived)
        self._last_emit[st.req.uid] = now
        if st.done:
            if st.req.handoff:
                # keep the slot (and its pages' refs) live until the
                # gateway exports the prompt KV to a decode replica —
                # finishing here could recycle the pages mid-export
                self._handoff_ready.append(st)
                self.metrics.handoffs_out += 1
            else:
                self._finish_request(st)

    # ---- disaggregated prefill -> decode handoff ------------------------
    def take_handoffs(self) -> List[SlotState]:
        """Slots whose handoff prefill finished this step (prompt KV still
        pinned). The caller must ``export_kv`` then ``release_handoff``
        each one."""
        out, self._handoff_ready = self._handoff_ready, []
        return out

    def export_kv(self, st: SlotState) -> List:
        """Read the slot's prompt-KV pages to host, block order. The
        partial tail block rides along — positions past ``prompt_len``
        hold garbage the position-encoded validity never reads."""
        nb_kv = math.ceil(st.req.prompt_len / self.eng.page_size)
        return self.connector.export(st.pages[:nb_kv])

    def release_handoff(self, st: SlotState) -> None:
        """Drop the handoff slot after its KV has been exported."""
        self._finish_request(st)

    def add_prefilled(self, req: Request, first_token: int,
                      blocks: List) -> None:
        """Decode-role entry point: queue a request whose prompt KV and
        first token came from a prefill replica. No TTFT is observed here
        — the first token was emitted by the prefill engine."""
        self.scheduler.enqueue_prefilled(req, first_token, blocks)

    def step(self) -> List[Tuple[str, int]]:
        """One driver iteration: admit, advance prefills (one chunk each),
        one decode step for every decoding slot.

        Returns the (uid, token) pairs emitted this step.
        """
        with obs.scope(self.obs_scope), \
                self.tracer.span("engine/step", cat="engine",
                                 scope=self.obs_scope,
                                 step=self.metrics.steps):
            emitted = self._step_inner()
        # always-on phase sums and the process's host counters reach the
        # registry once per step, never per token
        self.metrics.add_phases(self.last_step_phases)
        self.host.fold()
        return emitted

    def _step_inner(self) -> List[Tuple[str, int]]:
        t0 = time.monotonic()
        clock = time.perf_counter
        emitted: List[Tuple[str, int]] = []
        m = self.metrics
        tracer = self.tracer
        self.last_step_prefills = []
        phases = self.last_step_phases = dict.fromkeys(PHASES, 0.0)

        # commit spills staged by the previous step's evictions: host-tier
        # entries become hittable only once their d2h copy has landed (a
        # torn spill is never observable as a hit)
        self.connector.flush()

        # disaggregated handoff inbox: requests with prompt KV prefilled
        # on another replica enter here — inject the exported pages and
        # the already-sampled first token, skipping prefill entirely
        for st, tok, blocks in self.scheduler.admit_prefilled(m.steps):
            with tracer.span("engine/handoff_inject", cat="engine",
                             uid=st.req.uid, blocks=len(blocks)):
                nb_kv = math.ceil(st.req.prompt_len / self.eng.page_size)
                self.connector.inject(st.pages[:nb_kv], blocks)
            st.cache_len = st.req.prompt_len
            st.prefill_pos = st.req.prompt_len
            st.out.append(tok)
            st.first_token_step = m.steps
            m.handoffs_in += 1
            self._last_emit[st.req.uid] = time.monotonic()
            if st.done:                      # degenerate 1-token budget
                self._finish_request(st)

        # in-flight chunked prefills admitted on earlier steps: one chunk
        # each, *before* this step's admissions (FIFO progress)
        for st in list(self._prefilling):
            with tracer.span("engine/prefill_chunk", cat="engine",
                             uid=st.req.uid, start=st.prefill_pos):
                tok = self._advance_prefill(st)
            if tok is not None:
                self._complete_prefill(st, tok, emitted)

        while True:
            # one at a time: each admission registers its prompt blocks
            # before the next is matched, so same-step bursts sharing a
            # prefix hit the cache (a *chunked* long prompt registers only
            # when its last chunk lands, steps later)
            t = clock()
            with tracer.span("engine/admit", cat="engine"):
                batch = self.scheduler.admit(m.steps, limit=1)
            phases["admit"] += clock() - t
            if not batch:
                break
            st = batch[0]
            self._prefilling.append(st)
            with tracer.span("engine/prefill", cat="engine",
                             uid=st.req.uid, prompt_len=st.req.prompt_len,
                             cached_len=st.cached_len):
                tok = self._advance_prefill(st)
            if tok is not None:
                self._complete_prefill(st, tok, emitted)
        if self.scheduler.prefix_cache is not None:
            m.prefix_evictions = self.scheduler.prefix_cache.evicted_pages

        # decode: slots whose prefill has completed (mid-chunk slots hold
        # pages but have no token stream yet; done-but-unreleased handoff
        # slots only await export and must not keep generating)
        active = [st for st in self.scheduler.active()
                  if st.cache_len > 0 and not st.done]
        if active:
            width = self.scheduler.decode_width()
            sampled = any(st.req.temperature > 0.0 for st in active)
            with tracer.span("engine/decode", cat="engine",
                             width=width, active=len(active)):
                t_inputs = clock()
                with tracer.span("engine/decode_inputs", cat="engine"):
                    fn = self._decode_fn(width, sampled)
                    B = self.eng.max_slots
                    tokens = np.zeros((B, 1), np.int32)
                    cache_len = np.zeros((B,), np.int32)
                    temp = np.zeros((B,), np.float32)
                    top_k = np.zeros((B,), np.int32)
                    top_p = np.ones((B,), np.float32)
                    keys = np.zeros((B, 2), np.uint32)
                    act = np.zeros((B,), bool)
                    for st in active:
                        i = st.slot
                        tokens[i, 0] = st.out[-1]
                        cache_len[i] = st.cache_len
                        temp[i] = st.req.temperature
                        top_k[i] = st.req.top_k
                        top_p[i] = st.req.top_p
                        keys[i] = self._base_key(st.req.seed)
                        act[i] = True
                    table = np.ascontiguousarray(
                        self.scheduler.table[:, :, :width])
                t_launch = clock()
                with tracer.span("engine/decode_launch", cat="engine"):
                    tok, self.pools = fn(self.params, self.pools, tokens,
                                         cache_len, table, temp, top_k,
                                         top_p, keys, act)
                t_wait = clock()
                with tracer.span("engine/decode_wait", cat="engine"):
                    tok = np.asarray(tok)
                t_emit = clock()
            with tracer.span("engine/emit", cat="engine"):
                now = time.monotonic()
                for st in active:
                    t = int(tok[st.slot, 0])
                    st.out.append(t)
                    st.cache_len += 1
                    emitted.append((st.req.uid, t))
                    m.tokens_out += 1
                    uid = st.req.uid
                    last = self._last_emit.get(uid)
                    if last is not None:
                        m.observe_intertoken(now - last)
                    self._last_emit[uid] = now
                    if st.done:
                        self._finish_request(st)
                m.decode_steps += 1
                m.occupancy_sum += len(active) / self.eng.max_slots
            phases["decode_inputs"] += t_launch - t_inputs
            phases["decode_launch"] += t_wait - t_launch
            phases["decode_wait"] += t_emit - t_wait
            phases["emit"] += clock() - t_emit

        m.peak_pages = max(m.peak_pages, self.scheduler.pages_in_use())
        m.steps += 1
        m.wall_s += time.monotonic() - t0
        return emitted

    def idle(self) -> bool:
        return not self.scheduler.queue and not self.scheduler.prefilled \
            and not self.scheduler.active()

    def run(self, max_steps: Optional[int] = None) -> Dict[str, List[int]]:
        """Drive until every queued/running request finishes."""
        limit = max_steps or self.eng.max_steps
        n = 0
        while not self.idle():
            emitted = self.step()
            if not emitted and not self.scheduler.active():
                # queue non-empty but nothing admitted and nothing decoding:
                # the head request cannot make progress (enqueue validation
                # makes this unreachable, but fail loud rather than spin)
                raise RuntimeError(
                    f"engine stalled with {len(self.scheduler.queue)} queued "
                    "requests and no admissible slot/pages")
            n += 1
            if n > limit:
                raise RuntimeError(f"engine did not drain in {limit} steps")
        return self.collect()


def serve_runtime(model: Model, plan):
    """The runtime every engine island traces under: the plan's SPMD
    runtime with the batch replicated and the caches laid out
    contiguously."""
    import dataclasses as dc

    from repro.train import step as train_step

    rt = train_step.make_runtime(model, plan.run_config(),
                                 plan.shape_config(), mode="spmd")
    return dc.replace(rt, batch_axes=(),
                      st_cfg=dc.replace(rt.st_cfg, seq_scheme="contiguous"))


def decode_step_fn(cfg, rt, mesh, param_specs, page_size: int,
                   sampled: bool = False,
                   sc: Optional[sampling_lib.SamplingConfig] = None):
    """The engine's jitted decode step over ``mesh``: (params, pools,
    tokens, cache_len, table, temperature, top_k, top_p, keys, active) ->
    (next tokens, pools). The pools are donated: the step updates them in
    place. It needs no live engine, so it also compiles ahead of time for
    a described chip."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.serve import step as serve_step

    sc = sc if sc is not None else sampling_lib.SamplingConfig()
    pool_part = paged_cache.pool_partition(cfg)

    def island(params, pools, tokens, cache_len, table,
               temp, top_k, top_p, keys, active):
        paged = paged_cache.PagedTables(table=table, page_size=page_size)
        sampling = {"temperature": temp, "top_k": top_k, "top_p": top_p,
                    "keys": keys, "sc": sc} if sampled else None
        return serve_step.lm_decode_step(
            rt, params, pools, tokens, cfg, cache_len, paged=paged,
            active=active, sampling=sampling)

    return jax.jit(jax.shard_map(
        island, mesh=mesh,
        in_specs=(param_specs, pool_part, P(), P(), P(),
                  P(), P(), P(), P(), P()),
        out_specs=(P(), pool_part), check_vma=False),
        donate_argnums=(1,))


def build_engine(arch: str, *, smoke: bool = True, c: Optional[int] = 1,
                 data: int = 1, eng: EngineConfig = EngineConfig(),
                 params=None, init_seed: int = 0,
                 kernel: Optional[str] = None, plan=None,
                 registry: Optional[obs.Registry] = None,
                 tracer: Optional[obs.Tracer] = None) -> Engine:
    """Convenience constructor: resolve a serve plan, build the engine.

    With ``plan=None`` a ``kind='decode'`` ExecutionPlan is made from the
    knobs over every available device (``make_serve_plan`` — same
    refinement rule as the train launcher; pass ``c=None`` to let the cost
    model pick the factorisation). ``kernel`` selects the paged-decode
    kernel (None = backend default: pallas on TPU, ref on CPU).
    """
    import jax

    from repro.configs import registry as arch_registry
    from repro.models.factory import build_model
    from repro.plan import make_serve_plan

    cfg = arch_registry.get_smoke(arch) if smoke else arch_registry.get(arch)
    model = build_model(cfg)
    if plan is None:
        plan = make_serve_plan(
            cfg, arch=arch, n_devices=len(jax.devices()), data=data, c=c,
            decode_batch=eng.max_slots, page_size=eng.page_size,
            max_len=eng.max_len, mesh_kind="local", kernel_impl=kernel)
    return Engine(model, plan, eng, params, registry=registry, tracer=tracer,
                  seed=init_seed)
