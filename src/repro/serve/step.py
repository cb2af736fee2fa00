"""Serving: prefill and decode steps (manual SPMD, same island style as
training).

decode: one new token per sequence against the SP-sharded KV cache.
  * attention -> per-shard partial attention + global lse-combine psum
    (``core.startrail.decode_attention``): for M=1 queries the concentric
    ring degenerates to a reduction, which is the communication-optimal
    configuration.
  * mamba/mlstm/slstm -> single-step recurrences on the cached state.
  * vocab-parallel greedy sampling (local top-1 + global argmax combine;
    full logits are never gathered).

prefill: the full forward pass with cache write-out per layer (attention
K/V sharded in place; SSM states via the cross-shard-corrected final state).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import MambaConfig, ModelConfig, RunConfig, ShapeConfig
from repro.core import combine
from repro.core import startrail as st
from repro.dist import sharding as shard_rules
from repro.kernels import dispatch as kernels
from repro.models import blocks, moe as moe_lib, ssm, transformer
from repro.models.factory import Model
from repro.models.runtime import Runtime
from repro.serve import kv_cache
from repro.train import step as train_step


# ---------------------------------------------------------------------------
# per-mixer decode updates
# ---------------------------------------------------------------------------

def _attn_decode(rt: Runtime, p, x, cache, cfg: ModelConfig, cache_len,
                 paged=None, active=None, layer=None):
    """x: (B, 1, D) replicated over SP.

    cache_len: static int (whole batch at one length — the classic decode
      path) or a traced (B,) int32 array of per-sequence lengths (the
      engine's continuously-batched path).
    cache: contiguous k/v slices (B, S_loc, Hkv, hd), or — when ``paged``
      is an ``engine.paged_cache.PagedTables`` — this shard's whole
      period-stacked page pools (n_periods, pages_loc, page_size, Hkv,
      hd_pool), written and read at period ``layer`` (traced scalar).
    active: optional (B,) bool; inactive slots write nothing (engine slots
      between requests).
    """
    B = x.shape[0]
    with jax.named_scope("qkv"):
        h = blocks.rmsnorm(p["norm"], x, cfg.norm_eps)
        wq = rt.dense(p["wq"], ("embed", "heads", "head_dim"))
        wk = rt.dense(p["wk"], ("embed", "kv_heads", "head_dim"))
        wv = rt.dense(p["wv"], ("embed", "kv_heads", "head_dim"))
        cl = jnp.asarray(cache_len, jnp.int32)
        if cl.ndim == 0:
            cl = jnp.broadcast_to(cl, (B,))
        pos_new = cl[:, None]                                   # (B, 1)
        q = blocks.rope(jnp.einsum("bsd,dhk->bshk", h, wq), pos_new,
                        cfg.rope_theta)
        k_new = blocks.rope(jnp.einsum("bsd,dhk->bshk", h, wk), pos_new,
                            cfg.rope_theta)
        v_new = jnp.einsum("bsd,dhk->bshk", h, wv)

    if paged is not None:
        # paged pool: write the token, then hand the page table straight to
        # the dispatch layer — the Pallas kernel indexes the pool tiles via
        # the table (no dense gather); the ref impl gathers and reuses the
        # jnp oracle. Partial (o, lse) merge across shards exactly as the
        # contiguous path does.
        from repro.engine import paged_cache as paged_lib

        with jax.named_scope("kv_write"):
            new_cache, tbl = paged_lib.write_token(
                rt, cache, k_new, v_new, paged, cl, active, layer)
        with jax.named_scope("paged"):
            o_p, lse_p = kernels.paged_decode(
                q, new_cache["k"], new_cache["v"], tbl, cl, rt.sp_rank(),
                layer, sp=rt.sp_size(), page_size=paged.page_size,
                window=cfg.window, impl=rt.kernel_impl)
            o = st.combine_decode_partials(
                o_p, lse_p, rt.sp_axes).astype(x.dtype)
    else:
        s_loc = cache["k"].shape[1]
        with jax.named_scope("kv_write"):
            pos_k = rt.positions_contig(s_loc)
            # append the new K/V into its owning shard's slot
            rank = rt.sp_rank() if rt.mode == "spmd" else 0
            local_slot = cl - rank * s_loc
            write = jnp.arange(s_loc)[None] == local_slot[:, None]  # (B, S)
            if active is not None:
                write &= active[:, None]
            write = write[..., None, None]
            k_cache = jnp.where(write, k_new.astype(cache["k"].dtype),
                                cache["k"])
            v_cache = jnp.where(write, v_new.astype(cache["v"].dtype),
                                cache["v"])
            new_cache = {"k": k_cache, "v": v_cache}
        with jax.named_scope("dense"):
            valid = pos_k[None] <= cl[:, None]                  # (B, S_loc)
            # hide unfilled slots by pushing their positions beyond the query
            pos_k = jnp.where(valid, pos_k[None], (cl + 1)[:, None])
            cfg_st = dataclasses.replace(
                rt.st_cfg, causal=True, window=cfg.window, prefix_len=None)
            if rt.mode == "local":
                o = kernels.prefill(q, k_cache, v_cache, pos_new, pos_k,
                                    causal=True, window=cfg.window,
                                    impl=rt.kernel_impl)
            else:
                o = st.decode_attention(q, k_cache, v_cache, pos_new, pos_k,
                                        cfg_st)
    with jax.named_scope("out"):
        wo = rt.dense(p["wo"], ("heads", "head_dim", "embed_out"))
        return x + jnp.einsum("bshk,hkd->bsd", o, wo), new_cache


def _mamba_decode(rt: Runtime, p, x, cache, cfg: ModelConfig):
    m = cfg.mamba or MambaConfig()
    B = x.shape[0]
    D = cfg.d_model
    di = m.expand * D
    hm = di // m.head_dim
    n = m.d_state

    h = blocks.rmsnorm(p["norm_in"], x, cfg.norm_eps)
    proj = rt.dense(p["in_proj"], ("embed", "mamba_inner"))
    u = jnp.einsum("bsd,dx->bsx", h, proj)
    xin, z, Bc, Cc, dt_raw = jnp.split(
        u, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)

    conv = cache["conv"]                       # (B, K-1, di)
    window = jnp.concatenate([conv, xin], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    xc = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), w)[:, None]
    xc = jax.nn.silu(xc)
    conv_new = window[:, 1:]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # (B,1,Hm)
    decay = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32)) * dt)[:, 0]
    xh = xc.reshape(B, hm, m.head_dim)
    v = xh * dt[:, 0, :, None]
    state = cache["state"]                     # (B, Hm, N, P)
    state = state * decay[..., None, None] + jnp.einsum(
        "bn,bhp->bhnp", Bc[:, 0].astype(jnp.float32), v)
    y = jnp.einsum("bn,bhnp->bhp", Cc[:, 0].astype(jnp.float32), state)
    y = y + p["D_skip"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(B, 1, di)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = blocks.rmsnorm(p["norm"], y.astype(x.dtype), cfg.norm_eps)
    out_proj = rt.dense(p["out_proj"], ("mamba_inner", "embed_out"))
    return x + jnp.einsum("bsx,xd->bsd", y, out_proj), {
        "conv": conv_new, "state": state}


def _mlstm_decode(rt: Runtime, p, x, cache, cfg: ModelConfig):
    B = x.shape[0]
    h = blocks.rmsnorm(p["norm"], x, cfg.norm_eps)
    wq = rt.dense(p["wq"], ("embed", "heads", "head_dim"))
    wk = rt.dense(p["wk"], ("embed", "heads", "head_dim"))
    wv = rt.dense(p["wv"], ("embed", "heads", "head_dim"))
    wi = rt.dense(p["wi"], ("embed", "heads"))
    wf = rt.dense(p["wf"], ("embed", "heads"))
    wo = rt.dense(p["wo"], ("heads", "head_dim", "embed_out"))
    dk = wq.shape[-1]

    q = jnp.einsum("bsd,dhk->bhk", h[:, :1], wq)[:, None][:, 0] * dk ** -0.5
    k = jnp.einsum("bsd,dhk->bhk", h[:, :1], wk)
    v = jnp.einsum("bsd,dhk->bhk", h[:, :1], wv)
    ig = jax.nn.sigmoid(jnp.einsum("bsd,dh->bh", h[:, :1], wi).astype(jnp.float32))
    f = jax.nn.sigmoid(jnp.einsum("bsd,dh->bh", h[:, :1], wf).astype(jnp.float32))

    k = k.astype(jnp.float32) * ig[..., None]
    v_aug = jnp.concatenate(
        [v.astype(jnp.float32), jnp.ones((B, v.shape[1], 1), jnp.float32)], -1)
    state = cache["state"]                      # (B, H, dk, dv+1)
    state = state * f[..., None, None] + k[..., :, None] * v_aug[..., None, :]
    y_aug = jnp.einsum("bhk,bhkp->bhp", q.astype(jnp.float32), state)
    y, ndot = y_aug[..., :-1], y_aug[..., -1]
    y = y / jnp.maximum(jnp.abs(ndot), 1.0)[..., None]
    out = jnp.einsum("bhk,hkd->bd", y.astype(x.dtype), wo)[:, None]
    return x + out, {"state": state}


def _slstm_decode(rt: Runtime, p, x, cache, cfg: ModelConfig):
    B = x.shape[0]
    hq = cfg.num_heads
    dh = cfg.d_model // hq
    h = blocks.rmsnorm(p["norm"], x, cfg.norm_eps)
    wx = rt.dense(p["wx"], ("embed", "mamba_inner"))
    r = p["r"].astype(jnp.float32)
    wo = rt.dense(p["wo"], ("embed_nosplit", "embed_out"))

    gx = jnp.einsum("bsd,dg->bg", h[:, :1], wx).astype(jnp.float32)
    gx = gx.reshape(B, hq, 4 * dh)
    hs, cs = cache["h"], cache["c"]
    gr = jnp.einsum("bhk,hkg->bhg", hs, r)
    z, i, f, o = jnp.split(gx + gr, 4, axis=-1)
    cs = jax.nn.sigmoid(f) * cs + jax.nn.sigmoid(i) * jnp.tanh(z)
    hs = jax.nn.sigmoid(o) * jnp.tanh(cs)
    y = hs.reshape(B, 1, cfg.d_model).astype(x.dtype)
    return x + jnp.einsum("bsd,de->bse", y, wo), {"h": hs, "c": cs}


def _cross_decode(rt: Runtime, p, x, enc_out, cfg: ModelConfig):
    """Cross-attention for one decoder token vs the full encoder output."""
    h = blocks.rmsnorm(p["norm"], x, cfg.norm_eps)
    wq = rt.dense(p["wq"], ("embed", "heads", "head_dim"))
    wk = rt.dense(p["wk"], ("embed", "kv_heads", "head_dim"))
    wv = rt.dense(p["wv"], ("embed", "kv_heads", "head_dim"))
    wo = rt.dense(p["wo"], ("heads", "head_dim", "embed_out"))
    q = jnp.einsum("bsd,dhk->bshk", h, wq)
    k = jnp.einsum("bsd,dhk->bshk", enc_out, wk)
    v = jnp.einsum("bsd,dhk->bshk", enc_out, wv)
    s_loc = k.shape[1]
    pos_k = rt.positions_contig(s_loc)
    pos_q = jnp.array([0], jnp.int32)
    if rt.mode == "local":
        o = kernels.prefill(q, k, v, pos_q, pos_k, causal=False)
    else:
        cfg_st = dataclasses.replace(rt.st_cfg, causal=False, window=None)
        o = st.decode_attention(q, k, v, pos_q, pos_k, cfg_st)
    return x + jnp.einsum("bshk,hkd->bsd", o, wo)


# ---------------------------------------------------------------------------
# full decode step
# ---------------------------------------------------------------------------

def lm_decode_step(rt: Runtime, params, cache, tokens, cfg: ModelConfig,
                   cache_len, paged=None, active=None, sampling=None):
    """tokens: (B, 1) int32 (replicated across SP). Returns (next_token,
    new_cache).

    cache_len: static int, or (B,) traced per-sequence lengths (engine).
    paged: ``engine.paged_cache.PagedTables`` — attention caches are page
      pools instead of contiguous slices. Only all-attention stacks page
      (``paged_cache.supported``); their period-stacked pools ride in the
      layer scan's carry and each period writes and reads them in place at
      its scan index, so no step slices a period's pool out of the stack
      and writes it back. The contiguous caches (and SSM states) are
      per-slot and small, and scan as ``xs``/``ys``.
    active: (B,) bool — engine slots currently serving a request.
    sampling: None for greedy, or a dict {temperature, top_k, top_p, keys}
      of per-sequence (B,)-shaped arrays ((B, 2) for keys — PRNG keys *not*
      yet folded with the position; the fold happens here so solo and
      batched serving draw identical noise).
    """
    pat = transformer.layer_pattern(cfg)
    with jax.named_scope("decode/embed"):
        x = blocks.embed(rt, params["embed"], tokens, cfg,
                         tokens_replicated=True)

    def period_fn(x, p_and_cache):
        p, c = p_and_cache
        new_c = {}
        for i, (mixer, mlp) in enumerate(pat):
            sub_p, sub_c = p[f"sub{i}"], c[f"sub{i}"]
            with jax.named_scope(mixer):
                if mixer == "attn":
                    x, nc = _attn_decode(rt, sub_p["mixer"], x, sub_c, cfg,
                                         cache_len, active=active)
                elif mixer == "mamba":
                    x, nc = _mamba_decode(rt, sub_p["mixer"], x, sub_c, cfg)
                elif mixer == "mlstm":
                    x, nc = _mlstm_decode(rt, sub_p["mixer"], x, sub_c, cfg)
                else:
                    x, nc = _slstm_decode(rt, sub_p["mixer"], x, sub_c, cfg)
            new_c[f"sub{i}"] = nc
            x = _mlp(rt, mlp, sub_p, x, cfg)
        return x, new_c

    def paged_period_fn(carry, p_and_layer):
        x, pools = carry
        p, layer = p_and_layer
        pools = dict(pools)
        for i, (mixer, mlp) in enumerate(pat):
            assert mixer == "attn", "paged decode covers attention mixers"
            with jax.named_scope(mixer):
                x, pools[f"sub{i}"] = _attn_decode(
                    rt, p[f"sub{i}"]["mixer"], x, pools[f"sub{i}"], cfg,
                    cache_len, paged=paged, active=active, layer=layer)
            x = _mlp(rt, mlp, p[f"sub{i}"], x, cfg)
        return (x, pools), None

    n_p = jax.tree.leaves(params["stack"])[0].shape[0]
    unroll = n_p if rt.unroll_scans else 1
    with jax.named_scope("decode/layers"):
        if paged is None:
            x, new_subs = jax.lax.scan(
                period_fn, x, (params["stack"], cache["stack"]),
                unroll=unroll)
        else:
            (x, new_subs), _ = jax.lax.scan(
                paged_period_fn, (x, cache["stack"]),
                (params["stack"], jnp.arange(n_p, dtype=jnp.int32)),
                unroll=unroll)
    with jax.named_scope("decode/head"):
        x = blocks.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        head = params.get("lm_head", params["embed"])
        if sampling is None:
            next_tok = vocab_parallel_greedy(rt, head, x, cfg)
        else:
            from repro.engine import sampling as sampling_lib

            cl = jnp.asarray(cache_len, jnp.int32)
            if cl.ndim == 0:
                cl = jnp.broadcast_to(cl, (x.shape[0],))
            # key the noise by the sampled token's *position* so a request's
            # sample stream is independent of slot/step placement
            keys = jax.vmap(jax.random.fold_in)(sampling["keys"], cl + 1)
            next_tok = sampling_lib.sample(
                rt, head, x, cfg, temperature=sampling["temperature"],
                top_k=sampling["top_k"], top_p=sampling["top_p"], keys=keys,
                sc=sampling.get("sc", sampling_lib.SamplingConfig()))
    return next_tok, {"stack": new_subs}


def _mlp(rt: Runtime, kind: Optional[str], p, x, cfg: ModelConfig):
    """A period's channel mixer (``p["mlp"]``), if it has one, under the
    ``mlp`` / ``moe`` scope."""
    if kind not in ("mlp", "moe"):
        return x
    with jax.named_scope(kind):
        if kind == "mlp":
            return blocks.mlp_block(rt, p["mlp"], x, cfg)
        return moe_lib.moe_block(rt, p["mlp"], x, cfg)[0]


def vocab_parallel_greedy(rt: Runtime, head_params, x, cfg: ModelConfig):
    """Greedy next token without gathering full logits: local top-1 over this
    shard's vocab slice, then a lexicographic global combine. Ties break
    toward the lowest shard (pmin over winning ranks) and the lowest local
    index — deterministically the smallest global token id among the tied
    maxima (see ``engine.sampling.lowest_shard_argmax``)."""
    from repro.engine import sampling as sampling_lib

    return sampling_lib.greedy(rt, head_params, x, cfg)


def encdec_decode_step(rt: Runtime, params, cache, tokens,
                       cfg: ModelConfig, cache_len: int):
    """Decoder-side decode step with static encoder output in the cache."""
    enc_out = cache["enc_out"]
    x = blocks.embed(rt, params["embed"], tokens, cfg, tokens_replicated=True)

    def period_fn(x, pc):
        p, c = pc
        x, nc = _attn_decode(rt, p["attn"], x, c, cfg, cache_len)
        x = _cross_decode(rt, p["cross"], x, enc_out, cfg)
        x = blocks.mlp_block(rt, p["mlp"], x, cfg)
        return x, nc

    n_p = jax.tree.leaves(params["decoder"])[0].shape[0]
    x, new_sub = jax.lax.scan(period_fn, x,
                              (params["decoder"], cache["stack"]["sub0"]),
                              unroll=n_p if rt.unroll_scans else 1)
    x = blocks.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    next_tok = vocab_parallel_greedy(rt, params["lm_head"], x, cfg)
    return next_tok, {"stack": {"sub0": new_sub}, "enc_out": enc_out}


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------

def batch_axes_for(shape: ShapeConfig, mesh, multi_pod: bool):
    """Shard batch over (pod, data) when divisible, else replicate (B=1
    long-context decode gets all its parallelism from the SP axes)."""
    axes = ("pod", "data") if multi_pod else ("data",)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return axes if shape.global_batch % n == 0 else ()


def build_decode_step(model: Model, mesh, run_cfg: RunConfig,
                      shape: ShapeConfig):
    """Jitted decode step over the production mesh + input/cache specs."""
    cfg = model.cfg
    cache_len = shape.seq_len - 1
    b_axes = batch_axes_for(shape, mesh, run_cfg.multi_pod)
    rt = dataclasses.replace(
        train_step.make_runtime(model, run_cfg, shape, mode="spmd"),
        batch_axes=b_axes)
    # decode caches are contiguous-sharded
    rt = dataclasses.replace(
        rt, st_cfg=dataclasses.replace(rt.st_cfg, seq_scheme="contiguous"))

    param_specs = model.partition(run_cfg.sharding_rules)
    cache_specs_tree = kv_cache.cache_partition_for(cfg, b_axes)
    tok_spec = P(tuple(b_axes) if b_axes else None, None)

    if cfg.encdec:
        def island(params, cache, tokens):
            return encdec_decode_step(rt, params, cache, tokens, cfg,
                                      cache_len)
    else:
        def island(params, cache, tokens):
            return lm_decode_step(rt, params, cache, tokens, cfg, cache_len)

    fn = jax.shard_map(
        island, mesh=mesh,
        in_specs=(param_specs, cache_specs_tree, tok_spec),
        out_specs=(tok_spec, cache_specs_tree),
        check_vma=False)
    return jax.jit(fn), dict(rt=rt, cache_len=cache_len,
                             cache_specs=cache_specs_tree,
                             param_specs=param_specs, tok_spec=tok_spec)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def lm_prefill(rt: Runtime, params, batch, cfg: ModelConfig,
               prompt_len=None, return_hidden=False):
    """Full forward pass over the prompt, collecting the serving cache.

    batch: {tokens (B, S)[, frontend_emb]}. Returns (next_token, cache).
    Attention K/V stay SP-sharded in place (contiguous layout); SSM states
    come from the cross-shard-corrected final state of the last shard.

    prompt_len: optional traced (B,) int32 — real prompt lengths when the
      sequence is right-padded to a compile bucket (engine path); the
      next-token hidden state is taken from position ``prompt_len - 1``
      instead of the last slot. Causal attention makes right-padding
      harmless to every position before it.
    return_hidden: return the (B, 1, D) pre-head hidden state (replicated
      across SP) instead of a greedily sampled token, so callers can apply
      their own sampling.
    """
    pat = transformer.layer_pattern(cfg)
    tokens = batch["tokens"]
    prefix_len = None
    with jax.named_scope("prefill/embed"):
        x = blocks.embed(rt, params["embed"], tokens, cfg)
        if cfg.frontend_stub is not None and "frontend_emb" in batch:
            prefix_len = int(cfg.prefix_len_frac * rt.st_cfg.seq_len)
            pos = rt.positions(tokens.shape[1])
            is_prefix = (pos < prefix_len)[None, :, None]
            x = jnp.where(is_prefix, batch["frontend_emb"].astype(x.dtype),
                          x)

    def period_fn(x, p):
        caches = {}
        for i, (mixer, mlp) in enumerate(pat):
            sub = p[f"sub{i}"]
            with jax.named_scope(mixer):
                if mixer == "attn":
                    x, (k, v) = blocks.attention_block(
                        rt, sub["mixer"], x, cfg, causal=True,
                        window=cfg.window, prefix_len=prefix_len,
                        return_kv=True)
                    caches[f"sub{i}"] = {"k": k, "v": v}
                elif mixer == "mamba":
                    x, caches[f"sub{i}"] = ssm.mamba_block(
                        rt, sub["mixer"], x, cfg, return_state=True)
                elif mixer == "mlstm":
                    x, caches[f"sub{i}"] = ssm.mlstm_block(
                        rt, sub["mixer"], x, cfg, return_state=True)
                else:
                    x, caches[f"sub{i}"] = ssm.slstm_block(
                        rt, sub["mixer"], x, cfg, return_state=True)
            x = _mlp(rt, mlp, sub, x, cfg)
        return x, caches

    n_p = jax.tree.leaves(params["stack"])[0].shape[0]
    with jax.named_scope("prefill/layers"):
        x, cache = jax.lax.scan(period_fn, x, params["stack"],
                                unroll=n_p if rt.unroll_scans else 1)
    with jax.named_scope("prefill/head"):
        last = _prefill_last(rt, params, x, cfg, prompt_len)
        if return_hidden:
            return last, {"stack": cache}
        head = params.get("lm_head", params["embed"])
        return vocab_parallel_greedy(rt, head, last, cfg), {"stack": cache}


def _prefill_last(rt: Runtime, params, x, cfg: ModelConfig, prompt_len):
    """The final-normed hidden state of each sequence's last prompt
    position, (B, 1, D), replicated across SP."""
    x = blocks.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if prompt_len is None:
        # next token from the LAST position: the last SP shard's final slot
        # (contiguous layout); broadcast its hidden state then sample.
        last = x[:, -1:, :]
        if rt.mode == "spmd":
            is_last = rt.sp_rank() == rt.sp_size() - 1
            last = jax.lax.psum(
                jnp.where(is_last, last, jnp.zeros_like(last)), rt.sp_axes)
    else:
        # per-sequence last position prompt_len-1: exactly one (shard, slot)
        # matches, so a one-hot contraction + psum broadcasts it everywhere
        target = jnp.asarray(prompt_len, jnp.int32) - 1          # (B,)
        pos = rt.positions_contig(x.shape[1])                    # (S_loc,)
        onehot = (pos[None] == target[:, None]).astype(jnp.float32)
        last = jnp.einsum("bs,bsd->bd", onehot,
                          x.astype(jnp.float32))[:, None]
        if rt.mode == "spmd":
            last = jax.lax.psum(last, rt.sp_axes)
        last = last.astype(x.dtype)
    return last


def _attn_prefill_paged(rt: Runtime, p, x, pool_sub, cfg: ModelConfig,
                        cached_len, prompt_len, table_row, page_size: int):
    """One attention layer of the prefix-cached (suffix) prefill.

    x: (1, S_loc, D) — the prompt *suffix* (positions ``cached_len ..``),
      SP-sharded contiguously, right-padded to the compile bucket.
    pool_sub: {'k','v'} this shard's page-pool slices
      (pages_loc, page_size, Hkv, hd_pool) for this layer.
    table_row: (P_sp, W) the slot's full page-table row (static W — the
      suffix prefill runs once per request, so unlike the decode step it
      does not bucket the table width).
    cached_len / prompt_len: traced scalars — tokens served from the prefix
      cache / real prompt length.

    The suffix attends to two disjoint key sets and the partials merge
    exactly (``core.combine``):
      * **cached prefix** — this shard's round-robin pages, read in place
        (the tokens the cache hit lets us skip); queries are all-gathered
        so each shard scores every suffix query against its own pages, and
        a psum-combine (with lse) merges the shards;
      * **suffix itself** — K/V all-gathered over SP (O(suffix), the same
        order insert_prompt already pays), scored locally per shard.
    The same gathered suffix K/V is then scattered into this shard's owned
    pages, continuing the round-robin layout from block ``cached_len/ps``.

    Attention here goes through the dispatch layer with the runtime's
    ``kernel_impl``: the cached-prefix partial dispatches to
    ``kernels.paged_prefill`` (under 'pallas' the kernel DMAs prefix K/V
    tiles straight off the page table — no dense gather), and the suffix
    self-attention partial runs the shared-position flash kernel (its
    positions are 1-D traced vectors).
    """
    B, S_loc = x.shape[0], x.shape[1]
    sp = rt.sp_size()
    rank = rt.sp_rank()
    ps = page_size
    with jax.named_scope("qkv"):
        h = blocks.rmsnorm(p["norm"], x, cfg.norm_eps)
        wq = rt.dense(p["wq"], ("embed", "heads", "head_dim"))
        wk = rt.dense(p["wk"], ("embed", "kv_heads", "head_dim"))
        wv = rt.dense(p["wv"], ("embed", "kv_heads", "head_dim"))
        pos_loc = cached_len + rt.positions_contig(S_loc)       # (S_loc,)
        q = blocks.rope(jnp.einsum("bsd,dhk->bshk", h, wq), pos_loc,
                        cfg.rope_theta)
        k = blocks.rope(jnp.einsum("bsd,dhk->bshk", h, wk), pos_loc,
                        cfg.rope_theta)
        v = jnp.einsum("bsd,dhk->bshk", h, wv)
        kg = rt.all_gather_model(k, axis=1)          # (1, S_b, Hkv, hd)
        vg = rt.all_gather_model(v, axis=1)
        qg = rt.all_gather_model(q, axis=1)          # (1, S_b, Hq, hd)
    S_b = S_loc * sp
    tbl = jax.lax.dynamic_index_in_dim(table_row, rank, axis=0,
                                       keepdims=False)          # (W,)
    W = tbl.shape[0]
    pages_loc = pool_sub["k"].shape[0]

    with jax.named_scope("paged"):
        # --- cached-prefix partial: every suffix query vs this shard's pages
        o_pre, lse_pre = kernels.paged_prefill(
            qg, pool_sub["k"], pool_sub["v"], tbl[None],
            jnp.reshape(cached_len, (1,)).astype(jnp.int32), rank, sp=sp,
            page_size=ps, window=cfg.window, impl=rt.kernel_impl)
        o_pre, lse_pre = st.combine_partials_with_lse(o_pre, lse_pre,
                                                      rt.sp_axes)
        lo = rank * S_loc
        o_pre = jax.lax.dynamic_slice_in_dim(o_pre, lo, S_loc, axis=1)
        lse_pre = jax.lax.dynamic_slice_in_dim(lse_pre, lo, S_loc, axis=2)

        # --- suffix self-attention partial (local queries, gathered keys)
        pos_all = cached_len + jnp.arange(S_b, dtype=jnp.int32)
        o_suf, lse_suf = kernels.block_fwd(
            q, kg, vg, pos_loc, pos_all, causal=True, window=cfg.window,
            impl=rt.kernel_impl)
        o, _ = combine.combine_pair(o_pre, lse_pre, o_suf, lse_suf)
    with jax.named_scope("out"):
        wo = rt.dense(p["wo"], ("heads", "head_dim", "embed_out"))
        x = x + jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), wo)

    with jax.named_scope("kv_write"):
        # --- scatter the suffix K/V into this shard's owned pages
        G = S_b // ps
        kb = kg[0].reshape(G, ps, *kg.shape[2:])
        vb = vg[0].reshape(G, ps, *vg.shape[2:])
        start_block = cached_len // ps               # cached_len % ps == 0
        gidx = jnp.arange(G, dtype=jnp.int32)
        gglob = start_block + gidx
        j = gglob // sp
        page = tbl[jnp.clip(j, 0, W - 1)]
        mine = ((gglob % sp) == rank) \
            & (gidx * ps < prompt_len - cached_len) & (j < W) & (page >= 0)
        page = jnp.where(mine, page, pages_loc)      # OOB -> drop
        hd_pool = pool_sub["k"].shape[-1]
        pool_k = pool_sub["k"].at[page].set(
            kernels.pad_head(kb, hd_pool).astype(pool_sub["k"].dtype),
            mode="drop")
        pool_v = pool_sub["v"].at[page].set(
            kernels.pad_head(vb, hd_pool).astype(pool_sub["v"].dtype),
            mode="drop")
    return x, {"k": pool_k, "v": pool_v}


def lm_prefill_paged(rt: Runtime, params, batch, cfg: ModelConfig, *,
                     prompt_len, cached_len, pools, table_row,
                     page_size: int):
    """Prefix-cached prefill: forward only the prompt *suffix*, reading the
    cached prefix KV from the paged pool and writing the suffix KV into the
    reserved pages. Returns ``(last_hidden, new_pools)`` with the (1, 1, D)
    hidden state of position ``prompt_len - 1`` replicated across SP.

    batch: {tokens: (1, S_bucket)} — the suffix tokens (prompt positions
      ``cached_len ..``), right-padded; prompt_len/cached_len: (1,) traced.
    pools: {'stack': {subN: {'k','v'}}} this shard's full pool slices
      (n_periods leading dim, scanned with the params).
    table_row: (P_sp, W) the admitted slot's page-table row.

    Only all-attention stacks reach this path (``paged_cache.supported``
    gates the engine), so every mixer here is 'attn'.
    """
    pat = transformer.layer_pattern(cfg)
    cl = jnp.asarray(prompt_len, jnp.int32)[0]
    cc = jnp.asarray(cached_len, jnp.int32)[0]
    tokens = batch["tokens"]
    with jax.named_scope("prefill/embed"):
        x = blocks.embed(rt, params["embed"], tokens, cfg)

    def period_fn(x, p_and_pool):
        p, pool = p_and_pool
        new_pool = {}
        for i, (mixer, mlp) in enumerate(pat):
            assert mixer == "attn", "paged prefill covers attention mixers"
            # MoE is unreachable too: Engine rejects prefix caching for MoE
            # stacks (capacity couples prefix KV to the suffix)
            assert mlp != "moe", "prefix-cached prefill excludes MoE"
            with jax.named_scope("attn"):
                x, new_pool[f"sub{i}"] = _attn_prefill_paged(
                    rt, p[f"sub{i}"]["mixer"], x, pool[f"sub{i}"], cfg,
                    cc, cl, table_row, page_size)
            x = _mlp(rt, mlp, p[f"sub{i}"], x, cfg)
        return x, new_pool

    n_p = jax.tree.leaves(params["stack"])[0].shape[0]
    with jax.named_scope("prefill/layers"):
        x, new_subs = jax.lax.scan(period_fn, x,
                                   (params["stack"], pools["stack"]),
                                   unroll=n_p if rt.unroll_scans else 1)
    with jax.named_scope("prefill/head"):
        x = blocks.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        # last real position prompt_len-1 sits at suffix offset
        # pl-1-cached_len: one (shard, slot) matches; one-hot contraction +
        # psum broadcasts it
        target = cl - 1 - cc
        pos = rt.positions_contig(x.shape[1])
        onehot = (pos == target).astype(jnp.float32)[None]
        last = jnp.einsum("bs,bsd->bd", onehot,
                          x.astype(jnp.float32))[:, None]
        last = jax.lax.psum(last, rt.sp_axes).astype(x.dtype)
    return last, {"stack": new_subs}


def encdec_prefill(rt: Runtime, params, batch, cfg: ModelConfig):
    """Encoder forward + empty decoder cache (seamless serving entry)."""
    from repro.models import encdec as encdec_lib
    from jax.ad_checkpoint import checkpoint_name

    fp = rt.dense(params["frontend_proj"], ("embed_nosplit", "embed_out"))
    x = jnp.einsum("bsd,de->bse", batch["frontend_emb"].astype(fp.dtype), fp)

    def enc_body(x, p):
        x = blocks.attention_block(rt, p["attn"], x, cfg, causal=False)
        x = blocks.mlp_block(rt, p["mlp"], x, cfg)
        return x, None

    x, _ = jax.lax.scan(enc_body, x, params["encoder"])
    enc_out = blocks.rmsnorm(params["enc_norm"], x, cfg.norm_eps)
    return enc_out


def build_prefill_step(model: Model, mesh, run_cfg: RunConfig,
                       shape: ShapeConfig):
    """Jitted prefill over the production mesh."""
    cfg = model.cfg
    b_axes = batch_axes_for(shape, mesh, run_cfg.multi_pod)
    rt = dataclasses.replace(
        train_step.make_runtime(model, run_cfg, shape, mode="spmd"),
        batch_axes=b_axes)
    rt = dataclasses.replace(
        rt, st_cfg=dataclasses.replace(rt.st_cfg, seq_scheme="contiguous"))

    param_specs = model.partition(run_cfg.sharding_rules)
    seq = shard_rules.SP_AXES
    b = tuple(b_axes) if b_axes else None
    batch_specs = {"tokens": P(b, seq)}
    if cfg.frontend_stub is not None:
        batch_specs["frontend_emb"] = P(b, seq, None)
    tok_spec = P(b, None)

    if cfg.encdec:
        def island(params, batch):
            return encdec_prefill(rt, params, batch, cfg)

        out_specs = P(b, seq, None)
    else:
        def island(params, batch):
            return lm_prefill(rt, params, batch, cfg)

        cache_part = kv_cache.cache_partition_for(cfg, b_axes)
        out_specs = (tok_spec, {"stack": cache_part["stack"]})

    fn = jax.shard_map(island, mesh=mesh, in_specs=(param_specs, batch_specs),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn), dict(rt=rt, batch_specs=batch_specs,
                             param_specs=param_specs)
