"""Pallas TPU paged-decode flash kernel: one query token per sequence
against this shard's page-table-indexed slice of a paged KV pool.

This is the serving-side analogue of ``flash_attention.py``: instead of
gathering a sequence's pages into a dense per-shard cache (the pure-jnp
reference path — one full copy of the cache through HBM per decode step),
the page table is handed to the kernel as a *scalar-prefetch* operand and
the ``BlockSpec`` index map DMAs each K/V page straight from the pool:

    pool_k, pool_v : (L, pages_loc, page_size, Hkv, D) this shard's pools,
                     stacked over the L layer periods
    table          : (B, W) int32                     local page ids, -1 = unallocated
    cache_len      : (B,) int32                       the new token's position
    rank           : (1,) int32                       this shard's SP rank (traced)
    layer          : (1,) int32                       the period to read (traced)

The layer index rides with the other scalars, so the decode step's layer
scan hands the kernel the whole stacked pool and its counter: no layer's
pool is sliced out and written back around the call.

Grid ``(B, W)`` with the page dimension innermost. Each step DMAs one whole
page ``(page_size, Hkv, D)`` — its last two dims span the pool's, as the
TPU lowering requires — and serves every head from it: the query heads are
grouped by their K/V head, ``(B, Hkv, G, D)`` with G = Hq // Hkv, and a
static loop over the K/V heads runs the online softmax whose statistics
(m, l, acc) live in VMEM scratch across the W steps. Pages that are unallocated (``table < 0``), fully
in the causal future, or fully outside the sliding window are skipped with
``pl.when`` — the skip test only reads prefetched scalars, so a skipped
page costs no FLOPs.

Validity is *position-encoded*, matching the repo-wide contract: a key at
position p is visible iff ``p <= cache_len`` (causal; the query sits at
``cache_len``) and, with a window, ``cache_len - p < window``. Rows with no
visible key anywhere (inactive engine slots) finalise to ``(o=0,
lse=-inf)`` so the cross-shard lse-combine drops them exactly.

Returns *partial* ``(o, lse)`` in float32 — block-attention semantics, to
be merged across SP shards by ``core.startrail.combine_decode_partials``.
Validated in ``interpret=True`` mode against ``ref.block_attention`` over
the dense gather of the same pages (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.combine import NEG_INF


def _kernel(tbl_ref, cl_ref, rank_ref, layer_ref,       # scalar prefetch
            q_ref, k_ref, v_ref,                        # inputs
            o_ref, lse_ref,                             # outputs
            acc_ref, m_ref, l_ref,                      # scratch
            *, sp, page_size, window, scale, n_w, n_kv):
    b = pl.program_id(0)
    w = pl.program_id(1)
    del layer_ref                                        # read by the DMAs

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cl = cl_ref[b]
    page = tbl_ref[b, w]
    base = (w * sp + rank_ref[0]) * page_size
    live = (page >= 0) & (base <= cl)
    if window is not None:
        # newest visible position is cl; oldest is cl - window + 1
        live &= (cl - (base + page_size - 1)) < window

    @pl.when(live)
    def _compute():
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        valid = pos <= cl
        if window is not None:
            valid &= (cl - pos) < window
        for hk in range(n_kv):                           # static head loop
            q = q_ref[0, hk].astype(jnp.float32)         # (G, D)
            k = k_ref[0, :, hk, :].astype(jnp.float32)   # (ps, D)
            v = v_ref[0, :, hk, :].astype(jnp.float32)   # (ps, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, ps)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[hk]                           # (G, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.where(m_cur <= NEG_INF / 2, 0.0, m_cur)
            p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
            alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                              jnp.exp(m_prev - m_safe))
            l_ref[hk] = l_ref[hk] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[hk] = acc_ref[hk] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[hk] = m_cur

    @pl.when(w == n_w - 1)
    def _finalize():
        m = m_ref[...]
        l = l_ref[...]
        dead = m <= NEG_INF / 2
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(
            dead, NEG_INF, jnp.where(dead, 0.0, m) + jnp.log(l_safe)
        ).astype(lse_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sp", "page_size", "window", "scale", "interpret"),
)
def paged_decode_attention(
    q, pool_k, pool_v, table, cache_len, rank, layer=0, *, sp, page_size,
    window=None, scale=None, interpret=None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-shard paged decode attention -> partial (o, lse).

    q: (B, 1, Hq, D); pool_k/pool_v: (L, pages_loc, page_size, Hkv, D),
    read at period ``layer`` (traced scalar), or one layer's
    (pages_loc, page_size, Hkv, D) (L = 1, ``layer`` 0); table: (B, W)
    int32; cache_len: (B,) int32; rank: (1,) int32 (traced —
    ``jax.lax.axis_index`` products are fine). Page ``w`` of row ``b``
    covers global positions ``[(w*sp + rank)*page_size, ... + page_size)``
    — the round-robin layout of ``engine.paged_cache``.
    """
    B, M, Hq, D = q.shape
    if M != 1:
        raise ValueError(f"paged decode takes one query per row, got M={M}")
    if pool_k.ndim == 4:
        pool_k, pool_v = pool_k[None], pool_v[None]
    _, pages_loc, ps, Hkv, _ = pool_k.shape
    if ps != page_size:
        raise ValueError(f"pool page size {ps} != page_size {page_size}")
    G = Hq // Hkv
    W = table.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    kernel = functools.partial(
        _kernel, sp=sp, page_size=page_size, window=window, scale=scale,
        n_w=W, n_kv=Hkv)

    def page_idx(b, w, tbl, cl, rk, ly):
        # -1 (unallocated) clips to page 0; the kernel masks it via pl.when
        del cl, rk
        return (ly[0], jnp.maximum(tbl[b, w], 0), 0, 0, 0)

    def row_idx(b, w, tbl, cl, rk, ly):
        del w, tbl, cl, rk, ly
        return (b, 0, 0, 0)

    # every head of a row in one tile: the query heads grouped by their K/V
    # head, (B, Hkv, G, D), and whole pages, whose last two dims (Hkv, D)
    # span the pool's — one DMA per page serves all heads
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, W),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), row_idx),
            pl.BlockSpec((pl.squeezed, 1, page_size, Hkv, D), page_idx),
            pl.BlockSpec((pl.squeezed, 1, page_size, Hkv, D), page_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, G, D), row_idx),
            pl.BlockSpec((1, Hkv, G, 1), row_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))

    o, lse = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        ],
        interpret=interpret,
        **params,
    )(table.astype(jnp.int32), cache_len.astype(jnp.int32),
      jnp.asarray(rank, jnp.int32).reshape(1),
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(B, Hkv, G, D),
      pool_k, pool_v)
    return o.reshape(B, 1, Hq, D), lse.reshape(B, Hq, 1)
