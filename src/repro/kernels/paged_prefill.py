"""Pallas TPU paged-suffix prefill kernel: a block of suffix queries
against this shard's page-table-indexed slice of the paged KV pool.

This is the prefill-side sibling of ``kernels/paged_decode.py``. During a
prefix-cached (or chunked) prefill, the suffix queries at positions
``cached_len ..`` must attend to the *cached prefix* — tokens already
sitting in the SP-sharded page pool. The reference path gathers this
shard's pages into a dense ``(W * page_size)`` view (one full copy of the
prefix through HBM per layer); here the page table rides in as a
*scalar-prefetch* operand and the ``BlockSpec`` index map DMAs each K/V
page tile straight from the pool:

    q              : (B, Sq, Hq, D)     suffix queries (pos cached_len + i)
    pool_k, pool_v : (pages_loc, page_size, Hkv, D)  this shard's pool slice
    table          : (B, W) int32       local page ids, -1 = unallocated
    cached_len     : (B,) int32         tokens already in the pool
    rank           : (1,) int32         this shard's SP rank (traced)

Grid ``(B, n_q, W)`` with the page dimension innermost. Each step DMAs one
whole page ``(page_size, Hkv, D)`` — its last two dims span the pool's, as
the TPU lowering requires — and serves every head from it: the queries are
heads-major ``(B, Hq, Sq, D)`` tiles, and static loops over the K/V heads
and their G = Hq // Hkv query heads run the online softmax, whose
statistics (m, l, acc) persist in VMEM scratch across the W steps. Pages that are unallocated (``table < 0``), entirely at or past
``cached_len`` (suffix pages being written this very call), or fully
outside the sliding window are skipped with ``pl.when`` — the skip test
reads only prefetched scalars, so a masked page costs no FLOPs and no
extra mask stream.

A key at position p is visible iff ``p < cached_len`` (strict: the suffix
itself is scored by the dense self-attention partial, not here) and, with
a window, ``pos_q - p < window``. Causality against the suffix queries is
then automatic (``p < cached_len <= pos_q``). Rows with no visible key —
every row when ``cached_len = 0``, bucket padding rows, all rows of a
window that has slid past the prefix — finalise to ``(o=0, lse=-inf)``,
so ``core.startrail.combine_partials_with_lse`` and the pairwise merge
with the suffix partial stay exact.

Returns partial ``(o, lse)`` in float32. Validated in ``interpret=True``
mode against the dense-gather reference (tests/test_prefill_kernels.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.combine import NEG_INF
from repro.kernels.flash_attention import SUBLANES, choose_block

# every head's (block_q, D) tile, accumulator and statistics share VMEM:
# at 32 heads a 128-row tile overflows the 16 MiB scoped default
DEFAULT_BLOCK_Q = 64


def _kernel(tbl_ref, cl_ref, rank_ref,                  # scalar prefetch
            q_ref, k_ref, v_ref,                        # inputs
            o_ref, lse_ref,                             # outputs
            acc_ref, m_ref, l_ref,                      # scratch
            *, sp, page_size, window, scale, block_q, n_w, n_kv, group):
    b = pl.program_id(0)
    iq = pl.program_id(1)
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cl = cl_ref[b]
    page = tbl_ref[b, w]
    base = (w * sp + rank_ref[0]) * page_size
    live = (page >= 0) & (base < cl)
    if window is not None:
        # the oldest query in this tile sits at cl + iq*block_q; a page
        # whose newest key is already out of its window is dead for the
        # whole tile
        live &= (cl + iq * block_q - (base + page_size - 1)) < window

    @pl.when(live)
    def _compute():
        pos_k = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)                # (1, ps)
        valid = pos_k < cl                               # strict: prefix only
        if window is not None:
            pos_q = cl + iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)              # (bq, 1)
            valid = valid & ((pos_q - pos_k) < window)   # (bq, ps)
        for hk in range(n_kv):                           # static head loops
            k = k_ref[0, :, hk, :].astype(jnp.float32)   # (ps, D)
            v = v_ref[0, :, hk, :].astype(jnp.float32)   # (ps, D)
            for g in range(group):
                h = hk * group + g
                q = q_ref[0, h].astype(jnp.float32)      # (bq, D)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (bq, ps)
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_ref[h]                        # (bq, 1)
                m_cur = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                m_safe = jnp.where(m_cur <= NEG_INF / 2, 0.0, m_cur)
                p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
                alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                                  jnp.exp(m_prev - m_safe))
                l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_cur

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
    static_argnames=("sp", "page_size", "window", "scale", "block_q",
                     "interpret"),
)
def paged_prefill_attention(
    q, pool_k, pool_v, table, cached_len, rank, *, sp, page_size,
    window=None, scale=None, block_q=DEFAULT_BLOCK_Q, interpret=None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-shard suffix-vs-prefix paged attention -> partial (o, lse).

    q: (B, Sq, Hq, D) suffix queries — row b's query i sits at global
    position ``cached_len[b] + i`` (bucket-padding rows past the real
    suffix simply score the same prefix; the caller's lse-combine with the
    positionally-masked suffix partial keeps them exact). pool_k/pool_v:
    (pages_loc, page_size, Hkv, D); table: (B, W) int32; cached_len: (B,)
    int32; rank: (1,) int32 (traced). Page ``w`` of row ``b`` covers global
    positions ``[(w*sp + rank)*page_size, ... + page_size)`` — the
    round-robin layout of ``engine.paged_cache``.
    """
    B, Sq, Hq, D = q.shape
    pages_loc, ps, Hkv, _ = pool_k.shape
    if ps != page_size:
        raise ValueError(f"pool page size {ps} != page_size {page_size}")
    G = Hq // Hkv
    W = table.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block_q = choose_block(Sq, block_q, 1 if interpret else SUBLANES)
    n_q = Sq // block_q

    kernel = functools.partial(
        _kernel, sp=sp, page_size=page_size, window=window, scale=scale,
        block_q=block_q, n_w=W, n_kv=Hkv, group=G)

    def page_idx(b, iq, w, tbl, cl, rk):
        # -1 (unallocated) clips to page 0; the kernel masks it via pl.when
        del iq, cl, rk
        return (jnp.maximum(tbl[b, w], 0), 0, 0, 0)

    def tile_idx(b, iq, w, tbl, cl, rk):
        del w, tbl, cl, rk
        return (b, 0, iq, 0)

    # one query tile of every head, heads-major (B, Hq, Sq, D), against
    # whole pages, whose last two dims (Hkv, D) span the pool's — one DMA
    # per page serves all heads
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_q, W),
        in_specs=[
            pl.BlockSpec((1, Hq, block_q, D), tile_idx),
            pl.BlockSpec((1, page_size, Hkv, D), page_idx),
            pl.BlockSpec((1, page_size, Hkv, D), page_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, Hq, block_q, D), tile_idx),
            pl.BlockSpec((1, Hq, block_q, 1), tile_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hq, block_q, D), jnp.float32),
            pltpu.VMEM((Hq, block_q, 1), jnp.float32),
            pltpu.VMEM((Hq, block_q, 1), jnp.float32),
        ],
    )
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    o, lse = pl.pallas_call(
        kernel,
        name="paged_prefill_attention",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
        ],
        interpret=interpret,
        **params,
    )(table.astype(jnp.int32), cached_len.astype(jnp.int32),
      jnp.asarray(rank, jnp.int32).reshape(1), jnp.swapaxes(q, 1, 2),
      pool_k, pool_v)
    return jnp.swapaxes(o, 1, 2), lse[..., 0]
