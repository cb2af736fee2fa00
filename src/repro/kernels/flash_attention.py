"""Pallas TPU flash-attention kernels (the per-ring-step compute hot spot).

TPU-native adaptation of the paper's FlashAttention usage: blocks are tiled
for VMEM with MXU-aligned shapes (multiples of 128 on the matmul dims), the
online-softmax statistics (m, l, acc) live in VMEM scratch that persists
across the innermost (K/V-block) grid dimension, and fully-masked tiles are
skipped with ``pl.when`` using the position metadata (this is what makes
zigzag/causal and sliding-window cheap inside a ring step).

Layouts at the API match ``repro.kernels.ref``:
    q (B, Sq, Hq, D); k, v (B, Sk, Hkv, D); o (B, Sq, Hq, D); lse (B, Hq, Sq)
    pos_q (Sq,) or (B, Sq); pos_k (Sk,) or (B, Sk)
Inside the wrappers the heads move ahead of the sequence, ``(B, H, S, D)``,
so every tile is a ``(block, D)`` slab whose last two dimensions the TPU
lowering accepts (block a multiple of 8, D the full head dim). Per-row
statistics travel as ``(B, H, S, 1)`` columns, query positions as
``(B, S, 1)`` columns and key positions as ``(B, 1, S)`` rows, so the tile
mask is a broadcast compare. GQA is native: the K/V block index maps divide
the query-head index by G = Hq // Hkv.

Shared ``(S,)`` positions (training) and per-row ``(B, S)`` positions (the
engine's ragged serving buckets, where an unfilled key slot is pushed past
every query) run the same kernels. Tile liveness — may this (Q, K/V) tile
hold any visible entry — is computed once per call from per-tile position
bounds into a table that rides in SMEM as a scalar-prefetch operand, so a
skipped tile costs no FLOPs. Rows whose every key is masked finalise to
``(o=0, lse=-inf)``, exact under ``core.combine.combine_pair``.

Validated in ``interpret=True`` mode on CPU against ``ref.py``
(tests/test_kernels.py, tests/test_prefill_kernels.py) and compiled for a
described TPU v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.combine import NEG_INF

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
# TPU lowering: a tile's second-minor dim must be a multiple of 8 and its
# minor dim a multiple of 128, unless the tile spans the whole dimension
SUBLANES = 8
LANES = 128


def _mask_tile(pos_q, pos_k, causal, window, prefix_len=None):
    """(bq, bk) bool mask from a (bq, 1) query column and a (1, bk) key row;
    None = all visible."""
    if not causal and window is None:
        return None
    m = None
    if causal:
        m = pos_k <= pos_q
        if prefix_len is not None:
            m |= pos_k < prefix_len
    if window is not None:
        wm = (pos_q - pos_k) < window
        if not causal:
            wm &= (pos_k - pos_q) < window
        if prefix_len is not None:
            wm |= pos_k < prefix_len
        m = wm if m is None else m & wm
    return m


def _tile_live(lo_q, hi_q, lo_k, hi_k, causal, window, prefix_len=None):
    """Does a tile with these position bounds hold any unmasked entry?"""
    live = jnp.ones(jnp.broadcast_shapes(jnp.shape(lo_q), jnp.shape(lo_k)),
                    jnp.bool_)
    if causal:
        live &= lo_k <= hi_q
    if window is not None:
        live &= (lo_q - hi_k) < window
        if not causal:
            live &= (lo_k - hi_q) < window
    if prefix_len is not None:
        live |= lo_k < prefix_len
    return live


def choose_block(s: int, pref: int, align: int = 1) -> int:
    """Largest tile size <= pref dividing s and a multiple of ``align``
    (non-power-of-two rows tile at their largest aligned divisor instead of
    raising); the whole dimension when no such divisor exists."""
    for d in range(min(pref, s), 0, -1):
        if s % d == 0 and d % align == 0:
            return d
    return s


def _rows(pos, S):
    """Positions as int32 (Bp, S) rows, Bp = 1 for a shared vector."""
    pos = jnp.asarray(pos, jnp.int32)
    return pos.reshape(1, S) if pos.ndim == 1 else pos


def _live_table(pq, pk, block_q, block_k, causal, window, prefix_len):
    """Flat int32 ``(Bl * n_q * n_k,)`` tile-liveness table, Bl = 1 when
    both position arrays are shared. Row-major over (b, iq, ik)."""
    bq_rows, sq = pq.shape
    bk_rows, sk = pk.shape
    tq = pq.reshape(bq_rows, sq // block_q, block_q)
    tk = pk.reshape(bk_rows, sk // block_k, block_k)
    live = _tile_live(tq.min(-1)[:, :, None], tq.max(-1)[:, :, None],
                      tk.min(-1)[:, None, :], tk.max(-1)[:, None, :],
                      causal, window, prefix_len)
    return live.astype(jnp.int32).reshape(-1)


class _Layout:
    """Tiles, grid sizes and index helpers shared by the fwd/bwd wrappers."""

    def __init__(self, q, k, pos_q, pos_k, block_q, block_k, interpret,
                 causal, window, prefix_len):
        B, Sq, Hq, D = q.shape
        _, Sk, Hkv, _ = k.shape
        self.B, self.Sq, self.Sk, self.Hq, self.Hkv, self.D = \
            B, Sq, Sk, Hq, Hkv, D
        self.G = Hq // Hkv
        # the key-position row tile is lane-minor, so it needs 128-alignment
        self.block_q = choose_block(Sq, block_q, 1 if interpret else SUBLANES)
        self.block_k = choose_block(Sk, block_k, 1 if interpret else LANES)
        self.n_q, self.n_k = Sq // self.block_q, Sk // self.block_k
        pq, pk = _rows(pos_q, Sq), _rows(pos_k, Sk)
        self.pq_b, self.pk_b = pq.shape[0] > 1, pk.shape[0] > 1
        self.live = _live_table(pq, pk, self.block_q, self.block_k, causal,
                                window, prefix_len)
        self.live_b = self.pq_b or self.pk_b
        self.pos_q_col = pq[:, :, None]                  # (Bp, Sq, 1)
        self.pos_k_row = pk[:, None, :]                  # (Bp, 1, Sk)

    def live_index(self, b, iq, ik):
        bl = b if self.live_b else 0
        return (bl * self.n_q + iq) * self.n_k + ik

    def pos_q_spec(self, iq_of):
        """BlockSpec of the query-position column; ``iq_of(*grid)`` -> iq."""
        pb = self.pq_b
        return pl.BlockSpec(
            (1, self.block_q, 1),
            lambda b, *g: (b if pb else 0, iq_of(b, *g), 0))

    def pos_k_spec(self, ik_of):
        pb = self.pk_b
        return pl.BlockSpec(
            (1, 1, self.block_k),
            lambda b, *g: (b if pb else 0, 0, ik_of(b, *g)))


def _heads_major(x):
    """(B, S, H, D) -> (B, H, S, D)."""
    return jnp.swapaxes(x, 1, 2)


def _compiler_params(interpret, n_parallel):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",))}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_accumulate(pos_q, pos_k, q, k, v, acc_ref, m_ref, l_ref, *, causal,
                    window, scale, prefix_len):
    """One K/V tile's online-softmax update of the (acc, m, l) scratch.

    q (bq, D), k/v (bk, D) f32; pos_q (bq, 1), pos_k (1, bk)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)
    mask = _mask_tile(pos_q, pos_k, causal, window, prefix_len)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]                                # (bq, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(m_cur <= NEG_INF / 2, 0.0, m_cur)
    p = jnp.exp(s - m_safe)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur


def _block_partial(acc_ref, m_ref, l_ref):
    """(o_blk, lse_blk) f32 of the accumulated tiles; dead rows -> lse=-inf."""
    m = m_ref[...]
    l = l_ref[...]
    dead = m <= NEG_INF / 2
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_blk = acc_ref[...] / l_safe
    lse_blk = jnp.where(
        dead, NEG_INF, jnp.where(dead, 0.0, m) + jnp.log(l_safe))
    return o_blk, lse_blk


def _fwd_kernel(live_ref,                                   # scalar prefetch
                pq_ref, pk_ref, q_ref, k_ref, v_ref, *refs, causal, window,
                scale, prefix_len, lay, merge):
    """Grid (B, Hq, n_q, n_k). With ``merge`` the ring-step combine is fused
    into the epilogue: instead of writing the block partial and paying a
    separate full-array ``combine_pair`` pass over the f32 accumulator, the
    finalize reads the running ``(o_acc, lse_acc)`` tile and emits the
    rescaled merge directly — the exact op sequence of
    ``core.combine.combine_pair``, in-register."""
    if merge:
        o_acc_ref, lse_acc_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live_ref[lay.live_index(b, iq, ik)] != 0)
    def _compute():
        _fwd_accumulate(
            pq_ref[0], pk_ref[0], q_ref[0, 0].astype(jnp.float32),
            k_ref[0, 0].astype(jnp.float32), v_ref[0, 0].astype(jnp.float32),
            acc_ref, m_ref, l_ref, causal=causal, window=window, scale=scale,
            prefix_len=prefix_len)

    @pl.when(ik == lay.n_k - 1)
    def _finalize():
        o_blk, lse_blk = _block_partial(acc_ref, m_ref, l_ref)
        if not merge:
            o_ref[0, 0] = o_blk.astype(o_ref.dtype)
            lse_ref[0, 0] = lse_blk.astype(lse_ref.dtype)
            return
        o_prev = o_acc_ref[0, 0].astype(jnp.float32)
        lse_prev = lse_acc_ref[0, 0].astype(jnp.float32)
        # combine_pair(o_prev, lse_prev, o_blk, lse_blk), op for op
        m2 = jnp.maximum(lse_prev, lse_blk)
        both_dead = m2 <= NEG_INF / 2
        m2_safe = jnp.where(both_dead, 0.0, m2)
        w1 = jnp.exp(lse_prev - m2_safe)
        w2 = jnp.exp(lse_blk - m2_safe)
        denom = w1 + w2
        denom_safe = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0] = ((w1 * o_prev + w2 * o_blk)
                       / denom_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            both_dead, NEG_INF, m2_safe + jnp.log(denom_safe)
        ).astype(lse_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "prefix_len", "block_q",
                     "block_k", "interpret"),
)
def flash_attention_fwd(
    q, k, v, pos_q, pos_k, o_acc=None, lse_acc=None, *, causal=True,
    window=None, scale=None, prefix_len=None, block_q=DEFAULT_BLOCK_Q,
    block_k=DEFAULT_BLOCK_K, interpret=None,
) -> Tuple[jax.Array, jax.Array]:
    """Block flash attention -> (o, lse). Same semantics as ref.block_attention.

    Positions are shared ``(S,)`` vectors or per-row ``(B, S)`` arrays
    (ragged serving buckets). With ``(o_acc, lse_acc)`` — a running partial
    accumulator of shapes ``(B, Sq, Hq, D)`` / ``(B, Hq, Sq)`` — the
    per-ring-step combine is fused into the kernel epilogue: the result is
    ``combine_pair(o_acc, lse_acc, *flash_attention_fwd(...))`` without the
    separate full-array pass over the f32 accumulator.
    """
    if (o_acc is None) != (lse_acc is None):
        raise ValueError("o_acc and lse_acc must be passed together")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    lay = _Layout(q, k, pos_q, pos_k, block_q, block_k, interpret, causal,
                  window, prefix_len)
    B, Sq, Hq, D, G = lay.B, lay.Sq, lay.Hq, lay.D, lay.G
    bq, bk = lay.block_q, lay.block_k
    merge = o_acc is not None

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik, lv: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D),
                           lambda b, h, iq, ik, lv: (b, h // G, ik, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1),
                            lambda b, h, iq, ik, lv: (b, h, iq, 0))
    in_specs = [lay.pos_q_spec(lambda b, h, iq, ik, lv: iq),
                lay.pos_k_spec(lambda b, h, iq, ik, lv: ik),
                q_spec, kv_spec, kv_spec]
    inputs = [lay.pos_q_col, lay.pos_k_row, _heads_major(q), _heads_major(k),
              _heads_major(v)]
    if merge:
        in_specs += [q_spec, row_spec]
        inputs += [_heads_major(o_acc.astype(jnp.float32)),
                   lse_acc.astype(jnp.float32)[..., None]]

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, window=window,
                          scale=scale, prefix_len=prefix_len, lay=lay,
                          merge=merge),
        name="flash_attention_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hq, lay.n_q, lay.n_k),
            in_specs=in_specs,
            out_specs=[q_spec, row_spec],
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 3),
    )(lay.live, *inputs)
    return _heads_major(o), lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_probs(pos_q, pos_k, q, k, lse, *, causal, window, scale,
               prefix_len):
    """True attention probabilities p (bq, bk) of one tile from the global
    lse (bq, 1)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _mask_tile(pos_q, pos_k, causal, window, prefix_len)
    if mask is not None:
        # mask BEFORE exp: masked raw scores can exceed lse -> inf*0=NaN
        s = jnp.where(mask, s, NEG_INF)
    dead = lse <= NEG_INF / 2
    p = jnp.exp(s - jnp.where(dead, 0.0, lse))
    return jnp.where(dead, 0.0, p)


def _bwd_tile(pq_ref, pk_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
              delta_ref, **kw):
    """(q, k, do, p, ds) of one tile, f32."""
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    p = _bwd_probs(pq_ref[0], pk_ref[0], q, k,
                   lse_ref[0, 0].astype(jnp.float32), **kw)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, 0].astype(jnp.float32)) * kw["scale"]
    return q, k, do, p, ds


def _bwd_dq_kernel(live_ref, pq_ref, pk_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_acc, *, lay, **kw):
    """Grid (B, Hq, n_q, n_k): dq accumulates over the K/V tiles."""
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(live_ref[lay.live_index(b, iq, ik)] != 0)
    def _compute():
        _, k, _, _, ds = _bwd_tile(pq_ref, pk_ref, q_ref, k_ref, v_ref,
                                   do_ref, lse_ref, delta_ref, **kw)
        dq_acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(ik == lay.n_k - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(live_ref, pq_ref, pk_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    lay, **kw):
    """Grid (B, Hkv, n_k, G * n_q), t = g * n_q + iq: dk/dv accumulate over
    the query tiles of every query head in this K/V head's group."""
    b, ik, t = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n_t = lay.G * lay.n_q

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(live_ref[lay.live_index(b, t % lay.n_q, ik)] != 0)
    def _compute():
        q, _, do, p, ds = _bwd_tile(pq_ref, pk_ref, q_ref, k_ref, v_ref,
                                    do_ref, lse_ref, delta_ref, **kw)
        # dv += p^T do ; dk += ds^T q
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(t == n_t - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "prefix_len", "block_q",
                     "block_k", "interpret"),
)
def flash_attention_bwd(
    q, k, v, do, lse, delta, pos_q, pos_k, *, causal=True, window=None,
    scale=None, prefix_len=None, block_q=DEFAULT_BLOCK_Q,
    block_k=DEFAULT_BLOCK_K, interpret=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash backward for one (Q x K/V) block pair using the global lse.

    Returns (dq, dk, dv) in float32 (shapes of q, k, v). Semantics match
    ``ref.block_attention_bwd``; shared ``(S,)`` and per-row ``(B, S)``
    positions run the same kernels.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    lay = _Layout(q, k, pos_q, pos_k, block_q, block_k, interpret, causal,
                  window, prefix_len)
    B, Sq, Sk, Hq, Hkv, D, G = (lay.B, lay.Sq, lay.Sk, lay.Hq, lay.Hkv,
                                lay.D, lay.G)
    bq, bk, n_q = lay.block_q, lay.block_k, lay.n_q
    kw = dict(causal=causal, window=window, scale=scale,
              prefix_len=prefix_len, lay=lay)
    inputs = (lay.live, lay.pos_q_col, lay.pos_k_row, _heads_major(q),
              _heads_major(k), _heads_major(v), _heads_major(do),
              lse.astype(jnp.float32)[..., None],
              delta.astype(jnp.float32)[..., None])

    # ---- dq: grid (B, Hq, n_q, n_k), accumulate over ik ----
    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik, lv: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D),
                           lambda b, h, iq, ik, lv: (b, h // G, ik, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1),
                            lambda b, h, iq, ik, lv: (b, h, iq, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        name="flash_attention_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hq, n_q, lay.n_k),
            in_specs=[lay.pos_q_spec(lambda b, h, iq, ik, lv: iq),
                      lay.pos_k_spec(lambda b, h, iq, ik, lv: ik),
                      q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), jnp.float32),
        interpret=interpret,
        **_compiler_params(interpret, 3),
    )(*inputs)

    # ---- dk/dv: grid (B, Hkv, n_k, G * n_q); t = g * n_q + iq ----
    qg_spec = pl.BlockSpec(
        (1, 1, bq, D),
        lambda b, h, ik, t, lv: (b, h * G + t // n_q, t % n_q, 0))
    kvg_spec = pl.BlockSpec((1, 1, bk, D),
                            lambda b, h, ik, t, lv: (b, h, ik, 0))
    rowg_spec = pl.BlockSpec(
        (1, 1, bq, 1),
        lambda b, h, ik, t, lv: (b, h * G + t // n_q, t % n_q, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        name="flash_attention_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, lay.n_k, G * n_q),
            in_specs=[lay.pos_q_spec(lambda b, h, ik, t, lv: t % n_q),
                      lay.pos_k_spec(lambda b, h, ik, t, lv: ik),
                      qg_spec, kvg_spec, kvg_spec, qg_spec, rowg_spec,
                      rowg_spec],
            out_specs=[kvg_spec, kvg_spec],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Sk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, Sk, D), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 3),
    )(*inputs)

    return _heads_major(dq), _heads_major(dk), _heads_major(dv)
