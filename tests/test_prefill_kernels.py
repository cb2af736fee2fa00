"""Interpret-mode parity suite for the serving prefill kernels:

  * ``kernels/flash_attention.py`` with batched per-row ``(B, S)``
    positions (the engine's ragged buckets), checked against
    ``kernels.ref.block_attention``;
  * ``kernels/paged_prefill.py`` — suffix queries vs the page-table-indexed
    cached prefix, checked against the dense-gather reference path of
    ``kernels.dispatch.paged_prefill`` and, combined across shards, against
    the dense oracle.

Bit-level discipline: when the kernel's online softmax takes a *single*
accumulation step per row (one K tile / one live page) it executes the
exact instruction sequence of the reference (same max/exp/sum/divide order
in f32) and the comparison is ``np.array_equal`` — bit identical. Across
multiple tiles/pages the online rescaling reorders floating-point sums, so
those cases assert a tight ``allclose`` (2e-5, the repo-wide kernel
tolerance) plus *exact* dead-row semantics: rows with no visible key must
finalise to precisely (o=0, lse=NEG_INF), or the downstream lse-combines
drift.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import combine
from repro.core.combine import NEG_INF
from repro.kernels import dispatch, ref
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.kernels.flash_attention import choose_block, flash_attention_fwd


def _qkv(key, B, Sq, Sk, Hq, Hkv, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, Hq, D), dtype)
    k = jax.random.normal(kk, (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(kv, (B, Sk, Hkv, D), dtype)
    return q, k, v


def _ragged_positions(B, Sq, Sk, lens):
    """The engine's validity encoding: row b sees ``lens[b]`` keys at
    positions 0..lens[b]-1; the rest are pushed past every query."""
    lens = jnp.asarray(lens, jnp.int32)
    idx = jnp.arange(Sk, dtype=jnp.int32)
    pos_k = jnp.where(idx[None] < lens[:, None], idx[None], Sq + Sk)
    base = jnp.maximum(lens - 1, 0)          # queries start at the last key
    pos_q = base[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None]
    return pos_q.astype(jnp.int32), pos_k.astype(jnp.int32)


def test_choose_block():
    assert choose_block(128, 128) == 128
    assert choose_block(256, 128) == 128
    assert choose_block(24, 128) == 24       # non-pow2, single tile
    assert choose_block(192, 128) == 96      # non-pow2, two tiles
    assert choose_block(17, 8) == 1          # prime vs small pref
    assert choose_block(1, 128) == 1


# ---------------------------------------------------------------------------
# ragged-prefill kernel vs ref.block_attention
# ---------------------------------------------------------------------------

# (name, B, Sq, Sk, Hq, Hkv, D, window, lens) — lens None = full causal.
# single_acc: Sk tiles into one K block -> bit-identical to the reference.
RAGGED_CASES = [
    ("mha_single_tile", 2, 16, 16, 2, 2, 32, None, [16, 7]),
    ("gqa", 2, 16, 16, 4, 2, 32, None, [16, 5]),
    ("len_zero_and_full", 3, 8, 8, 2, 1, 16, None, [0, 8, 3]),
    ("sliding_window", 2, 16, 32, 2, 2, 32, 4, [32, 11]),
    ("non_pow2_rows", 2, 24, 24, 2, 2, 16, None, [24, 13]),
    ("multi_tile", 1, 16, 256, 2, 1, 32, None, None),
    ("multi_tile_non_pow2", 1, 16, 192, 2, 2, 16, None, [192, ]),
    ("multi_tile_ragged", 2, 8, 256, 2, 2, 16, None, [256, 130]),
]


@pytest.mark.parametrize(
    "name,B,Sq,Sk,Hq,Hkv,D,window,lens",
    RAGGED_CASES, ids=[c[0] for c in RAGGED_CASES])
def test_ragged_prefill_matches_ref(name, B, Sq, Sk, Hq, Hkv, D, window,
                                    lens):
    key = jax.random.PRNGKey(hash(name) % (2 ** 31))
    q, k, v = _qkv(key, B, Sq, Sk, Hq, Hkv, D)
    if lens is None:
        pos_q = jnp.broadcast_to(
            (Sk - Sq) + jnp.arange(Sq, dtype=jnp.int32)[None], (B, Sq))
        pos_k = jnp.broadcast_to(
            jnp.arange(Sk, dtype=jnp.int32)[None], (B, Sk))
        lens_arr = [Sk] * B
    else:
        lens_arr = list(lens) + [Sk] * (B - len(lens))
        pos_q, pos_k = _ragged_positions(B, Sq, Sk, lens_arr)
    o_pl, lse_pl = flash_attention_fwd(
        q, k, v, pos_q, pos_k, causal=True, window=window, interpret=True)
    o_ref, lse_ref = ref.block_attention(
        q, k, v, pos_q, pos_k, causal=True, window=window)
    o_pl, lse_pl = np.asarray(o_pl), np.asarray(lse_pl)
    o_ref, lse_ref = np.asarray(o_ref), np.asarray(lse_ref)

    single_acc = Sk // choose_block(Sk, 128) == 1
    if single_acc:
        # one accumulation step == the reference instruction sequence
        np.testing.assert_array_equal(o_pl, o_ref)
        np.testing.assert_array_equal(lse_pl, lse_ref)
    else:
        np.testing.assert_allclose(o_pl, o_ref, atol=2e-5, rtol=2e-5)
        live = lse_ref > NEG_INF / 2
        np.testing.assert_allclose(lse_pl[live], lse_ref[live],
                                   atol=2e-5, rtol=2e-5)
    # dead rows are exact regardless of tiling: (o=0, lse=NEG_INF)
    dead = lse_ref <= NEG_INF / 2
    assert np.array_equal(lse_pl <= NEG_INF / 2, dead)
    if dead.any():
        np.testing.assert_array_equal(
            o_pl[np.moveaxis(dead, -1, 1)], 0.0)


def test_ragged_prefill_len_zero_rows_are_dead():
    """A row whose every key is pushed past the queries (len = 0) must
    finalise to exactly (o=0, lse=NEG_INF) so combine_pair treats it as
    'no keys seen' rather than polluting the merge."""
    B, Sq, Sk, Hq, Hkv, D = 2, 8, 8, 2, 2, 16
    q, k, v = _qkv(jax.random.PRNGKey(0), B, Sq, Sk, Hq, Hkv, D)
    pos_q, pos_k = _ragged_positions(B, Sq, Sk, [0, 8])
    o, lse = flash_attention_fwd(q, k, v, pos_q, pos_k, causal=True,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(o)[0], 0.0)
    np.testing.assert_array_equal(np.asarray(lse)[0], np.float32(NEG_INF))
    assert np.all(np.asarray(lse)[1] > NEG_INF / 2)


def test_ragged_prefill_shared_positions_broadcast():
    """1-D (S,) positions broadcast to every row — same contract as ref."""
    B, S, H, D = 2, 16, 2, 32
    q, k, v = _qkv(jax.random.PRNGKey(1), B, S, S, H, H, D)
    pos = jnp.arange(S, dtype=jnp.int32)
    o1, lse1 = flash_attention_fwd(q, k, v, pos, pos, causal=True,
                                  interpret=True)
    o2, lse2 = flash_attention_fwd(
        q, k, v, jnp.broadcast_to(pos[None], (B, S)),
        jnp.broadcast_to(pos[None], (B, S)), causal=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(lse1), np.asarray(lse2))


def test_ragged_prefill_prefix_lm():
    """prefix_len (bidirectional prefix) flows through the tile mask."""
    B, S, H, D = 2, 16, 2, 16
    q, k, v = _qkv(jax.random.PRNGKey(2), B, S, S, H, H, D)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    o_pl, lse_pl = flash_attention_fwd(q, k, v, pos, pos, causal=True,
                                      prefix_len=6, interpret=True)
    o_ref, lse_ref = ref.block_attention(q, k, v, pos, pos, causal=True,
                                         prefix_len=6)
    np.testing.assert_array_equal(np.asarray(o_pl), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(lse_pl), np.asarray(lse_ref))


def test_dispatch_batched_fwd_routes_to_ragged_kernel():
    """dispatch.block_fwd(impl='pallas') with (B, S) positions returns the
    flash kernel's result (not the ref fallback) and counts nothing."""
    B, S, H, D = 2, 8, 2, 16
    q, k, v = _qkv(jax.random.PRNGKey(3), B, S, S, H, H, D)
    pos_q, pos_k = _ragged_positions(B, S, S, [8, 3])
    dispatch.reset_pallas_fallbacks()
    o_d, lse_d = dispatch.block_fwd(q, k, v, pos_q, pos_k, causal=True,
                                    impl="pallas")
    assert dispatch.pallas_fallbacks() == {}
    o_k, lse_k = flash_attention_fwd(q, k, v, pos_q, pos_k, causal=True)
    np.testing.assert_array_equal(np.asarray(o_d), np.asarray(o_k))
    np.testing.assert_array_equal(np.asarray(lse_d), np.asarray(lse_k))


# ---------------------------------------------------------------------------
# paged-suffix prefill kernel vs the dense-gather reference
# ---------------------------------------------------------------------------

def _paged_fixture(key, *, B, Sq, sp, page_size, pages_loc, cached_lens,
                   Hq, Hkv, D, rank):
    """Round-robin scatter of a dense prefix into one shard's pool.

    Returns (q, pool_k, pool_v, table, cached_len) plus the dense per-shard
    gather ingredients so the reference path sees the same bytes. Rows may
    have fewer pages than the table width (unallocated = -1) and partial
    last pages (cached_len not page-aligned).
    """
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, Hq, D), jnp.float32)
    cl_max = max(cached_lens)
    # global dense prefix per row
    k_all = jax.random.normal(kk, (B, cl_max, Hkv, D), jnp.float32)
    v_all = jax.random.normal(kv, (B, cl_max, Hkv, D), jnp.float32)
    W = max(1, -(-(-(-cl_max // page_size)) // sp))
    pool_k = np.zeros((pages_loc, page_size, Hkv, D), np.float32)
    pool_v = np.zeros((pages_loc, page_size, Hkv, D), np.float32)
    table = np.full((B, W), -1, np.int32)
    next_page = 0
    for b, cl in enumerate(cached_lens):
        n_blocks = -(-cl // page_size)
        for blk in range(n_blocks):
            if blk % sp != rank:
                continue
            w = blk // sp
            page = next_page
            next_page += 1
            assert page < pages_loc
            table[b, w] = page
            lo = blk * page_size
            hi = min(lo + page_size, cl)
            pool_k[page, :hi - lo] = np.asarray(k_all[b, lo:hi])
            pool_v[page, :hi - lo] = np.asarray(v_all[b, lo:hi])
    cached_len = np.asarray(cached_lens, np.int32)
    return (q, jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.asarray(cached_len), k_all, v_all)


# (name, B, Sq, sp, page_size, cached_lens, window)
PAGED_CASES = [
    ("single_shard", 2, 8, 1, 8, [24, 16], None),
    ("partial_pages", 2, 8, 1, 8, [13, 21], None),
    ("empty_prefix", 2, 8, 1, 8, [0, 16], None),
    ("multi_shard_rank", 2, 8, 4, 4, [29, 7], None),
    ("windowed", 1, 8, 1, 8, [32], 6),
    ("non_pow2_suffix", 1, 12, 2, 4, [17], None),
]


@pytest.mark.parametrize(
    "name,B,Sq,sp,page_size,cached_lens,window",
    PAGED_CASES, ids=[c[0] for c in PAGED_CASES])
def test_paged_prefill_matches_dense_gather(name, B, Sq, sp, page_size,
                                            cached_lens, window):
    """Kernel vs dispatch's dense-gather ref path, per shard rank — partial
    pages, unallocated entries and empty prefixes included."""
    Hq, Hkv, D = 4, 2, 16
    for rank in range(sp):
        q, pool_k, pool_v, table, cached_len, _, _ = _paged_fixture(
            jax.random.PRNGKey(hash(name) % (2 ** 31)), B=B, Sq=Sq, sp=sp,
            page_size=page_size, pages_loc=32, cached_lens=cached_lens,
            Hq=Hq, Hkv=Hkv, D=D, rank=rank)
        o_pl, lse_pl = paged_prefill_attention(
            q, pool_k, pool_v, table, cached_len, jnp.asarray(rank),
            sp=sp, page_size=page_size, window=window, interpret=True)
        o_ref, lse_ref = dispatch.paged_prefill(
            q, pool_k, pool_v, table, cached_len, jnp.asarray(rank),
            sp=sp, page_size=page_size, window=window, impl="ref")
        o_pl, lse_pl = np.asarray(o_pl), np.asarray(lse_pl)
        o_ref, lse_ref = np.asarray(o_ref), np.asarray(lse_ref)

        live_pages = max(
            sum(1 for blk in range(-(-cl // page_size)) if blk % sp == rank)
            for cl in cached_lens)
        if live_pages <= 1:
            # at most one accumulation step per row: bit-identical
            np.testing.assert_array_equal(o_pl, o_ref, err_msg=f"rank {rank}")
            np.testing.assert_array_equal(lse_pl, lse_ref)
        else:
            np.testing.assert_allclose(o_pl, o_ref, atol=2e-5, rtol=2e-5,
                                       err_msg=f"rank {rank}")
            live = lse_ref > NEG_INF / 2
            np.testing.assert_allclose(lse_pl[live], lse_ref[live],
                                       atol=2e-5, rtol=2e-5)
        # dead rows exact: every row with no key on this shard
        dead = lse_ref <= NEG_INF / 2
        assert np.array_equal(lse_pl <= NEG_INF / 2, dead), f"rank {rank}"
        if dead.any():
            np.testing.assert_array_equal(o_pl[np.moveaxis(dead, -1, 1)], 0.0)


def test_paged_prefill_empty_prefix_all_dead():
    """cached_len = 0: no page is live, every row must be exactly
    (o=0, lse=NEG_INF) — the combine then keeps only the dense suffix
    partial, which is what makes chunk 0 == monolithic prefill."""
    q, pool_k, pool_v, table, cached_len, _, _ = _paged_fixture(
        jax.random.PRNGKey(9), B=2, Sq=8, sp=1, page_size=8, pages_loc=8,
        cached_lens=[0, 0], Hq=2, Hkv=2, D=16, rank=0)
    o, lse = paged_prefill_attention(
        q, pool_k, pool_v, table, cached_len, jnp.asarray(0), sp=1,
        page_size=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(o), 0.0)
    np.testing.assert_array_equal(np.asarray(lse), np.float32(NEG_INF))


def test_paged_prefill_combined_across_all_shards():
    """Prefix spanning every shard: the per-rank kernel partials, merged
    with combine_pair, equal full dense attention of the suffix queries
    over the whole prefix — layout, masking and lse all exact end-to-end."""
    B, Sq, sp, ps = 2, 8, 4, 4
    Hq, Hkv, D = 4, 2, 16
    cached_lens = [61, 35]                   # partial pages on most shards
    parts = []
    for rank in range(sp):
        q, pool_k, pool_v, table, cached_len, k_all, v_all = _paged_fixture(
            jax.random.PRNGKey(7), B=B, Sq=Sq, sp=sp, page_size=ps,
            pages_loc=32, cached_lens=cached_lens, Hq=Hq, Hkv=Hkv, D=D,
            rank=rank)
        o, lse = paged_prefill_attention(
            q, pool_k, pool_v, table, cached_len, jnp.asarray(rank),
            sp=sp, page_size=ps, interpret=True)
        parts.append((o, lse))
    o, lse = parts[0]
    for o2, lse2 in parts[1:]:
        o, lse = combine.combine_pair(o, lse, o2, lse2)

    # dense oracle: suffix queries (pos cached_len + i) over keys < cached_len
    cl_max = max(cached_lens)
    pos_k = jnp.broadcast_to(
        jnp.arange(cl_max, dtype=jnp.int32)[None], (B, cl_max))
    cl = jnp.asarray(cached_lens, jnp.int32)
    # invalid (>= cached_len) keys pushed past every query
    pos_k = jnp.where(pos_k < cl[:, None], pos_k, (cl + Sq)[:, None])
    pos_q = cl[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None]
    o_ref, lse_ref = ref.block_attention(q, k_all, v_all, pos_q, pos_k,
                                         causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               atol=2e-5, rtol=2e-5)
