"""Pallas flash-attention + paged-decode kernels vs the pure-jnp oracle
(interpret mode).

Sweeps shapes/dtypes per the kernel-testing contract: every kernel is
asserted allclose against ref.py. Also grep-enforces the dispatch-layer
contract: nothing outside kernels/ imports ref/ops/flash_attention/
paged_decode directly — all attention call sites go through
``kernels.dispatch``.

Runnable standalone (the CI ``kernels-interpret`` step):
    PYTHONPATH=src python -m pytest -x -q tests/test_kernels.py
"""

import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels import ops
from repro.kernels import ref


def _data(key, B, Sq, Sk, Hq, Hkv, D, dtype):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), jnp.float32).astype(dtype)
    do = jax.random.normal(ks[3], (B, Sq, Hq, D), jnp.float32).astype(dtype)
    return q, k, v, do


CASES = [
    # B, Sq, Sk, Hq, Hkv, D, causal, window, dtype, block
    (1, 128, 128, 2, 2, 64, True, None, jnp.float32, 64),
    (2, 128, 256, 4, 2, 64, True, None, jnp.float32, 128),
    (1, 128, 128, 4, 1, 128, False, None, jnp.float32, 64),
    (1, 256, 128, 2, 2, 64, True, 64, jnp.float32, 64),
    (1, 128, 128, 2, 2, 64, False, 32, jnp.float32, 64),
    (1, 128, 128, 2, 2, 64, True, None, jnp.bfloat16, 64),
    (1, 64, 64, 3, 1, 32, True, None, jnp.float32, 32),  # odd head count
]


@pytest.mark.parametrize("B,Sq,Sk,Hq,Hkv,D,causal,window,dtype,blk", CASES)
def test_fwd_matches_ref(B, Sq, Sk, Hq, Hkv, D, causal, window, dtype, blk):
    q, k, v, _ = _data(jax.random.PRNGKey(0), B, Sq, Sk, Hq, Hkv, D, dtype)
    pos_q = jnp.arange(Sq, dtype=jnp.int32)
    # offset k positions so causal masks are non-trivial across blocks
    pos_k = jnp.arange(Sk, dtype=jnp.int32) + (Sq - Sk) // 2
    o_ker, lse_ker = ops.flash_attention_fwd(
        q, k, v, pos_q, pos_k, causal=causal, window=window,
        block_q=blk, block_k=blk)
    o_ref, lse_ref = ref.block_attention(
        q, k, v, pos_q, pos_k, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_ker, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol, rtol=tol)
    # compare lse only on live rows
    live = np.asarray(lse_ref) > -1e29
    np.testing.assert_allclose(np.asarray(lse_ker)[live],
                               np.asarray(lse_ref)[live], atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("B,Sq,Sk,Hq,Hkv,D,causal,window,dtype,blk", CASES[:5])
def test_bwd_matches_ref(B, Sq, Sk, Hq, Hkv, D, causal, window, dtype, blk):
    q, k, v, do = _data(jax.random.PRNGKey(1), B, Sq, Sk, Hq, Hkv, D, dtype)
    pos_q = jnp.arange(Sq, dtype=jnp.int32)
    pos_k = jnp.arange(Sk, dtype=jnp.int32) + (Sq - Sk) // 2
    o_ref, lse = ref.block_attention(q, k, v, pos_q, pos_k, causal=causal,
                                     window=window)
    delta = jnp.einsum("bshd,bshd->bhs", do.astype(jnp.float32),
                       o_ref.astype(jnp.float32))
    got = ops.flash_attention_bwd(q, k, v, do, lse, delta, pos_q, pos_k,
                                  causal=causal, window=window,
                                  block_q=blk, block_k=blk)
    want = ref.block_attention_bwd(q, k, v, do, lse, delta, pos_q, pos_k,
                                   causal=causal, window=window)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=3e-4, rtol=3e-4,
                                   err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# paged-decode kernel: page-table-indexed online softmax vs the dense oracle
# ---------------------------------------------------------------------------

PAGED_CASES = [
    # B, Hq, Hkv, D, page_size, W, sp, rank, window, raggedness
    (2, 4, 2, 16, 4, 3, 2, 1, None, "ragged"),     # GQA, mid-shard
    (3, 4, 1, 8, 4, 4, 4, 3, None, "ragged"),      # MQA, last shard
    (2, 2, 2, 32, 8, 2, 1, 0, None, "partial"),    # MHA, partially-filled page
    (2, 4, 2, 16, 4, 4, 2, 0, 6, "ragged"),        # sliding window
    (1, 4, 2, 16, 4, 3, 2, 1, 5, "partial"),       # window + partial page
    (2, 4, 2, 16, 4, 3, 2, 0, None, "empty"),      # a row with nothing valid
]


#: how the kernels get the pool: one layer's (pages, ps, Hkv, D) pool
#: (None), or a stack of 3 layers' pools read at layer 0 or 2
STACKED = [None, 0, 2]


def _stack(pool, layer, seed):
    """``pool`` as layer ``layer`` of a 3-layer stack whose other layers
    hold other values, so a read of the wrong layer shows."""
    if layer is None:
        return pool
    rng = np.random.default_rng(seed)
    layers = [jnp.asarray(rng.normal(size=pool.shape).astype(np.float32))
              + 10.0 * (i + 1) for i in range(3)]
    layers[layer] = pool
    return jnp.stack(layers)


def _paged_fixture(B, Hkv, D, ps, W, sp, raggedness, seed=0):
    """Random pools + a table with some -1 holes + per-row cache lengths."""
    rng = np.random.default_rng(seed)
    pages_loc = 8
    pool_k = jnp.asarray(rng.normal(size=(pages_loc, ps, Hkv, D))
                         .astype(np.float32))
    pool_v = jnp.asarray(rng.normal(size=(pages_loc, ps, Hkv, D))
                         .astype(np.float32))
    tbl = rng.integers(0, pages_loc, size=(B, W)).astype(np.int32)
    tbl[0, -1] = -1                               # unallocated tail page
    max_pos = W * sp * ps
    if raggedness == "partial":
        # last valid position lands mid-page on every row
        cl = (rng.integers(0, W * sp, size=(B,)) * ps
              + rng.integers(1, ps - 1, size=(B,))).astype(np.int32)
    else:
        cl = rng.integers(0, max_pos, size=(B,)).astype(np.int32)
    if raggedness == "empty":
        tbl[-1] = -1                              # no pages at all
        cl[-1] = 0
    return pool_k, pool_v, jnp.asarray(tbl), jnp.asarray(cl)


@pytest.mark.parametrize("layer", STACKED)
@pytest.mark.parametrize("B,Hq,Hkv,D,ps,W,sp,rank,window,ragged", PAGED_CASES)
def test_paged_decode_matches_ref(B, Hq, Hkv, D, ps, W, sp, rank, window,
                                  ragged, layer):
    """Interpret-mode parity: the Pallas paged kernel's partial (o, lse)
    equals ref.block_attention over the dense gather of the same pages
    (GQA, sliding window, ragged cache_len, partially-filled pages), from
    one layer's pool or from a layer of a stacked pool."""
    pool_k, pool_v, tbl, cl = _paged_fixture(B, Hkv, D, ps, W, sp, ragged)
    q = jnp.asarray(np.random.default_rng(1)
                    .normal(size=(B, 1, Hq, D)).astype(np.float32))
    rank_a = jnp.int32(rank)
    layer_args = () if layer is None else (jnp.int32(layer),)

    o_p, lse_p = dispatch.paged_decode(
        q, _stack(pool_k, layer, 3), _stack(pool_v, layer, 4), tbl, cl,
        rank_a, *layer_args, sp=sp, page_size=ps, window=window,
        impl="pallas")

    # dense oracle: gather this shard's pages by hand, positions encode
    # validity (invalid slots pushed past the query position)
    pages_loc = pool_k.shape[0]
    safe = jnp.clip(tbl, 0, pages_loc - 1)
    k_r = pool_k[safe].reshape(B, W * ps, Hkv, D)
    v_r = pool_v[safe].reshape(B, W * ps, Hkv, D)
    pos = ((np.arange(W) * sp + rank) * ps)[:, None] + np.arange(ps)[None]
    pos = jnp.asarray(pos.reshape(-1).astype(np.int32))
    valid = jnp.repeat(tbl >= 0, ps, axis=1) & (pos[None] <= cl[:, None])
    pos_k = jnp.where(valid, pos[None], (cl + 1)[:, None])
    o_r, lse_r = ref.block_attention(q, k_r, v_r, cl[:, None], pos_k,
                                     causal=True, window=window)

    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r),
                               atol=2e-5, rtol=2e-5)
    live = np.asarray(lse_r) > -1e29
    np.testing.assert_allclose(np.asarray(lse_p)[live],
                               np.asarray(lse_r)[live], atol=1e-4, rtol=1e-4)
    # dead rows (no visible key on this shard) must report lse = -inf so
    # the cross-shard combine drops them
    assert (np.asarray(lse_p)[~live] < -1e29).all()


@pytest.mark.parametrize("layer", STACKED)
def test_paged_decode_ref_impl_matches_oracle(layer):
    """dispatch.paged_decode(impl='ref') — the gather fallback — agrees
    with the pallas kernel bit-for-tolerance on the same fixture, and both
    read the layer of a stacked pool that they are given."""
    B, Hq, Hkv, D, ps, W, sp, rank = 2, 4, 2, 16, 4, 3, 2, 1
    pool_k, pool_v, tbl, cl = _paged_fixture(B, Hkv, D, ps, W, sp, "ragged")
    q = jnp.asarray(np.random.default_rng(2)
                    .normal(size=(B, 1, Hq, D)).astype(np.float32))
    one_layer = (q, pool_k, pool_v, tbl, cl, jnp.int32(rank))
    args = one_layer if layer is None else (
        q, _stack(pool_k, layer, 3), _stack(pool_v, layer, 4), tbl, cl,
        jnp.int32(rank), jnp.int32(layer))
    o_r, lse_r = dispatch.paged_decode(*args, sp=sp, page_size=ps,
                                       impl="ref")
    o_p, lse_p = dispatch.paged_decode(*args, sp=sp, page_size=ps,
                                       impl="pallas")
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r),
                               atol=2e-5, rtol=2e-5)
    live = np.asarray(lse_r) > -1e29
    np.testing.assert_allclose(np.asarray(lse_p)[live],
                               np.asarray(lse_r)[live], atol=1e-4, rtol=1e-4)
    # the stacked read equals the read of that layer's pool alone
    o_1, lse_1 = dispatch.paged_decode(*one_layer, sp=sp, page_size=ps,
                                       impl="ref")
    np.testing.assert_array_equal(np.asarray(o_r), np.asarray(o_1))
    np.testing.assert_array_equal(np.asarray(lse_r), np.asarray(lse_1))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_paged_decode_reads_lane_padded_pool(impl):
    """The engine's pools carry the head dim zero-padded to whole lane
    tiles (``paged_cache.pool_head_dim``); dispatch reads such a pool as
    the unpadded one, and returns q's head dim."""
    B, Hq, Hkv, D, ps, W, sp, rank = 2, 4, 2, 16, 4, 3, 2, 1
    pool_k, pool_v, tbl, cl = _paged_fixture(B, Hkv, D, ps, W, sp, "ragged")
    q = jnp.asarray(np.random.default_rng(2)
                    .normal(size=(B, 1, Hq, D)).astype(np.float32))
    pad = [(0, 0)] * 3 + [(0, 128 - D)]
    kw = dict(sp=sp, page_size=ps, window=6, impl=impl)
    o, lse = dispatch.paged_decode(q, jnp.pad(pool_k, pad),
                                   jnp.pad(pool_v, pad), tbl, cl,
                                   jnp.int32(rank), **kw)
    o_1, lse_1 = dispatch.paged_decode(q, pool_k, pool_v, tbl, cl,
                                       jnp.int32(rank), **kw)
    assert o.shape == o_1.shape == q.shape
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_1),
                               atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_1),
                               atol=2e-6, rtol=2e-6)


# ---------------------------------------------------------------------------
# dispatch layer contract
# ---------------------------------------------------------------------------

def test_resolve_impl():
    assert dispatch.resolve_impl("ref") == "ref"
    assert dispatch.resolve_impl("pallas") == "pallas"
    assert dispatch.resolve_impl(None) == (
        "pallas" if jax.default_backend() == "tpu" else "ref")
    with pytest.raises(ValueError):
        dispatch.resolve_impl("cuda")


def test_pallas_batched_positions_no_fallback():
    """impl='pallas' with batched (B, S) positions (per-sequence cache
    lengths) runs the flash kernels — forward AND backward. The fallback
    counter stays empty and both directions match the reference."""
    key = jax.random.PRNGKey(3)
    q, k, v, do = _data(key, 2, 8, 8, 2, 2, 16, jnp.float32)
    pos_shared = jnp.arange(8, dtype=jnp.int32)
    pos_batched = jnp.stack([pos_shared, pos_shared + 1])     # (B, S)

    dispatch.reset_pallas_fallbacks()
    o_pl, lse_pl = dispatch.block_fwd(q, k, v, pos_batched, pos_batched,
                                      causal=True, impl="pallas")
    assert dispatch.pallas_fallbacks() == {}, \
        "batched forward positions must run the ragged kernel, not fall back"
    o_ref, lse_ref = ref.block_attention(q, k, v, pos_batched, pos_batched,
                                         causal=True)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_pl), np.asarray(lse_ref),
                               atol=2e-5, rtol=2e-5)

    # shared (S,) positions keep running the training flash kernel...
    dispatch.block_fwd(q, k, v, pos_shared, pos_shared, causal=True,
                       impl="pallas")
    assert dispatch.pallas_fallbacks() == {}
    # ...and impl='ref' is not a fallback, it is the requested path
    dispatch.block_fwd(q, k, v, pos_batched, pos_batched, causal=True,
                       impl="ref")
    assert dispatch.pallas_fallbacks() == {}
    # the backward pass now has ragged kernels too: no fallback, and the
    # grads match the reference
    lse = lse_pl
    delta = jnp.sum(o_pl * do, axis=-1).swapaxes(1, 2).astype(jnp.float32)
    got = dispatch.block_bwd(q, k, v, do, lse, delta, pos_batched,
                             pos_batched, causal=True, impl="pallas")
    assert dispatch.pallas_fallbacks() == {}, \
        "batched backward positions must run the ragged kernels, not fall back"
    want = ref.block_attention_bwd(q, k, v, do, lse, delta, pos_batched,
                                   pos_batched, causal=True)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=3e-4, rtol=3e-4, err_msg=f"d{name}")
    dispatch.reset_pallas_fallbacks()


RAGGED_BWD_CASES = [
    # B, Sq, Sk, Hq, Hkv, D, causal, window
    (2, 64, 64, 4, 2, 32, True, None),     # GQA
    (2, 64, 128, 4, 1, 32, True, None),    # MQA, rectangular
    (1, 96, 96, 2, 2, 32, True, 24),       # window + non-pow2 seq
    (2, 64, 64, 2, 2, 32, False, None),    # full attention
]


@pytest.mark.parametrize("B,Sq,Sk,Hq,Hkv,D,causal,window", RAGGED_BWD_CASES)
def test_bwd_ragged_matches_ref(B, Sq, Sk, Hq, Hkv, D, causal, window):
    """The backward kernels with per-batch (B, S) positions vs the
    reference backward, including per-row offsets that differ across the
    batch."""
    q, k, v, do = _data(jax.random.PRNGKey(7), B, Sq, Sk, Hq, Hkv, D,
                        jnp.float32)
    base_q = jnp.arange(Sq, dtype=jnp.int32)
    base_k = jnp.arange(Sk, dtype=jnp.int32) + (Sq - Sk) // 2
    pos_q = jnp.stack([base_q + 3 * b for b in range(B)])
    pos_k = jnp.stack([base_k + 3 * b for b in range(B)])
    o_ref, lse = ref.block_attention(q, k, v, pos_q, pos_k, causal=causal,
                                     window=window)
    delta = jnp.einsum("bshd,bshd->bhs", do.astype(jnp.float32),
                       o_ref.astype(jnp.float32))
    got = ops.flash_attention_bwd(q, k, v, do, lse, delta, pos_q, pos_k,
                                  causal=causal, window=window)
    want = ref.block_attention_bwd(q, k, v, do, lse, delta, pos_q, pos_k,
                                   causal=causal, window=window)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=3e-4, rtol=3e-4, err_msg=f"d{name}")


MERGE_CASES = [
    # B, S, Hq, Hkv, D, causal, window, seed_dead
    (2, 128, 4, 2, 64, True, None, False),   # GQA
    (1, 128, 4, 1, 64, True, None, False),   # MQA
    (1, 128, 2, 2, 64, True, 32, False),     # window: dead rows in block
    (2, 128, 2, 2, 64, True, None, True),    # dead rows in the RUNNING acc
    (1, 128, 2, 2, 64, False, None, False),  # full attention
]


@pytest.mark.parametrize("B,S,Hq,Hkv,D,causal,window,seed_dead", MERGE_CASES)
def test_fwd_merge_fused_matches_two_step(B, S, Hq, Hkv, D, causal, window,
                                          seed_dead):
    """The fused merge epilogue (flash kernel consuming a running
    (o_acc, lse_acc)) matches the two-step form it replaces — block_fwd
    followed by combine_pair — to within 2 ulp on o (XLA may fuse the
    merge's multiply-adds differently across the two compilations) and
    bit-exactly on lse. Covers GQA, windowed masks that kill whole rows
    inside the block, and dead rows (lse=-inf) arriving in the running
    accumulator."""
    from repro.core.combine import NEG_INF, combine_pair

    q, k, v, _ = _data(jax.random.PRNGKey(11), B, S, S, Hq, Hkv, D,
                       jnp.float32)
    pos_q = jnp.arange(S, dtype=jnp.int32)
    pos_k = jnp.arange(S, dtype=jnp.int32) + 16
    # a running accumulator from an earlier ring step over different keys
    k2, v2, _, _ = _data(jax.random.PRNGKey(12), B, S, S, Hkv, Hkv, D,
                         jnp.float32)
    o_acc, lse_acc = ref.block_attention(q, k2, v2, pos_q,
                                         jnp.arange(S, dtype=jnp.int32),
                                         causal=causal, window=window)
    if seed_dead:
        # first half of the rows have seen nothing yet (lse = -inf)
        dead = (jnp.arange(S) < S // 2)[None, None, :]
        lse_acc = jnp.where(dead, NEG_INF, lse_acc)
        o_acc = jnp.where(dead.swapaxes(1, 2)[..., None], 0.0, o_acc)

    fused = dispatch.block_fwd_merge(q, k, v, o_acc, lse_acc, pos_q, pos_k,
                                     causal=causal, window=window,
                                     impl="pallas")
    o_blk, lse_blk = dispatch.block_fwd(q, k, v, pos_q, pos_k,
                                        causal=causal, window=window,
                                        impl="pallas")
    two_step = combine_pair(o_acc, lse_acc, o_blk, lse_blk)
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(two_step[0]),
                               atol=1e-7, rtol=5e-7,
                               err_msg="fused merge o vs combine_pair")
    assert np.array_equal(np.asarray(fused[1]), np.asarray(two_step[1])), (
        "fused merge lse not bit-identical to combine_pair (max diff "
        f"{np.abs(np.asarray(fused[1]) - np.asarray(two_step[1])).max()})")
    # the ref-impl fallback of block_fwd_merge is the same two-step form
    ref_merge = dispatch.block_fwd_merge(q, k, v, o_acc, lse_acc, pos_q,
                                         pos_k, causal=causal, window=window,
                                         impl="ref")
    np.testing.assert_allclose(np.asarray(ref_merge[0]),
                               np.asarray(two_step[0]), atol=2e-5, rtol=2e-5)


def test_no_direct_kernel_imports():
    """Grep-enforced: no module outside kernels/ imports kernels.ref /
    kernels.ops / kernels.flash_attention / kernels.paged_decode directly —
    every attention call site in core/, serve/, engine/, models/ goes
    through kernels.dispatch. (testing/dist_checks.py is exempt: it uses
    ref as the *oracle* the distributed paths are checked against.)"""
    src = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"
    pat = re.compile(
        r"repro\.kernels\s+import\s+"
        r"(ref|ops|flash_attention|paged_decode|paged_prefill)"
        r"|repro\.kernels\."
        r"(ref|ops|flash_attention|paged_decode|paged_prefill)")
    offenders = []
    for path in sorted(src.rglob("*.py")):
        rel = path.relative_to(src)
        if rel.parts[0] in ("kernels", "testing"):
            continue
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(f"{rel}:{i}: {line.strip()}")
    assert not offenders, (
        "direct kernel imports outside kernels/ (use kernels.dispatch):\n"
        + "\n".join(offenders))


def test_flash_attention_grad_end_to_end():
    """custom_vjp wrapper: jax.grad through the kernel == grad through ref."""
    B, S, Hq, Hkv, D = 1, 128, 2, 1, 64
    q, k, v, do = _data(jax.random.PRNGKey(2), B, S, S, Hq, Hkv, D, jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)

    def loss_ker(q, k, v):
        o = ops.flash_attention(q, k, v, pos, pos, True, None, None)
        return (o * do).sum()

    def loss_ref(q, k, v):
        o, _ = ref.block_attention(q, k, v, pos, pos, causal=True)
        return (o.astype(q.dtype) * do).sum()

    g_ker = jax.grad(loss_ker, (0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ker, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-4, rtol=3e-4, err_msg=f"d{name}")
