"""The single attention-kernel dispatch layer.

Every attention call site in ``core/``, ``serve/``, ``engine/`` and
``models/`` goes through this module (grep-enforced by
``tests/test_kernels.py::test_no_direct_kernel_imports``) instead of
importing ``kernels.ref`` / ``kernels.ops`` / ``kernels.flash_attention``
directly. One ``impl`` knob — ``'ref'`` (pure jnp, XLA-fused; CPU default)
or ``'pallas'`` (TPU kernels, interpret-mode on CPU) — selects the backend
for the entry points:

    block_fwd / block_bwd  — one (Q block x K/V block) pair of the ring
                             step (online-softmax partials + flash backward)
    prefill                — full masked attention of batched positions
                             (o only; the serve/encdec dense call sites)
    decode                 — per-shard partial (o, lse) of M=1 queries vs a
                             dense cache slice
    paged_decode           — per-shard partial (o, lse) straight off a
                             page-table-indexed pool (no dense gather);
                             'pallas' runs kernels/paged_decode.py, 'ref'
                             gathers the pages and reuses the jnp oracle
    paged_prefill          — suffix-query block vs the cached-prefix pages
                             (the prefix-cached / chunked prefill partial);
                             'pallas' runs kernels/paged_prefill.py, 'ref'
                             gathers the pages densely

``resolve_impl(None)`` picks the backend default: ``'pallas'`` when
``jax.default_backend()`` is TPU, ``'ref'`` otherwise — the rule
``plan.make_plan`` applies to unset ``block_impl`` / ``kernel_impl`` knobs.

The Pallas block kernels take shared ``(S,)`` position vectors (training)
and *batched* ``(B, S)`` positions (per-sequence cache lengths) alike, so
neither direction falls back to the reference. The fallback *accounting*
stays: any future pallas->ref fallback must go through
``_note_fallback`` so it is counted per entry point
(``pallas_fallbacks()``) and logged once, assertable in tests (the counter
ticks at *trace* time — once per jit compilation, not per step).

``block_fwd_merge`` is the ring-scan entry: it folds one block's partials
into the running ``(o_acc, lse_acc)`` accumulator. On 'pallas' the combine
is fused into the flash kernel's epilogue (no separate full-array pass
over the f32 accumulator); on 'ref' it stays the explicit two-step
``block_attention`` + ``combine_pair`` form — the oracle the fused kernel
is validated against.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.core.combine import combine_pair as _combine_pair
from repro.obs import registry as _obs

IMPLS = ("ref", "pallas")

FALLBACK_METRIC = "dispatch_pallas_fallback_total"

_log = logging.getLogger(__name__)
_warned = set()


def _fallback_counter() -> _obs.Counter:
    return _obs.global_registry().counter(
        FALLBACK_METRIC,
        "Trace-time pallas->ref fallbacks by entry point, with provenance "
        "(reason, q shape) and the obs scope active at trace time")


def _note_fallback(entry: str, *, reason: str = "batched_positions",
                   shape=None) -> None:
    """Record a pallas->ref fallback as a labeled counter.

    Ticks at *trace* time — once per jit compilation, not per step. The
    ``scope`` label carries the active ``obs.scope(...)`` (engines trace
    under their own scope), so per-instance attribution is a label filter
    instead of the process-global snapshot-delta arithmetic this replaced.
    """
    if entry not in _warned:
        _warned.add(entry)
        _log.warning(
            "kernels.dispatch.%s: impl='pallas' requested but falling back "
            "to the reference implementation (reason=%s; see "
            "docs/SERVING.md, 'known gaps'). Logged once; occurrences are "
            "counted in the %s metric and pallas_fallbacks().",
            entry, reason, FALLBACK_METRIC)
    _fallback_counter().inc(
        entry=entry, reason=reason,
        shape="x".join(str(d) for d in shape) if shape is not None else "",
        scope=_obs.current_scope())


def pallas_fallbacks(scope: Optional[str] = None) -> Dict[str, int]:
    """Trace-time pallas->ref fallback counts, keyed by entry point.

    ``scope`` filters to counts recorded under one ``obs.scope(...)``
    (e.g. a single engine instance); None sums every scope.
    """
    counter = _obs.global_registry().get(FALLBACK_METRIC)
    if counter is None:
        return {}
    out: Dict[str, int] = {}
    labels = {"scope": scope} if scope is not None else {}
    for key, v in counter.series(**labels).items():
        entry = dict(key).get("entry", "?")
        out[entry] = out.get(entry, 0) + int(v)
    return {k: v for k, v in out.items() if v}


def reset_pallas_fallbacks(scope: Optional[str] = None) -> None:
    counter = _obs.global_registry().get(FALLBACK_METRIC)
    if counter is not None:
        counter.reset(**({"scope": scope} if scope is not None else {}))


def resolve_impl(impl: Optional[str] = None) -> str:
    """'ref' | 'pallas', with None/'auto' resolved from the backend."""
    if impl in (None, "", "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl not in IMPLS:
        raise ValueError(f"attention impl must be one of {IMPLS} (or None "
                         f"for the backend default), got {impl!r}")
    return impl


# ---------------------------------------------------------------------------
# ring-step block compute (training hot spot)
# ---------------------------------------------------------------------------

def block_fwd(q, k, v, pos_q, pos_k, *, causal=True, window=None, scale=None,
              prefix_len=None, impl="ref") -> Tuple[jax.Array, jax.Array]:
    """Masked (Q block x K/V block) attention -> (o, lse) partials."""
    if impl == "pallas":
        from repro.kernels import ops as _ops

        return _ops.flash_attention_fwd(
            q, k, v, pos_q, pos_k, causal=causal, window=window,
            scale=scale, prefix_len=prefix_len)
    return _ref.block_attention(
        q, k, v, pos_q, pos_k, causal=causal, window=window, scale=scale,
        prefix_len=prefix_len)


def block_fwd_merge(q, k, v, o_acc, lse_acc, pos_q, pos_k, *, causal=True,
                    window=None, scale=None, prefix_len=None,
                    impl="ref") -> Tuple[jax.Array, jax.Array]:
    """One ring step: block attention merged into the running accumulator.

    Semantically ``combine_pair(o_acc, lse_acc, *block_fwd(...))``. The
    'pallas' path fuses the combine into the flash kernel epilogue, saving
    the extra HBM pass over the f32 accumulator; 'ref' keeps the explicit
    two-step form (the oracle).
    """
    if impl == "pallas":
        from repro.kernels import ops as _ops

        return _ops.flash_attention_fwd(
            q, k, v, pos_q, pos_k, o_acc=o_acc, lse_acc=lse_acc,
            causal=causal, window=window, scale=scale,
            prefix_len=prefix_len)
    o_s, lse_s = _ref.block_attention(q, k, v, pos_q, pos_k, causal=causal,
                                      window=window, scale=scale,
                                      prefix_len=prefix_len)
    return _combine_pair(o_acc, lse_acc, o_s, lse_s)


def block_bwd(q, k, v, do, lse, delta, pos_q, pos_k, *, causal=True,
              window=None, scale=None, prefix_len=None, impl="ref"):
    """Flash backward for one block pair -> (dq, dk, dv) in float32."""
    if impl == "pallas":
        from repro.kernels import ops as _ops

        return _ops.flash_attention_bwd(
            q, k, v, do, lse, delta, pos_q, pos_k, causal=causal,
            window=window, scale=scale, prefix_len=prefix_len)
    return _ref.block_attention_bwd(
        q, k, v, do, lse, delta, pos_q, pos_k, causal=causal, window=window,
        scale=scale, prefix_len=prefix_len)


# ---------------------------------------------------------------------------
# prefill (dense full attention; o only, in q's dtype)
# ---------------------------------------------------------------------------

def prefill(q, k, v, pos_q, pos_k, *, causal=True, window=None, scale=None,
            prefix_len=None, impl="ref") -> jax.Array:
    """Full masked attention over a dense K/V set (batched positions ok)."""
    o, _ = block_fwd(q, k, v, pos_q, pos_k, causal=causal, window=window,
                     scale=scale, prefix_len=prefix_len, impl=impl)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# decode (per-shard partials; the caller lse-combines across SP shards)
# ---------------------------------------------------------------------------

def decode(q, k, v, pos_q, pos_k, *, causal=True, window=None, scale=None,
           impl="ref") -> Tuple[jax.Array, jax.Array]:
    """M-query attention vs a dense cache slice -> partial (o, lse).

    Validity is position-encoded (the repo-wide contract): callers push the
    positions of unfilled cache slots past the query position so the causal
    mask removes them — no separate validity mask enters the kernels.
    """
    return block_fwd(q, k, v, pos_q, pos_k, causal=causal, window=window,
                     scale=scale, impl=impl)


def paged_decode(q, pool_k, pool_v, table, cache_len, rank, layer=0, *,
                 sp: int, page_size: int, window=None, scale=None,
                 impl="ref") -> Tuple[jax.Array, jax.Array]:
    """One query per row vs this shard's pages -> partial (o, lse).

    q: (B, 1, Hq, D); pool_k/pool_v: (L, pages_loc, page_size, Hkv, Dp)
    stacked over layer periods and read at ``layer`` (traced scalar), or
    one layer's (pages_loc, page_size, Hkv, Dp); Dp >= D. table: (B, W)
    local page ids (-1 = unallocated); cache_len: (B,) the new token's
    position; rank: traced scalar SP rank. Page ``w`` covers positions
    ``[(w*sp + rank)*page_size, ...)`` (round-robin layout).

    'pallas' streams page-table-indexed tiles through
    ``kernels/paged_decode.py``; 'ref' gathers the pages into a dense
    (B, W*page_size) view and reuses the jnp oracle — bit-for-bit the
    engine's pre-dispatch behaviour. Neither slices a layer out of the
    stack: both index it where they read the pages. The pools' head dim
    may be zero-padded past q's (``engine.paged_cache.pool_head_dim``).
    """
    D = q.shape[-1]
    if impl == "pallas":
        from repro.kernels import paged_decode as _paged

        o, lse = _paged.paged_decode_attention(
            pad_head(q, pool_k.shape[-1]), pool_k, pool_v, table,
            cache_len, rank, layer, sp=sp, page_size=page_size,
            window=window, scale=D ** -0.5 if scale is None else scale)
        return o[..., :D], lse

    if pool_k.ndim == 4:
        pool_k, pool_v = pool_k[None], pool_v[None]
    pages_loc = pool_k.shape[1]
    B, W = table.shape
    safe = jnp.clip(table, 0, pages_loc - 1)
    k_r, v_r = (p[layer, safe, ..., :D] for p in (pool_k, pool_v))
    k_r = k_r.reshape(B, W * page_size, *k_r.shape[3:])
    v_r = v_r.reshape(B, W * page_size, *v_r.shape[3:])
    pos = ((jnp.arange(W, dtype=jnp.int32) * sp + rank) * page_size)[:, None] \
        + jnp.arange(page_size, dtype=jnp.int32)[None]
    pos = pos.reshape(W * page_size)
    valid = jnp.repeat(table >= 0, page_size, axis=1)
    valid &= pos[None] <= cache_len[:, None]
    pos_k = jnp.where(valid, pos[None], (cache_len + 1)[:, None])
    pos_q = cache_len[:, None]
    return decode(q, k_r, v_r, pos_q, pos_k, causal=True, window=window,
                  scale=scale, impl="ref")


def paged_prefill(q, pool_k, pool_v, table, cached_len, rank, *, sp: int,
                  page_size: int, window=None, scale=None,
                  impl="ref") -> Tuple[jax.Array, jax.Array]:
    """Suffix queries vs this shard's cached-prefix pages -> partial (o, lse).

    q: (B, Sq, Hq, D) — row b's query i sits at global position
    ``cached_len[b] + i`` (the prompt suffix, bucket-padded);
    pool_k/pool_v: (pages_loc, page_size, Hkv, Dp), Dp >= D (zero pad);
    table: (B, W) local page ids (-1 = unallocated); cached_len: (B,)
    tokens already in the pool; rank: traced scalar SP rank. Keys at
    positions ``< cached_len`` are visible (strict — the suffix scores
    itself through the dense partial), page ``w`` covering
    ``[(w*sp + rank)*page_size, ...)`` (round-robin).

    'pallas' streams page-table-indexed tiles through
    ``kernels/paged_prefill.py``; 'ref' gathers the pages into a dense
    (B, W*page_size) view and masks positionally — bit-for-bit the
    suffix prefill's pre-dispatch behaviour.
    """
    D = q.shape[-1]
    if impl == "pallas":
        from repro.kernels import paged_prefill as _paged_pre

        o, lse = _paged_pre.paged_prefill_attention(
            pad_head(q, pool_k.shape[-1]), pool_k, pool_v, table,
            cached_len, rank, sp=sp, page_size=page_size, window=window,
            scale=D ** -0.5 if scale is None else scale)
        return o[..., :D], lse

    pages_loc = pool_k.shape[0]
    B, W = table.shape
    Sq = q.shape[1]
    safe = jnp.clip(table, 0, pages_loc - 1)
    k_r, v_r = (p[safe, ..., :D] for p in (pool_k, pool_v))
    k_r = k_r.reshape(B, W * page_size, *k_r.shape[3:])
    v_r = v_r.reshape(B, W * page_size, *v_r.shape[3:])
    pos = ((jnp.arange(W, dtype=jnp.int32) * sp + rank) * page_size)[:, None] \
        + jnp.arange(page_size, dtype=jnp.int32)[None]
    pos = pos.reshape(W * page_size)
    valid = jnp.repeat(table >= 0, page_size, axis=1)
    valid &= pos[None] < cached_len[:, None]
    # invalid slots (unallocated, or suffix pages being written this very
    # call) get pushed past every query position -> causally masked
    pos_k = jnp.where(valid, pos[None], (cached_len + Sq)[:, None])
    pos_q = cached_len[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None]
    return block_fwd(q, k_r.astype(q.dtype), v_r.astype(q.dtype), pos_q,
                     pos_k, causal=True, window=window, scale=scale,
                     impl="ref")


def pad_head(x, width: int):
    """``x`` with its last (head) dim zero-padded to ``width``: the page
    pools' head dim (``engine.paged_cache.pool_head_dim``). Zero lanes of
    q add nothing to a score; the output's are cut off."""
    pad = width - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


# ---------------------------------------------------------------------------
# single-device oracle (examples / tests convenience)
# ---------------------------------------------------------------------------

def mha(q, k, v, *, positions=None, causal=True, window=None, scale=None,
        prefix_len=None) -> jax.Array:
    """Plain full attention — re-exported end-to-end oracle (always ref)."""
    return _ref.mha_reference(q, k, v, positions=positions, causal=causal,
                              window=window, scale=scale,
                              prefix_len=prefix_len)
