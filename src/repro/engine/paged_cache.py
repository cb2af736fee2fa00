"""Block-paged KV cache with an SP-sharded page pool.

Layout
------
Each attention sub-layer owns a pool of fixed-size pages

    k, v : (n_periods, P_sp * pages_per_shard, page_size, Hkv, hd_pool)

sharded on the *page* dimension over the concentric SP axes
``(sp_grp, sp_ring, sp_team)`` — the same axes (and the same linear rank
order, ``rank = (g*R + r)*C + t``) that ``serve.kv_cache.cache_partition_for``
uses for the contiguous decode cache. Inside the decode island every shard
therefore holds a ``(n_periods, pages_per_shard, page_size, Hkv, hd_pool)``
slice.

``hd_pool`` is the head dim rounded up to whole 128-lane tiles
(:func:`pool_head_dim`), the pad lanes zero. The TPU compiler stores an
array row-major only when its minor dim fills whole lane tiles; otherwise
it moves the page dim minor-most, and every program that hands the pool
to a paged kernel (which DMAs whole pages, row-major) re-lays the pool
out. With whole tiles the pool stays in the kernels' layout for good, at
the price of the pad (80 -> 128 at h2o-danube widths: 1.6x the payload).

A sequence's logical KV blocks (block ``b`` covers token positions
``[b*page_size, (b+1)*page_size)``) are distributed **round-robin** over the
SP shards: block ``b`` lives on shard ``b % P_sp`` as that shard's ``b //
P_sp``-th block of the sequence. The page table is a replicated

    table : (max_slots, P_sp, W) int32     # local page id, -1 = unallocated

so each shard reads its own row (``dynamic_index`` at the traced rank) and
touches only ``ceil(blocks / P_sp)`` pages per sequence — per-device decode
compute and memory stay flat in the SP degree, exactly the Ring-Attention
degenerate configuration of ``core.startrail.decode_attention`` (partial
attention per shard + global lse-combine ``psum``).

Validity is encoded through *positions*, as everywhere else in this repo:
unallocated/unfilled slots get ``pos = cache_len + 1`` so the causal mask
kills them — no extra mask plumbing through the attention kernels.

Device-side helpers in this module are pure functions meant to run inside a
``shard_map`` island; host-side page accounting lives in
``repro.engine.scheduler`` on top of this module's :class:`PagePool` —
the ref-counted free list that lets several sequences share immutable
pages (the copy-on-write substrate of ``repro.gateway``'s prefix cache).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.sharding import SP_AXES
from repro.kernels.dispatch import pad_head
from repro.models import transformer
from repro.models.runtime import Runtime


class PagePool:
    """Host-side, ref-counted page free lists (one per SP shard).

    Every physical page carries a reference count: 1 for each live sequence
    whose page table points at it, plus 1 when the gateway's prefix cache
    retains it. Pages return to the free list only when the count reaches
    zero, so a shared prefix page outlives any single request and an
    over-release (double free) is a loud error instead of silent cache
    corruption. Shared pages are **immutable by construction** — decode
    appends land in blocks past the shared full-prompt prefix — so
    copy-on-write never has to copy; the ref counts are the entire
    write-safety story (see docs/SERVING.md, "COW semantics").
    """

    def __init__(self, sp: int, pages_per_shard: int):
        self.sp = sp
        self.pages_per_shard = pages_per_shard
        self.free: List[List[int]] = [
            list(range(pages_per_shard - 1, -1, -1)) for _ in range(sp)]
        self.refs = np.zeros((sp, pages_per_shard), np.int32)

    def available(self, shard: int) -> int:
        return len(self.free[shard])

    def alloc(self, shard: int) -> int:
        """Pop a free page on ``shard`` with refcount 1."""
        if not self.free[shard]:
            raise RuntimeError(
                f"page pool exhausted on shard {shard} "
                f"({self.pages_per_shard} pages)")
        page = self.free[shard].pop()
        assert self.refs[shard, page] == 0, "free-list page had live refs"
        self.refs[shard, page] = 1
        return page

    def incref(self, shard: int, page: int) -> None:
        if self.refs[shard, page] <= 0:
            raise ValueError(
                f"incref of free page ({shard}, {page}) — stale reference")
        self.refs[shard, page] += 1

    def decref(self, shard: int, page: int) -> bool:
        """Drop one reference; returns True when the page was freed."""
        if self.refs[shard, page] <= 0:
            raise ValueError(
                f"double free of page ({shard}, {page}): refcount already 0")
        self.refs[shard, page] -= 1
        if self.refs[shard, page] == 0:
            self.free[shard].append(page)
            return True
        return False

    def pages_in_use(self) -> int:
        return self.sp * self.pages_per_shard - sum(
            len(f) for f in self.free)

    def pages_total(self) -> int:
        return self.sp * self.pages_per_shard


@dataclasses.dataclass(frozen=True)
class PagedTables:
    """Traced page-table view threaded through the decode step.

    table: (B, P_sp, W) int32, replicated — local page ids per (slot, shard,
      local block); -1 marks unallocated entries.
    page_size: static tokens per page.
    """

    table: jax.Array
    page_size: int


def supported(cfg: ModelConfig) -> Tuple[bool, str]:
    """Engine v1 serves decoder-only stacks whose mixers are all attention
    (paged KV is meaningless for recurrent per-slot states; those archs
    keep the contiguous serving path)."""
    if cfg.encdec:
        return False, "encoder-decoder archs use the contiguous serve path"
    if cfg.frontend_stub is not None:
        return False, "frontend (VLM/audio) archs use the contiguous serve path"
    for mixer, _ in transformer.layer_pattern(cfg):
        if mixer != "attn":
            return False, (f"mixer {mixer!r} keeps per-slot recurrent state; "
                           "paged engine v1 covers attention mixers only")
    return True, ""


LANES = 128


def pool_head_dim(cfg: ModelConfig) -> int:
    """The pools' head dim: ``cfg.head_dim_`` rounded up to whole lane
    tiles."""
    return -(-cfg.head_dim_ // LANES) * LANES


def pool_spec(cfg: ModelConfig, pages_global: int, page_size: int):
    """Abstract pool tree {'stack': {subN: {'k','v'}}} (period-stacked)."""
    dtype = jnp.dtype(cfg.param_dtype)
    pat = transformer.layer_pattern(cfg)
    n_periods = cfg.num_layers // len(pat)
    leaf = jax.ShapeDtypeStruct(
        (n_periods, pages_global, page_size, cfg.num_kv_heads,
         pool_head_dim(cfg)), dtype)
    return {"stack": {f"sub{i}": {"k": leaf, "v": leaf}
                      for i in range(len(pat))}}


def pool_partition(cfg: ModelConfig):
    """PartitionSpec tree matching pool_spec: pages sharded over SP."""
    pat = transformer.layer_pattern(cfg)
    spec = P(None, SP_AXES, None, None, None)
    return {"stack": {f"sub{i}": {"k": spec, "v": spec}
                      for i in range(len(pat))}}


def zeroed_pools_fn(cfg: ModelConfig, mesh, pages_global: int,
                    page_size: int):
    """A jitted, argument-free maker of zeroed pools: each device makes its
    own SP shard in place, so the pools come out sharded as every step
    island returns them."""
    sp = 1
    for a in SP_AXES:
        sp *= mesh.shape[a]
    local = pool_spec(cfg, pages_global // sp, page_size)
    return jax.jit(jax.shard_map(
        lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), local),
        mesh=mesh, in_specs=(), out_specs=pool_partition(cfg),
        check_vma=False))


# ---------------------------------------------------------------------------
# device-side read/write (call inside shard_map; pools are local slices)
# ---------------------------------------------------------------------------

def read_pages(rt: Runtime, pools, idx):
    """Gather whole pages by *global* page id, replicated to every device.

    pools: the full pool tree's local slices, leaves
      (n_periods, pages_loc, page_size, Hkv, hd_pool).
    idx: (B,) int32 global page ids (``shard * pages_loc + local_page``);
      -1 pads the fixed transfer bucket (padding reads as zeros).

    Each shard contributes the pages it owns (zeros elsewhere); a psum
    over the SP axes rebuilds the full batch on every device, so the
    caller can pull the result to the host from any one of them. This is
    the device->host leg of the KV connector's spill and of the
    prefill->decode handoff (`engine.kv_connector`).
    """
    rank = rt.sp_rank()

    def leaf(pool):
        pages_loc = pool.shape[1]
        local = idx - rank * pages_loc
        ok = (idx >= 0) & (local >= 0) & (local < pages_loc)
        vals = jnp.take(pool, jnp.where(ok, local, 0), axis=1)
        vals = jnp.where(ok[None, :, None, None, None], vals,
                         jnp.zeros_like(vals))
        return rt.psum_model(vals)

    return jax.tree.map(leaf, pools)


def write_pages(rt: Runtime, pools, idx, data):
    """Scatter whole pages by global page id (inverse of ``read_pages``).

    data: a tree like ``pools`` with leaves (n_periods, B, page_size, Hkv,
    hd_pool), replicated. Every shard writes only the batch entries whose page
    it owns; idx -1 (bucket padding) and out-of-range ids drop. This is
    the host->device leg of the connector's reload and of the decode-side
    handoff injection.
    """
    rank = rt.sp_rank()

    def leaf(pool, d):
        pages_loc = pool.shape[1]
        local = idx - rank * pages_loc
        ok = (idx >= 0) & (local >= 0) & (local < pages_loc)
        tgt = jnp.where(ok, local, pages_loc)               # OOB -> drop
        return pool.at[:, tgt].set(d.astype(pool.dtype), mode="drop")

    return jax.tree.map(leaf, pools, data)


def write_token(rt: Runtime, pools: Dict[str, jax.Array], k_new, v_new,
                paged: PagedTables, cache_len, active, layer):
    """Append one token per slot into its owning shard's page of period
    ``layer``.

    pools: {'k','v'} this shard's period-stacked pool slices
      (n_periods, pages_loc, page_size, Hkv, hd_pool); the token lands in
      place at ``[layer, page, offset]`` (one scatter per leaf — no layer
      is sliced out of the stack).
    k_new/v_new: (B, 1, Hkv, hd) — post-RoPE K and V of the new token
      (zero-padded here to the pools' head dim).
    cache_len: (B,) int32 — the new token's global position.
    active: (B,) bool or None — inactive slots write nothing.
    layer: traced int32 scalar — the period being decoded.

    Returns (new_pools, tbl) where tbl (B, W) is this shard's slice of the
    page table — the operand both decode-kernel paths consume (the Pallas
    paged kernel indexes the pool with it directly; the ref path gathers a
    dense view via ``kernels.dispatch.paged_decode(..., impl='ref')``).
    """
    pool_k, pool_v = pools["k"], pools["v"]
    pages_loc, ps = pool_k.shape[1], paged.page_size
    rank = rt.sp_rank()
    sp = rt.sp_size()
    tbl = jax.lax.dynamic_index_in_dim(paged.table, rank, axis=1,
                                       keepdims=False)        # (B, W)
    B, W = tbl.shape

    g = cache_len // ps                                       # global block
    j = g // sp                                               # local block
    page = jnp.take_along_axis(tbl, jnp.clip(j, 0, W - 1)[:, None],
                               axis=1)[:, 0]
    ok = ((g % sp) == rank) & (j < W) & (page >= 0)
    if active is not None:
        ok &= active
    page = jnp.where(ok, page, pages_loc)                     # OOB -> drop
    off = cache_len % ps
    hd_pool = pool_k.shape[-1]
    pool_k = pool_k.at[layer, page, off].set(
        pad_head(k_new[:, 0], hd_pool).astype(pool_k.dtype), mode="drop")
    pool_v = pool_v.at[layer, page, off].set(
        pad_head(v_new[:, 0], hd_pool).astype(pool_v.dtype), mode="drop")
    return {"k": pool_k, "v": pool_v}, tbl


def insert_prompt(rt: Runtime, pools_sub: Dict[str, jax.Array],
                  k_stack, v_stack, table_row, prompt_len, page_size: int):
    """Scatter a prefilled sequence's K/V into this shard's pool pages.

    pools_sub: {'k','v'} local slices (n_periods, pages_loc, ps, Hkv,
      hd_pool).
    k_stack/v_stack: (n_periods, 1, S_loc, Hkv, hd) — the prefill cache of
      one sequence, SP-sharded contiguously (post-RoPE, as written by
      ``serve.step.lm_prefill``).
    table_row: (P_sp, W) int32 — the target slot's page-table row.
    prompt_len: traced scalar int32 — tokens beyond it are padding; their
      blocks are never written (and padding *within* a prompt's last block
      is written but unreadable: its positions exceed every cache_len until
      decode overwrites them).

    The prompt arrives sequence-sharded but pages are owned round-robin, so
    one tiled all_gather over the SP axes (O(L) — same order as the prefill
    itself) re-materialises the full prompt before each shard scatters the
    blocks it owns.
    """
    rank = rt.sp_rank()
    sp = rt.sp_size()
    ps = page_size
    kg = rt.all_gather_model(k_stack, axis=2)[:, 0]     # (n_per, L, Hkv, hd)
    vg = rt.all_gather_model(v_stack, axis=2)[:, 0]
    n_per, L = kg.shape[0], kg.shape[1]
    G = L // ps
    kb = kg.reshape(n_per, G, ps, *kg.shape[2:])
    vb = vg.reshape(n_per, G, ps, *vg.shape[2:])

    tbl = jax.lax.dynamic_index_in_dim(table_row, rank, axis=0,
                                       keepdims=False)  # (W,)
    W = tbl.shape[0]
    pages_loc = pools_sub["k"].shape[1]
    gidx = jnp.arange(G, dtype=jnp.int32)
    j = gidx // sp
    page = tbl[jnp.clip(j, 0, W - 1)]
    mine = ((gidx % sp) == rank) & (gidx * ps < prompt_len) \
        & (j < W) & (page >= 0)
    page = jnp.where(mine, page, pages_loc)             # OOB -> drop
    hd_pool = pools_sub["k"].shape[-1]
    pool_k = pools_sub["k"].at[:, page].set(
        pad_head(kb, hd_pool).astype(pools_sub["k"].dtype), mode="drop")
    pool_v = pools_sub["v"].at[:, page].set(
        pad_head(vb, hd_pool).astype(pools_sub["v"].dtype), mode="drop")
    return {"k": pool_k, "v": pool_v}
