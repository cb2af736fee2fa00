"""The paged decode step writes and reads the period-stacked KV pool in
place: its layer scan carries the whole pool and hands the token scatter
and the attention kernel the period index. These tests hold it, bit for
bit, to a per-layer reference — the same step with each period's pool
sliced out of the stack, decoded as a one-period pool and stacked back —
directly and through the engine, with the 'ref' gather and with the
Pallas kernel (interpret mode), on a stack of 2 periods x 2 attention
layers with inactive slots and positions that cross a page and the
sliding window.
"""

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.engine import Engine, EngineConfig, Request, engine, paged_cache
from repro.models import blocks, transformer
from repro.models.factory import build_model
from repro.plan import make_serve_plan
from repro.serve import step as serve_step

PAGE, WINDOW, SLOTS, PAGES, WIDTH = 4, 8, 4, 16, 4

# MoE on every second layer: a period of two attention layers
CFG = ModelConfig(
    name="tiny-2x2", family="moe", num_layers=4, d_model=32, num_heads=4,
    num_kv_heads=2, d_ff=64, vocab_size=64, head_dim=8, window=WINDOW,
    moe=MoEConfig(num_experts=2, top_k=1, d_ff_expert=32, every_n_layers=2))


def per_layer_decode_step(rt, params, cache, tokens, cfg, cache_len,
                          paged=None, active=None, sampling=None):
    """``serve.step.lm_decode_step``'s paged branch with the pool scanned
    as ``xs``/``ys``: each period's pools come out of the stack, are
    decoded as a one-period pool at index 0, and go back into it."""
    assert paged is not None and sampling is None
    pat = transformer.layer_pattern(cfg)
    x = blocks.embed(rt, params["embed"], tokens, cfg, tokens_replicated=True)

    def period_fn(x, p_and_pools):
        p, pools = p_and_pools
        new = {}
        for i, (_, mlp) in enumerate(pat):
            one = jax.tree.map(lambda a: a[None], pools[f"sub{i}"])
            x, nc = serve_step._attn_decode(
                rt, p[f"sub{i}"]["mixer"], x, one, cfg, cache_len,
                paged=paged, active=active, layer=0)
            new[f"sub{i}"] = jax.tree.map(lambda a: a[0], nc)
            x = serve_step._mlp(rt, mlp, p[f"sub{i}"], x, cfg)
        return x, new

    x, subs = jax.lax.scan(period_fn, x, (params["stack"], cache["stack"]))
    x = blocks.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params.get("lm_head", params["embed"])
    return serve_step.vocab_parallel_greedy(rt, head, x, cfg), \
        {"stack": subs}


def _plan(impl):
    return make_serve_plan(CFG, n_devices=1, decode_batch=SLOTS,
                           page_size=PAGE, max_len=WIDTH * PAGE * 2,
                           kernel_impl=impl)


def _host(pools):
    return jax.tree.map(lambda a: np.asarray(a, np.float32), pools)


def _assert_same(got, want):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(g, w, err_msg=str(path))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_decode_step_matches_per_layer_reference(impl, monkeypatch):
    """Three engine decode steps from random pools: the same tokens and
    pools as the per-layer reference; the inactive slot's pages are
    untouched and every period's pools were written."""
    model = build_model(CFG)
    plan = _plan(impl)
    mesh = plan.build_mesh()
    params = model.init(jax.random.PRNGKey(0))
    spec = paged_cache.pool_spec(CFG, PAGES, PAGE)

    def pools():
        # random contents, so a read or write of the wrong period shows
        keys = iter(jax.random.split(jax.random.PRNGKey(1), 4))
        return jax.jit(lambda: jax.tree.map(
            lambda s: jax.random.normal(next(keys), s.shape, s.dtype),
            spec))()

    # slot 0 enters page 1 at position 4, slot 1 decodes past the window,
    # slot 2 is inactive (its pages must not change), slot 3 reaches an
    # unallocated block (-1) on its third step, where nothing is written
    table = np.arange(SLOTS * WIDTH, dtype=np.int32).reshape(SLOTS, 1, WIDTH)
    table[3, 0, 3] = -1
    active = np.array([True, True, False, True])
    sampling = (np.zeros(SLOTS, np.float32), np.zeros(SLOTS, np.int32),
                np.ones(SLOTS, np.float32), np.zeros((SLOTS, 2), np.uint32))

    def decode():
        """Three steps of the decode step as ``lm_decode_step`` is now
        (traced on the first call) -> (tokens of each step, pools)."""
        fn = engine.decode_step_fn(
            CFG, engine.serve_runtime(model, plan), mesh,
            model.partition(plan.run_config().sharding_rules), PAGE)
        tokens = np.array([[1], [7], [3], [60]], np.int32)
        cache_len = np.array([3, 9, 5, 10], np.int32)
        state, out = pools(), []
        for _ in range(3):
            tok, state = fn(params, state, tokens, cache_len, table,
                            *sampling, active)
            tokens = np.asarray(tok)
            out.append(tokens)
            cache_len = cache_len + active
        return out, _host(state)

    start = _host(pools())
    got, got_pools = decode()
    monkeypatch.setattr(serve_step, "lm_decode_step", per_layer_decode_step)
    want, want_pools = decode()
    np.testing.assert_array_equal(np.stack(got), np.stack(want))
    _assert_same(got_pools, want_pools)

    idle = table[2, 0]
    for name, sub in got_pools["stack"].items():
        for kv in ("k", "v"):
            # the inactive slot's pages are as they were
            np.testing.assert_array_equal(
                sub[kv][:, idle], start["stack"][name][kv][:, idle])
    changed = [
        not np.array_equal(got_pools["stack"][s][kv][l],
                           start["stack"][s][kv][l])
        for s in got_pools["stack"] for kv in ("k", "v") for l in range(2)]
    assert all(changed), "a period's pool was never written"


def test_engine_decode_matches_per_layer_reference(monkeypatch):
    """Staggered requests through the engine ('ref' gather): the same
    tokens and the same pools as the engine running the per-layer
    reference step."""
    rng = np.random.default_rng(5)
    reqs = [Request("a", rng.integers(0, 64, 6).tolist(), 6),
            Request("b", rng.integers(0, 64, 3).tolist(), 9),
            Request("c", rng.integers(0, 64, 10).tolist(), 4)]

    def serve():
        eng = Engine(build_model(CFG), _plan("ref"),
                     EngineConfig(pages_per_shard=PAGES))
        eng.add_request(reqs[0])
        eng.add_request(reqs[1])
        eng.step()
        eng.step()
        eng.add_request(reqs[2])
        return eng.run(), _host(eng.pools)

    out, pools = serve()
    monkeypatch.setattr(serve_step, "lm_decode_step", per_layer_decode_step)
    want_out, want_pools = serve()
    assert out == want_out
    assert [len(out[r.uid]) for r in reqs] == [6, 9, 4]
    _assert_same(pools, want_pools)
