"""Substrate tests: combine, optimizer, checkpoint, elastic, data, grads."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import combine
from repro.dist import checkpoint, elastic
from repro.optim import adamw, grad as grad_lib


# ---- lse combine ------------------------------------------------------------

@given(st.integers(0, 1000), st.integers(2, 6), st.integers(2, 5))
@settings(max_examples=30, deadline=None)
def test_property_combine_matches_joint_softmax(seed, sq, blocks):
    """Combining per-block (o, lse) over disjoint key blocks == softmax over
    the union — for random splits (associativity + exactness)."""
    from repro.kernels import ref

    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    B, H, D, Sk = 1, 2, 8, 4 * blocks
    q = jax.random.normal(kq, (B, sq, H, D))
    k = jax.random.normal(kk, (B, Sk, H, D))
    v = jax.random.normal(kv, (B, Sk, H, D))
    pos_q = jnp.arange(sq, dtype=jnp.int32) + Sk  # all keys visible (causal)
    pos_k = jnp.arange(Sk, dtype=jnp.int32)

    o_ref, lse_ref = ref.block_attention(q, k, v, pos_q, pos_k, causal=True)

    o_acc = jnp.zeros((B, sq, H, D), jnp.float32)
    lse_acc = jnp.full((B, H, sq), combine.NEG_INF, jnp.float32)
    for i in range(blocks):
        sl = slice(4 * i, 4 * (i + 1))
        o_i, lse_i = ref.block_attention(q, k[:, sl], v[:, sl], pos_q,
                                         pos_k[sl], causal=True)
        o_acc, lse_acc = combine.combine_pair(o_acc, lse_acc, o_i, lse_i)
    np.testing.assert_allclose(np.asarray(o_acc), np.asarray(o_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_acc), np.asarray(lse_ref),
                               atol=1e-5, rtol=1e-5)


def test_combine_dead_blocks():
    o = jnp.ones((1, 2, 2, 4))
    lse = jnp.zeros((1, 2, 2))
    dead_o = jnp.zeros_like(o)
    dead_lse = jnp.full_like(lse, combine.NEG_INF)
    oc, lc = combine.combine_pair(dead_o, dead_lse, o, lse)
    np.testing.assert_allclose(np.asarray(oc), np.asarray(o))
    oc, lc = combine.combine_pair(dead_o, dead_lse, dead_o, dead_lse)
    assert np.all(np.asarray(lc) <= combine.NEG_INF / 2)
    assert np.all(np.asarray(oc) == 0)


# ---- optimizer --------------------------------------------------------------

def test_adamw_converges_quadratic():
    cfg = adamw.AdamWConfig(learning_rate=0.1, weight_decay=0.0,
                            warmup_steps=0, decay_steps=10_000)
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw.init_state(params, cfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, m = adamw.apply(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 1e-2
    assert m["grad_norm"] > 0


def test_adamw_grad_clip():
    cfg = adamw.AdamWConfig(grad_clip=1.0, warmup_steps=0)
    params = {"w": jnp.zeros(3)}
    state = adamw.init_state(params, cfg)
    _, _, m = adamw.apply(params, {"w": jnp.full(3, 1e6)}, state, cfg)
    assert np.isfinite(m["grad_norm"])


def test_schedule_warmup_and_decay():
    cfg = adamw.AdamWConfig(learning_rate=1.0, warmup_steps=10,
                            decay_steps=100, min_lr_ratio=0.1)
    lr0 = adamw.schedule(jnp.asarray(1), cfg)
    lr_mid = adamw.schedule(jnp.asarray(10), cfg)
    lr_end = adamw.schedule(jnp.asarray(100), cfg)
    assert float(lr0) < float(lr_mid)
    assert abs(float(lr_mid) - 1.0) < 1e-6
    assert abs(float(lr_end) - 0.1) < 1e-3


# ---- gradient compression ---------------------------------------------------

def test_int8_roundtrip_error_bounded():
    g = {"a": jnp.linspace(-3, 7, 100)}
    d = grad_lib.int8_roundtrip(g)
    err = float(jnp.abs(d["a"] - g["a"]).max())
    assert err <= 7 / 127.0 + 1e-6


def test_error_feedback_converges():
    """With error feedback, the accumulated compressed sum tracks the true
    sum (the residual stays bounded)."""
    g = {"a": jnp.array([0.001, -0.5, 2.0])}
    res = grad_lib.zeros_like_residual(g)
    total_c = jnp.zeros(3)
    for i in range(50):
        d, res = grad_lib.error_feedback_compress(g, res)
        total_c = total_c + d["a"]
    total_true = g["a"] * 50
    rel = float(jnp.abs(total_c - total_true).max() /
                jnp.abs(total_true).max())
    assert rel < 0.02


# ---- checkpoint -------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "b": {"c": jnp.ones(4, jnp.int32)}}
    checkpoint.save(tmp_path, 7, tree)
    assert checkpoint.latest_step(tmp_path) == 7
    out = checkpoint.restore(tmp_path, 7, tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_checkpoint_atomicity(tmp_path):
    tree = {"a": jnp.zeros(3)}
    checkpoint.save(tmp_path, 1, tree)
    # a stale tmp dir from a "crashed" writer must not be visible
    (tmp_path / "step_00000002.tmp").mkdir()
    assert checkpoint.latest_step(tmp_path) == 1


def test_checkpoint_async(tmp_path):
    tree = {"a": jnp.ones(10)}
    t = checkpoint.save(tmp_path, 3, tree, blocking=False)
    t.join()
    assert checkpoint.latest_step(tmp_path) == 3


# ---- elastic ---------------------------------------------------------------

def test_plan_mesh_full_and_degraded():
    p = elastic.plan_mesh(512, model_axis_target=16)
    assert (p.data, p.model) == (32, 16)
    p = elastic.plan_mesh(511, model_axis_target=16)   # one node lost
    assert p.model == 16 and p.data == 31
    p = elastic.plan_mesh(12, model_axis_target=16)    # small pool
    assert p.devices <= 12 and p.model >= 4
    with pytest.raises(ValueError):
        elastic.plan_mesh(2, model_axis_target=16)


def test_straggler_detector_flags_persistent_slow():
    durations = [1.0] * 10 + [5.0] * 5
    ticks = []
    t = 0.0
    for d in durations:
        ticks.extend([t, t + d])
        t += d
    times = iter(ticks)
    det = elastic.StragglerDetector(window=10, threshold=2.0, patience=3,
                                    clock=lambda: next(times))
    flags = []
    for _ in durations:
        det.step_start()
        flags.append(det.step_end())
    assert not any(flags[:10])      # healthy phase: no false positives
    assert any(flags[10:])          # persistent slowdown flagged


# ---- data pipeline -----------------------------------------------------------

def test_synthetic_deterministic_and_zigzagged():
    from repro.configs import registry
    from repro.configs.base import ShapeConfig
    from repro.core import zigzag as zz
    from repro.data.pipeline import SyntheticLM

    cfg = registry.get_smoke("h2o-danube-1.8b")
    shape = ShapeConfig("t", seq_len=32, global_batch=2, kind="train")
    src1 = SyntheticLM(cfg, shape, seed=1, sp_size=4)
    src2 = SyntheticLM(cfg, shape, seed=1, sp_size=4)
    b1, b2 = src1.get_batch(5), src2.get_batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token in GLOBAL order: unshard and check
    pos = zz.make_positions(32, 4, "zigzag")
    toks = zz.unshard_tokens(b1["tokens"], pos, axis=1)
    labs = zz.unshard_tokens(b1["labels"], pos, axis=1)
    np.testing.assert_array_equal(labs[:, :-1], toks[:, 1:])


def test_token_file_source(tmp_path):
    from repro.configs import registry
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import TokenFile

    cfg = registry.get_smoke("h2o-danube-1.8b")
    shape = ShapeConfig("t", seq_len=16, global_batch=2, kind="train")
    data = np.arange(3 * 2 * 17, dtype=np.uint16)
    f = tmp_path / "toks.bin"
    data.tofile(f)
    src = TokenFile(str(f), cfg, shape, sp_size=2)
    b0 = src.get_batch(0)
    b3 = src.get_batch(3)  # wraps around
    assert b0["tokens"].shape == (2, 16)
    np.testing.assert_array_equal(b0["tokens"], b3["tokens"])


# ---- scheduler ---------------------------------------------------------------

def test_scheduler_prefers_larger_c_when_comm_bound():
    from repro.core import scheduler as sch

    w = sch.AttnWorkload(batch=1, seq_len=512 * 1024, num_heads=32,
                         num_kv_heads=8, head_dim=128)
    # very slow links -> communication dominates -> big C wins
    slow = sch.ClusterModel(sp_size=16, link_bw=1e9)
    out = sch.schedule(w, slow)
    assert out["best"]["c"] >= 2
    # infinitely fast links -> compute bound -> C=1 is fine (no worse)
    fast = sch.ClusterModel(sp_size=16, link_bw=1e15, step_latency=0.0)
    out_f = sch.schedule(w, fast)
    costs = {g["c"]: g["total_s"] for g in out_f["grid"]
             if g["placement"] == "team_inner"}
    assert abs(costs[1] - min(costs.values())) / costs[1] < 0.05


def test_scheduler_profile_fn_hook():
    from repro.core import scheduler as sch

    w = sch.AttnWorkload(batch=1, seq_len=1024, num_heads=4, num_kv_heads=4,
                         head_dim=64)
    cl = sch.ClusterModel(sp_size=16)
    out = sch.schedule(w, cl, profile_fn=lambda c, p: abs(c - 2) + (p == "ring_inner") * 0.1)
    assert out["best"]["c"] == 2 and out["best"]["placement"] == "team_inner"


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "jamba-1.5-large-398b"])
def test_init_sharded_matches_eager_init(arch):
    """Params created in their sharded layout (one jit with out_shardings,
    as the trainer and engine do) equal eager ``model.init`` of the same
    seed bit for bit."""
    from jax.sharding import NamedSharding

    from repro.configs import registry
    from repro.dist import meshes
    from repro.models.factory import build_model

    model = build_model(registry.get_smoke(arch))
    mesh = meshes.local_mesh_for_tests(c=1, r=1)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), model.partition())
    want = model.init(jax.random.PRNGKey(5))
    got = model.init_sharded(sh, seed=5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
