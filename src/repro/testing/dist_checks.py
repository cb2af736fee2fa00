"""Distributed correctness checks for StarTrail attention.

Run standalone with 8 forced host devices (pytest launches this module in a
subprocess so the main test session keeps seeing 1 device):

    python -m repro.testing.dist_checks [check_name ...]

Every check compares the distributed implementation bit-for-bit semantics
(<= tolerance) against the single-device full-attention oracle in
``repro.kernels.ref`` — forward and gradients.
"""

import os
import sys

if __name__ == "__main__":  # set before jax import
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import startrail as st
from repro.core import topology as topo_lib
from repro.core import ulysses as ulysses_lib
from repro.core import zigzag as zz
from repro.kernels import ref as ref_kernels

AXES = ("sp_grp", "sp_ring", "sp_team")


def make_mesh(c: int, p: int):
    r = p // (c * c)
    devs = np.array(jax.devices()[:p]).reshape(c, r, c)
    return jax.sharding.Mesh(devs, AXES)


def to_sharded_layout(x: np.ndarray, positions: np.ndarray) -> np.ndarray:
    """Reorder global (B, N, ...) so an even split over axis 1 matches the
    per-shard position layout."""
    return np.take(x, positions.reshape(-1), axis=1)


def from_sharded_layout(x: np.ndarray, positions: np.ndarray) -> np.ndarray:
    inv = zz.inverse_permutation_for(positions)
    return np.take(x, inv, axis=1)


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


def check_attention(c, p, *, causal, scheme, window=None, hq=4, hkv=2,
                    dtype=jnp.float32, seq=64, batch=2, d=8, impl="ref",
                    block_skip=False, tol=2e-4):
    """StarTrail forward + grads vs full-attention oracle."""
    mesh = make_mesh(c, p)
    cfg = st.StarTrailConfig(
        seq_len=seq, axes=AXES, seq_scheme=scheme, causal=causal,
        window=window, block_impl=impl, block_skip=block_skip,
    )
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = _rand(kq, (batch, seq, hq, d), dtype)
    k = _rand(kk, (batch, seq, hkv, d), dtype)
    v = _rand(kv, (batch, seq, hkv, d), dtype)
    do = _rand(kg, (batch, seq, hq, d), dtype)

    positions = zz.make_positions(seq, p, scheme)
    spec = P(None, AXES, None, None)

    def local(q, k, v):
        return st.startrail_attention(q, k, v, cfg)

    dist = jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec, check_vma=False)
    )

    qs = jnp.asarray(to_sharded_layout(np.asarray(q), positions))
    ks = jnp.asarray(to_sharded_layout(np.asarray(k), positions))
    vs = jnp.asarray(to_sharded_layout(np.asarray(v), positions))
    dos = jnp.asarray(to_sharded_layout(np.asarray(do), positions))

    # forward
    o_dist = from_sharded_layout(np.asarray(dist(qs, ks, vs)), positions)
    o_ref = np.asarray(
        ref_kernels.mha_reference(q, k, v, causal=causal, window=window)
    )
    err = np.abs(o_dist.astype(np.float32) - o_ref.astype(np.float32)).max()
    assert err < tol, f"forward err {err} (C={c}, causal={causal}, {scheme})"

    # gradients
    def loss_dist(q, k, v):
        return (dist(q, k, v).astype(jnp.float32) * dos.astype(jnp.float32)).sum()

    def loss_ref(q, k, v):
        o = ref_kernels.mha_reference(q, k, v, causal=causal, window=window)
        return (o.astype(jnp.float32) * do.astype(jnp.float32)).sum()

    gd = jax.jit(jax.grad(loss_dist, argnums=(0, 1, 2)))(qs, ks, vs)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gd, gr):
        a = from_sharded_layout(np.asarray(a), positions)
        e = np.abs(a.astype(np.float32) - np.asarray(b).astype(np.float32)).max()
        assert e < tol, f"grad d{name} err {e} (C={c}, causal={causal}, {scheme})"
    return err


def check_ulysses(p=4, seq=32, hq=8, hkv=4, d=8, causal=True):
    mesh = make_mesh(1, p)  # (1, p, 1)
    cfg = st.StarTrailConfig(seq_len=seq, axes=AXES, seq_scheme="contiguous",
                             causal=causal)
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (2, seq, hq, d))
    k = _rand(kk, (2, seq, hkv, d))
    v = _rand(kv, (2, seq, hkv, d))
    spec = P(None, AXES, None, None)
    dist = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_lib.ulysses_attention(q, k, v, cfg),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False))
    o = np.asarray(dist(q, k, v))
    o_ref = np.asarray(ref_kernels.mha_reference(q, k, v, causal=causal))
    err = np.abs(o - o_ref).max()
    assert err < 2e-4, f"ulysses err {err}"


def check_decode(p=8, cache_len=64, hq=4, hkv=2, d=8):
    c = 2
    mesh = make_mesh(c, p)
    cfg = st.StarTrailConfig(seq_len=cache_len, axes=AXES,
                             seq_scheme="contiguous", causal=True)
    key = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(key, 3)
    B = 2
    q = _rand(kq, (B, 1, hq, d))
    k = _rand(kk, (B, cache_len, hkv, d))
    v = _rand(kv, (B, cache_len, hkv, d))
    pos_q = jnp.array([cache_len - 1], jnp.int32)

    spec_kv = P(None, AXES, None, None)

    def local(q, k, v):
        # contiguous cache shard positions
        gi = jax.lax.axis_index(AXES[0])
        ji = jax.lax.axis_index(AXES[1])
        ti = jax.lax.axis_index(AXES[2])
        r = p // (c * c)
        rank = (gi * r + ji) * c + ti
        pos_k = st.shard_positions(rank, cache_len, p, "contiguous")
        return st.decode_attention(q, k, v, pos_q, pos_k, cfg)

    dist = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, None, None), spec_kv, spec_kv),
        out_specs=P(None, None, None, None), check_vma=False))
    o = np.asarray(dist(q, k, v))
    o_ref, _ = ref_kernels.block_attention(
        q, k, v, pos_q, jnp.arange(cache_len, dtype=jnp.int32), causal=True)
    err = np.abs(o - np.asarray(o_ref)).max()
    assert err < 2e-4, f"decode err {err}"


def check_topology_vs_paper():
    """Structural formulation == verbatim paper Algs. 2/3 for many (P, C)."""
    for p in (4, 8, 16, 64, 256):
        for c in topo_lib.valid_c_values(p):
            tp = topo_lib.StarTrailTopology(p, c)
            tp.check_invariants()
            d_t, d_a = tp.num_teams, c
            # Alg 2: member (r_t, r_a)'s send target == structural placement
            perm = dict(tp.init_placement_permutation())
            for r_t in range(d_t):
                for r_a in range(d_a):
                    src = r_t * c + r_a
                    assert perm[src] == topo_lib.paper_get_init_send(r_t, r_a, d_t, d_a), (
                        p, c, r_t, r_a)
            # Alg 3: ring neighbours == structural ring permutation
            ring = dict(tp.ring_permutation(shift=1))
            for r_t in range(d_t):
                for r_a in range(d_a):
                    src = r_t * c + r_a
                    nxt, _last = topo_lib.paper_get_p2p_config(r_t, r_a, d_t, d_a)
                    # our ring sends j -> j-1 i.e. to the *last* team; the
                    # paper's "next" is the other direction. Both tours are
                    # valid; assert we send to one of the two neighbours and
                    # the tour is a single cycle per ring.
                    assert ring[src] in (nxt, _last), (p, c, src)


CHECKS = {
    "topology": check_topology_vs_paper,
    "ring_causal_zigzag": functools.partial(
        check_attention, 1, 8, causal=True, scheme="zigzag"),
    "ring_full_contig": functools.partial(
        check_attention, 1, 8, causal=False, scheme="contiguous"),
    "st2_causal_zigzag": functools.partial(
        check_attention, 2, 8, causal=True, scheme="zigzag"),
    "st2_causal_contig": functools.partial(
        check_attention, 2, 8, causal=True, scheme="contiguous"),
    "st2_full": functools.partial(
        check_attention, 2, 8, causal=False, scheme="contiguous"),
    "st2_window": functools.partial(
        check_attention, 2, 8, causal=True, scheme="zigzag", window=16),
    "st2_window_skip": functools.partial(
        check_attention, 2, 8, causal=True, scheme="contiguous", window=16,
        block_skip=True),
    "st2_mha": functools.partial(
        check_attention, 2, 8, causal=True, scheme="zigzag", hq=4, hkv=4),
    "st2_mqa": functools.partial(
        check_attention, 2, 8, causal=True, scheme="zigzag", hq=4, hkv=1),
    "st2_bf16": functools.partial(
        check_attention, 2, 8, causal=True, scheme="zigzag",
        dtype=jnp.bfloat16, tol=5e-2),
    "st2_r1": functools.partial(  # R=1: fully-collective degenerate point
        check_attention, 2, 4, causal=True, scheme="zigzag"),
    "st2_pallas": functools.partial(
        check_attention, 2, 8, causal=True, scheme="zigzag", impl="pallas",
        seq=64, d=64, tol=5e-4),
    "ulysses": check_ulysses,
    "decode": check_decode,
    # 16-device factorisations (run with device_count=16): C=4 is the
    # fully-collective degenerate point at P=16 (R=1); C=2 gives R=4 rings
    "st4_p16": functools.partial(
        check_attention, 4, 16, causal=True, scheme="zigzag", seq=64),
    "st2_p16_r4": functools.partial(
        check_attention, 2, 16, causal=True, scheme="zigzag", seq=64),
    "st2_p16_window": functools.partial(
        check_attention, 2, 16, causal=True, scheme="contiguous", window=24,
        block_skip=True, seq=64),
}


def main(argv):
    names = argv or list(CHECKS)
    failures = []
    for name in names:
        try:
            CHECKS[name]()
            print(f"PASS {name}")
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"FAIL {name}: {e!r}")
    if failures:
        sys.exit(1)
    print(f"ALL {len(names)} DISTRIBUTED CHECKS PASSED")




# ---------------------------------------------------------------------------
# end-to-end manual-SPMD model equivalence: spmd loss/grads == local mode
# ---------------------------------------------------------------------------

def check_spmd_model(arch="h2o-danube-1.8b", c=2, data=2, seq=32, batch=2,
                     tol=2e-3, grad_tol=None, check_grads=True):
    """tol guards the loss equivalence; grad_tol (default: tol) the grads —
    kept separate so archs with large-magnitude grads can loosen only the
    grad bound without weakening the loss check."""
    import dataclasses as dc

    from repro.configs import registry
    from repro.configs.base import MoEConfig, RunConfig, ShapeConfig
    from repro.core import zigzag as zz
    from repro.dist import meshes
    from repro.models.factory import build_model
    from repro.models.runtime import Runtime
    from repro.train import step as train_step

    cfg = registry.get_smoke(arch)
    if cfg.moe is not None:
        # avoid token dropping so local and spmd routing agree exactly
        cfg = dc.replace(cfg, moe=dc.replace(cfg.moe, capacity_factor=8.0))
    model = build_model(cfg)
    shape = ShapeConfig("test", seq_len=seq, global_batch=batch, kind="train")
    run_cfg = RunConfig(c=c, seq_scheme="zigzag")

    r = 8 // (data * c * c)
    mesh = meshes.local_mesh_for_tests(c=c, r=r, data=data)

    # one island build: the fwd+bwd vg island already returns the loss, so
    # the grad checks reuse its compile; loss-only archs keep the cheaper
    # forward-only island
    if check_grads:
        island_fn, rt = train_step.build_value_and_grad_fn(model, mesh,
                                                           run_cfg, shape)
    else:
        island_fn, rt = train_step.build_loss_fn(model, mesh, run_cfg, shape)
    rt_local = train_step.make_runtime(model, run_cfg, shape, mode="local")

    params = model.init(jax.random.PRNGKey(0))
    batch_g = model.make_batch(jax.random.PRNGKey(1), shape)

    # permute batch into the sharded layout
    psp = c * c * r
    positions = zz.make_positions(seq, psp, rt.st_cfg.seq_scheme)
    perm = positions.reshape(-1)
    batch_s = dict(batch_g)
    for k in batch_s:
        batch_s[k] = jnp.take(batch_s[k], perm, axis=1)

    out = jax.jit(island_fn)(params, batch_s)
    l_spmd, g_spmd = out if check_grads else (out, None)
    l_local = jax.jit(lambda p, b: model.loss(rt_local, p, b))(params, batch_g)
    err = abs(float(l_spmd) - float(l_local))
    assert err < tol, f"{arch}: spmd loss {l_spmd} vs local {l_local}"

    if check_grads:
        g_local = jax.jit(jax.grad(
            lambda p: model.loss(rt_local, p, batch_g)))(params)
        errs = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            g_spmd, g_local)
        leaves = np.array(jax.tree.leaves(errs))
        assert np.all(np.isfinite(leaves)), f"{arch}: NaN/inf in grads"
        worst = float(leaves.max())
        assert worst < (tol if grad_tol is None else grad_tol), (
            f"{arch}: grad mismatch {worst}: " + str(
                {k: v for k, v in jax.tree_util.tree_leaves_with_path(errs)
                 if v == worst}))
    return float(l_spmd)


def check_spmd_train_step(arch="h2o-danube-1.8b", c=2, data=2):
    """Full jitted train step on the refined mesh: runs, loss finite+decreases."""
    from repro.configs import registry
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.core import zigzag as zz
    from repro.dist import meshes
    from repro.models.factory import build_model
    from repro.optim import adamw
    from repro.train import step as train_step

    cfg = registry.get_smoke(arch)
    model = build_model(cfg)
    shape = ShapeConfig("test", seq_len=32, global_batch=2, kind="train")
    run_cfg = RunConfig(c=c, seq_scheme="zigzag")
    r = 8 // (data * c * c)
    mesh = meshes.local_mesh_for_tests(c=c, r=r, data=data)

    jstep, sh = train_step.build_train_step(
        model, mesh, run_cfg, shape,
        adamw.AdamWConfig(learning_rate=1e-2, warmup_steps=0))
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.init_state(params, adamw.AdamWConfig())
    batch_g = model.make_batch(jax.random.PRNGKey(1), shape)
    psp = c * c * r
    positions = zz.make_positions(shape.seq_len, psp, sh["rt"].st_cfg.seq_scheme)
    perm = positions.reshape(-1)
    batch_s = {k: jnp.take(v, perm, axis=1) for k, v in batch_g.items()}

    losses = []
    for _ in range(3):
        params, opt, metrics = jstep(params, opt, batch_s)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss not decreasing: {losses}"


CHECKS.update({
    "spmd_dense_swa": functools.partial(check_spmd_model, "h2o-danube-1.8b"),
    "spmd_dense_c1": functools.partial(check_spmd_model, "h2o-danube-1.8b",
                                       c=1),
    "spmd_moe": functools.partial(check_spmd_model, "phi3.5-moe-42b-a6.6b"),
    "spmd_hybrid": functools.partial(check_spmd_model, "jamba-1.5-large-398b",
                                     tol=5e-3),
    "spmd_vlm": functools.partial(check_spmd_model, "paligemma-3b"),
    # grad_tol 3e-2 abs: frontend_proj/embed grads are O(16) and accumulate
    # over vocab-parallel scatter transposes — f32 reassociation noise
    # (~1.5e-3 relative); the loss itself matches to 1e-6 so its bound
    # stays at the default
    "spmd_encdec": functools.partial(check_spmd_model,
                                     "seamless-m4t-large-v2", grad_tol=3e-2),
    "spmd_xlstm_runs": functools.partial(check_spmd_model, "xlstm-1.3b",
                                         tol=1e9, check_grads=False),
    "spmd_train_step": check_spmd_train_step,
})



def check_spmd_serve(arch="h2o-danube-1.8b", c=2, data=2, seq=32):
    """Decode + prefill steps lower and run on the refined mesh; decode
    matches the local-mode decode step."""
    from repro.configs import registry
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.dist import meshes
    from repro.models.factory import build_model
    from repro.serve import kv_cache, step as serve_step
    from repro.train import step as train_step

    cfg = registry.get_smoke(arch)
    model = build_model(cfg)
    shape = ShapeConfig("t", seq_len=seq, global_batch=2, kind="decode")
    run_cfg = RunConfig(c=c, seq_scheme="contiguous")
    r = 8 // (data * c * c)
    mesh = meshes.local_mesh_for_tests(c=c, r=r, data=data)

    params = model.init(jax.random.PRNGKey(0))
    cache = kv_cache.init_cache(cfg, 2, seq)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 1), 0,
                                cfg.vocab_size, jnp.int32)

    jdecode, info = serve_step.build_decode_step(model, mesh, run_cfg, shape)
    tok_s, cache_s = jdecode(params, cache, tokens)

    # local-mode reference decode
    import dataclasses as dc
    rt_local = dc.replace(
        train_step.make_runtime(model, run_cfg, shape, mode="local"),
        batch_axes=())
    if cfg.encdec:
        local_fn = lambda: serve_step.encdec_decode_step(
            rt_local, params, cache, tokens, cfg, seq - 1)
    else:
        local_fn = lambda: serve_step.lm_decode_step(
            rt_local, params, cache, tokens, cfg, seq - 1)
    tok_l, _ = jax.jit(local_fn)()
    assert np.array_equal(np.asarray(tok_s), np.asarray(tok_l)), (
        f"{arch}: decode tokens differ: {tok_s} vs {tok_l}")

    if not cfg.encdec:
        # prefill lowers and runs
        shape_p = ShapeConfig("t", seq_len=seq, global_batch=2, kind="prefill")
        jprefill, _ = serve_step.build_prefill_step(model, mesh, run_cfg, shape_p)
        batch = {k: v for k, v in model.make_batch(
            jax.random.PRNGKey(1), shape_p).items() if k != "labels"}
        tok0, cache0 = jprefill(params, batch)
        assert np.all(np.isfinite(np.asarray(tok0, np.float32)))


CHECKS.update({
    "serve_dense": functools.partial(check_spmd_serve, "h2o-danube-1.8b"),
    "serve_moe": functools.partial(check_spmd_serve, "phi3.5-moe-42b-a6.6b"),
    "serve_hybrid": functools.partial(check_spmd_serve, "jamba-1.5-large-398b"),
    "serve_xlstm": functools.partial(check_spmd_serve, "xlstm-1.3b"),
    "serve_encdec": functools.partial(check_spmd_serve, "seamless-m4t-large-v2"),
})


# ---------------------------------------------------------------------------
# serving engine (continuous batching + paged cache + vocab-parallel sampling)
# ---------------------------------------------------------------------------

def _tie_fixture():
    """(rt, cfg, mesh, x) for direct vocab-parallel head checks: logits for
    token v are exactly table[v, 0] (x is the first basis vector)."""
    from repro.configs.base import ModelConfig
    from repro.dist import meshes
    from repro.models.runtime import Runtime

    mesh = meshes.local_mesh_for_tests(c=2, r=2, data=1)  # sp = 8
    cfg = ModelConfig(name="tie", family="dense", num_layers=1, d_model=4,
                      num_heads=1, num_kv_heads=1, d_ff=8, vocab_size=64)
    st_cfg = st.StarTrailConfig(seq_len=8, axes=AXES, seq_scheme="contiguous")
    rt = Runtime(mode="spmd", st_cfg=st_cfg, batch_axes=())
    x = np.zeros((1, 1, 4), np.float32)
    x[0, 0, 0] = 1.0
    return rt, cfg, mesh, x


def check_greedy_tie():
    """vocab_parallel_greedy regression: an exact cross-shard logit tie must
    resolve to the lowest shard's candidate (= the smallest global token
    id), not to an averaged id that neither shard proposed."""
    from repro.serve import step as serve_step

    rt, cfg, mesh, x = _tie_fixture()
    table = np.zeros((64, 4), np.float32)
    table[:, 0] = -np.arange(64, dtype=np.float32) * 1e-3
    table[9, 0] = 5.0     # shard 1 (v_local = 8)
    table[17, 0] = 5.0    # shard 2 — exact tie
    fn = jax.jit(jax.shard_map(
        lambda t, x: serve_step.vocab_parallel_greedy(rt, {"table": t}, x, cfg),
        mesh=mesh, in_specs=(P(AXES, None), P(None, None, None)),
        out_specs=P(None, None), check_vma=False))
    tok = int(np.asarray(fn(table, x))[0, 0])
    assert tok == 9, f"cross-shard tie broke to {tok}, want token 9"
    # three-way tie including a same-shard pair -> still the smallest id
    table[11, 0] = 5.0
    tok = int(np.asarray(fn(table, x))[0, 0])
    assert tok == 9, f"three-way tie broke to {tok}, want token 9"


def check_engine_sampling():
    """Vocab-parallel sampling on the mesh: greedy == argmax; top-k/top-p
    samples stay inside the host-computed candidate sets; same key -> same
    token (determinism)."""
    from repro.engine import sampling as sampling_lib

    rt, cfg, mesh, x = _tie_fixture()
    rng = np.random.default_rng(0)
    table = np.zeros((64, 4), np.float32)
    table[:, 0] = rng.normal(size=64).astype(np.float32)
    full = table[:, 0].astype(np.float64)

    def run(temp, top_k, top_p, fold):
        fn = jax.jit(jax.shard_map(
            lambda t, x, keys: sampling_lib.sample(
                rt, {"table": t}, x, cfg,
                temperature=jnp.full((1,), temp, jnp.float32),
                top_k=jnp.full((1,), top_k, jnp.int32),
                top_p=jnp.full((1,), top_p, jnp.float32), keys=keys),
            mesh=mesh, in_specs=(P(AXES, None), P(None, None, None), P()),
            out_specs=P(None, None), check_vma=False))
        keys = np.asarray(jax.random.fold_in(jax.random.PRNGKey(0), fold))
        return int(np.asarray(fn(table, x, keys[None]))[0, 0])

    assert run(0.0, 0, 1.0, 0) == int(np.argmax(full)), "greedy != argmax"

    topk_set = set(np.argsort(full)[-5:].tolist())
    seen = set()
    for i in range(24):
        t = run(1.0, 5, 1.0, i)
        assert t in topk_set, f"top-k sample {t} outside top-5 {topk_set}"
        seen.add(t)
    assert len(seen) > 1, "top-k sampling degenerate (one token in 24 draws)"

    probs = np.exp(full - full.max())
    probs /= probs.sum()
    order = np.argsort(-probs)
    csum = np.cumsum(probs[order])
    nucleus = set(order[:int(np.searchsorted(csum, 0.5) + 1)].tolist())
    for i in range(24):
        t = run(1.0, 0, 0.5, i)
        assert t in nucleus, f"top-p sample {t} outside nucleus {nucleus}"

    assert run(0.7, 8, 0.9, 3) == run(0.7, 8, 0.9, 3), "sampling not deterministic"


def check_engine_mixed(arch="h2o-danube-1.8b"):
    """Acceptance: a mixed workload (8 requests, different prompt lengths,
    budgets, sampling settings, arriving over time) through the engine —
    decode compiles at most once per width bucket, replay adds no compiles,
    and every request's output is bit-identical to serving it alone."""
    from repro.engine import EngineConfig, Request, build_engine

    eng = build_engine(arch, smoke=True, c=2, data=1,
                       eng=EngineConfig(max_slots=4, page_size=4,
                                        pages_per_shard=32, max_len=128))
    rng = np.random.default_rng(1)
    vocab = eng.cfg.vocab_size
    reqs, arrivals = [], []
    for i in range(8):
        plen = int(rng.integers(2, 28))
        gen = int(rng.integers(2, 10))
        temp = 0.0 if i % 2 == 0 else 0.9
        reqs.append(Request(
            uid=f"r{i}", tokens=rng.integers(0, vocab, plen).tolist(),
            max_new_tokens=gen, temperature=temp, top_k=16 * (i % 3),
            top_p=[1.0, 0.9, 0.8][i % 3], seed=100 + i))
        arrivals.append(i)  # one new arrival per step

    def run_workload():
        pending = list(zip(arrivals, reqs))
        while pending or not eng.idle():
            step = eng.metrics.steps
            while pending and pending[0][0] <= step:
                eng.add_request(pending.pop(0)[1])
            eng.step()
        return eng.collect()

    mixed = run_workload()
    assert len(mixed) == 8 and all(
        len(mixed[r.uid]) == r.max_new_tokens for r in reqs)
    pc, dc = eng.metrics.prefill_compiles, eng.metrics.decode_compiles
    # once-per-bucket: each bucket fn must hold exactly one XLA trace
    # (xla_compiles counts traces, not dict misses — catches silent
    # retracing from operand dtype/sharding drift)
    assert eng.xla_compiles() == (len(eng._prefill_fns),
                                  len(eng._decode_fns)), (
        f"a bucket fn compiled more than once: {eng.xla_compiles()} traces "
        f"for {(len(eng._prefill_fns), len(eng._decode_fns))} buckets")

    # replay: every bucket is warm, zero new compiles
    eng.reset()
    replay = run_workload()
    assert replay == mixed, "replay of the same workload diverged"
    assert (eng.metrics.prefill_compiles, eng.metrics.decode_compiles) == \
        (pc, dc), "recompiled on replay"
    assert eng.xla_compiles() == (len(eng._prefill_fns),
                                  len(eng._decode_fns)), \
        "silent XLA retrace on replay"

    # solo: each request alone, bit-identical outputs
    for r in reqs:
        eng.reset()
        eng.add_request(r)
        solo = eng.run()
        assert solo[r.uid] == mixed[r.uid], (
            f"{r.uid}: batched {mixed[r.uid]} != solo {solo[r.uid]}")


def check_engine_moe(arch="phi3.5-moe-42b-a6.6b"):
    """The engine also serves MoE stacks (expert-parallel decode over the
    paged cache); outputs drain and replay deterministically. (MoE capacity
    couples tokens across the batch, so solo-vs-batched bit-equality is not
    asserted — see docs/SERVING.md.)"""
    from repro.engine import EngineConfig, Request, build_engine

    eng = build_engine(arch, smoke=True, c=1, data=1,
                       eng=EngineConfig(max_slots=2, page_size=4,
                                        pages_per_shard=16, max_len=64))
    rng = np.random.default_rng(2)
    vocab = eng.cfg.vocab_size
    reqs = [Request(uid=f"m{i}",
                    tokens=rng.integers(0, vocab, 5 + 3 * i).tolist(),
                    max_new_tokens=3 + i) for i in range(3)]
    for r in reqs:
        eng.add_request(r)
    out = eng.run()
    assert all(len(out[r.uid]) == r.max_new_tokens for r in reqs)
    eng.reset()
    for r in reqs:
        eng.add_request(r)
    assert eng.run() == out, "MoE engine replay nondeterministic"


def check_paged_decode_dist(c=2, p=8, hq=4, hkv=2, d=16, ps=4, w=3):
    """The Pallas paged-decode kernel inside a shard_map island on the
    refined mesh: partial (o, lse) per shard + lse-combine psum must match
    the dense full-cache oracle, with ragged per-row cache lengths and
    round-robin page ownership."""
    from repro.kernels import dispatch as kernels

    mesh = make_mesh(c, p)
    B = 3
    pages_loc = 8
    cache_max = w * p * ps
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (B, 1, hq, d))
    k_full = _rand(kk, (B, cache_max, hkv, d))
    v_full = _rand(kv, (B, cache_max, hkv, d))
    cache_len = jnp.array([cache_max - 1, ps + 1, 0], jnp.int32)[:B]

    # host-side round-robin paging of the dense cache: global block g lives
    # on shard g % p as that shard's g // p-th block; per-shard pools +
    # a (B, p, w) table with distinct local pages per (slot, block)
    pools_k = np.zeros((p, pages_loc, ps, hkv, d), np.float32)
    pools_v = np.zeros((p, pages_loc, ps, hkv, d), np.float32)
    table = np.full((B, p, w), -1, np.int32)
    next_free = [0] * p
    for b in range(B):
        blocks = int(cache_len[b]) // ps + 1
        for g in range(blocks):
            sh, j = g % p, g // p
            page = next_free[sh]
            next_free[sh] += 1
            table[b, sh, j] = page
            sl = slice(g * ps, (g + 1) * ps)
            pools_k[sh, page] = np.asarray(k_full[b, sl])
            pools_v[sh, page] = np.asarray(v_full[b, sl])

    spec_pool = P(AXES, None, None, None, None)

    def island(q, pool_k, pool_v, table, cache_len):
        gi = jax.lax.axis_index(AXES[0])
        ji = jax.lax.axis_index(AXES[1])
        ti = jax.lax.axis_index(AXES[2])
        r = p // (c * c)
        rank = (gi * r + ji) * c + ti
        tbl = jax.lax.dynamic_index_in_dim(table, rank, axis=1,
                                           keepdims=False)
        o_p, lse_p = kernels.paged_decode(
            q, pool_k[0], pool_v[0], tbl, cache_len, rank, sp=p,
            page_size=ps, impl="pallas")
        return st.combine_decode_partials(o_p, lse_p, AXES)

    fn = jax.jit(jax.shard_map(
        island, mesh=mesh,
        in_specs=(P(None, None, None, None), spec_pool, spec_pool,
                  P(None, None, None), P(None)),
        out_specs=P(None, None, None, None), check_vma=False))
    o = np.asarray(fn(q, jnp.asarray(pools_k), jnp.asarray(pools_v),
                      jnp.asarray(table), cache_len))

    pos = jnp.arange(cache_max, dtype=jnp.int32)
    for b in range(B):
        cl = int(cache_len[b])
        pos_k = jnp.where(pos <= cl, pos, cl + 1)
        o_ref, _ = ref_kernels.block_attention(
            q[b:b + 1], k_full[b:b + 1], v_full[b:b + 1],
            jnp.array([cl], jnp.int32), pos_k, causal=True)
        err = np.abs(o[b] - np.asarray(o_ref)[0]).max()
        assert err < 2e-4, f"paged decode row {b} err {err}"


def check_engine_paged_kernel(arch="h2o-danube-1.8b"):
    """Acceptance (paged-decode kernel): the engine under
    kernel_impl='pallas' (interpret mode on CPU) emits bit-identical tokens
    to the ref gather path for the same mixed workload, and holds the same
    once-per-bucket compile guarantee."""
    from repro.engine import EngineConfig, Request, build_engine

    rng = np.random.default_rng(3)
    outs = {}
    engines = {}
    for kern in ("ref", "pallas"):
        eng = build_engine(arch, smoke=True, c=2, data=1, kernel=kern,
                           eng=EngineConfig(max_slots=2, page_size=4,
                                            pages_per_shard=16, max_len=64))
        assert eng.kernel_impl == kern
        vocab = eng.cfg.vocab_size
        rng_w = np.random.default_rng(3)
        reqs = [Request(uid=f"p{i}",
                        tokens=rng_w.integers(0, vocab, 3 + 4 * i).tolist(),
                        max_new_tokens=2 + i, seed=50 + i)
                for i in range(3)]
        eng.add_request(reqs[0])
        eng.add_request(reqs[1])
        eng.step()
        eng.add_request(reqs[2])        # joins the running batch
        outs[kern] = eng.run()
        assert eng.xla_compiles() == (len(eng._prefill_fns),
                                      len(eng._decode_fns)), (
            f"{kern}: a bucket fn compiled more than once")
        engines[kern] = eng
    assert outs["pallas"] == outs["ref"], (
        f"paged-kernel tokens diverged from the ref path:\n"
        f"  ref:    {outs['ref']}\n  pallas: {outs['pallas']}")

    # replay on the warm pallas engine: zero new compiles
    eng = engines["pallas"]
    pc, dc = eng.metrics.prefill_compiles, eng.metrics.decode_compiles
    eng.reset()
    vocab = eng.cfg.vocab_size
    rng_w = np.random.default_rng(3)
    for i in range(3):
        eng.add_request(Request(
            uid=f"p{i}", tokens=rng_w.integers(0, vocab, 3 + 4 * i).tolist(),
            max_new_tokens=2 + i, seed=50 + i))
    assert eng.run() == outs["pallas"], "pallas replay diverged"
    assert (eng.metrics.prefill_compiles, eng.metrics.decode_compiles) == \
        (pc, dc), "pallas engine recompiled on replay"


def check_gateway_prefix_cow(arch="h2o-danube-1.8b"):
    """Acceptance (gateway prefix cache, C=2 mesh): two requests sharing a
    long prefix then diverging produce exactly the tokens of solo
    cold-cache runs — the second request's prefill reads the first's pages
    in place (copy-on-write sharing, >0 hit rate) — and dropping the
    shared prefix from the cache *while a request is live* never corrupts
    it (ref counts keep the pages alive until the request finishes)."""
    from repro.engine import EngineConfig, Request
    from repro.gateway import build_gateway

    eng_cfg = EngineConfig(max_slots=2, page_size=4, pages_per_shard=16,
                           max_len=64)
    gw = build_gateway(arch, smoke=True, c=2, data=1, replicas=1,
                       prefix_cache=True, eng=eng_cfg)
    assert gw.engines[0].sp == 8 and gw.plan.c == 2
    rng = np.random.default_rng(11)
    vocab = gw.cfg.vocab_size
    shared = rng.integers(0, vocab, 16).tolist()
    req_a = Request("a", shared + rng.integers(0, vocab, 5).tolist(), 4,
                    seed=1)
    req_b = Request("b", shared + rng.integers(0, vocab, 7).tolist(), 4,
                    seed=2)

    # --- shared-prefix serving: A cold, B hits A's pages
    gw.add_request(req_a)
    gw.step()                                     # A prefilled + registered
    gw.add_request(req_b)
    out = gw.run()
    m = gw.engines[0].metrics
    assert m.prefill_tokens_cached == 16, (
        f"B should reuse A's 16 shared-prefix tokens, cached="
        f"{m.prefill_tokens_cached}")
    # shared blocks resolved to the SAME physical pages for both slots
    cache = gw.engines[0].prefix_cache
    assert cache.hit_tokens == 16 and cache.hit_rate > 0

    # --- solo cold-cache references
    cold = build_gateway(arch, smoke=True, c=2, data=1, replicas=1,
                         prefix_cache=False, eng=eng_cfg)
    for r in (req_a, req_b):
        cold.reset()
        cold.add_request(r)
        solo = cold.run()
        assert solo[r.uid] == out[r.uid], (
            f"{r.uid}: cached {out[r.uid]} != solo cold {solo[r.uid]}")

    # --- evict the shared prefix while a sharing request is LIVE
    gw.reset()
    gw.add_request(req_a)
    gw.step()
    gw.add_request(req_b)
    gw.step()                                     # B admitted, sharing pages
    live_cached = gw.engines[0].scheduler.active()
    assert any(s.cached_len for s in live_cached), "B should be a live hit"
    cache = gw.engines[0].prefix_cache
    cache.drop_all()                              # cache lets go of *all*
    #                                               holds; B still refs them
    assert gw.engines[0].scheduler.pool.pages_in_use() > 0
    out2 = gw.run()
    assert out2 == out, (
        f"evicting the shared prefix under a live request corrupted it:\n"
        f"  before: {out}\n  after:  {out2}")
    # prefix gone from the trie: a re-arrival misses but stays correct
    gw.add_request(Request("a2", req_a.tokens, 4, seed=1))
    pre = gw.engines[0].metrics.prefill_tokens_cached
    out3 = gw.run()
    assert gw.engines[0].metrics.prefill_tokens_cached == pre, \
        "dropped prefix should not hit"
    assert out3["a2"] == out["a"], "post-eviction cold rerun diverged"

    # --- pool-pressure eviction: a tiny pool (2 pages/shard) fills with
    # retained prompt blocks; fresh admissions must reclaim cache-only
    # pages (leaf-first LRU) and serving proceeds
    gp = build_gateway(arch, smoke=True, c=2, data=1, replicas=1,
                       prefix_cache=True,
                       eng=EngineConfig(max_slots=2, page_size=4,
                                        pages_per_shard=2, max_len=64))
    filler = [Request(f"f{i}", rng.integers(0, vocab, 9).tolist(), 1,
                      seed=10 + i) for i in range(4)]
    for r in filler:                              # retained after finish
        gp.add_request(r)
    out_f = gp.run()
    big = Request("big", rng.integers(0, vocab, 24).tolist(), 8, seed=99)
    gp.add_request(big)
    out4 = gp.run()
    assert len(out4["big"]) == 8 and all(
        len(out_f[r.uid]) == 1 for r in filler)
    assert gp.engines[0].prefix_cache.evicted_pages > 0, \
        "pool pressure should have evicted cache-only pages"

    # --- host tier: the same kind of pool-pressure eviction, but with a
    # pinned-host tier attached the evicted family prefix is *demoted*
    # (spilled) instead of destroyed — a re-arrival reloads its KV from
    # host into fresh pool pages and the stream stays bit-identical to a
    # big-pool serve that never evicted anything
    rng = np.random.default_rng(17)
    fam = rng.integers(0, vocab, 16).tolist()
    first = Request("h0", fam + rng.integers(0, vocab, 3).tolist(), 3,
                    seed=21)
    # deep filler chains (24 tokens > the 16-token family) so cost-aware
    # eviction unwinds the *family* chain, spilling it block by block
    evictors = [Request(f"e{i}", rng.integers(0, vocab, 24).tolist(), 1,
                        seed=30 + i) for i in range(2)]
    again = Request("h1", fam + rng.integers(0, vocab, 5).tolist(), 3,
                    seed=22)

    def serve_pressure(gw):
        out = {}
        gw.add_request(first)
        out.update(gw.run())                      # family registered
        for r in evictors:
            gw.add_request(r)
            out.update(gw.run())                  # family evicted/spilled
        gw.add_request(again)
        out.update(gw.run())                      # re-arrival: host reload
        return out

    ref_out = serve_pressure(build_gateway(        # 16 pages/shard: roomy,
        arch, smoke=True, c=2, data=1, replicas=1,  # nothing ever evicts
        prefix_cache=True, eng=eng_cfg))
    tiny = EngineConfig(max_slots=2, page_size=4, pages_per_shard=2,
                        max_len=64)
    gwt = build_gateway(arch, smoke=True, c=2, data=1, replicas=1,
                        prefix_cache=True, host_tier_bytes=64 << 20,
                        eng=tiny)
    tier_out = serve_pressure(gwt)
    assert tier_out == ref_out, (
        f"host-tier reload diverged from the never-evicted serve:\n"
        f"  ref:  {ref_out}\n  tier: {tier_out}")
    tier = gwt.stats()["host_tier"]
    assert tier["spill_pages"] >= 4, tier         # the 4 family blocks
    assert tier["reload_pages"] >= 4, tier
    assert tier["hit_tokens"] >= 16 and tier["hit_rate"] > 0, tier
    # both transfer islands (read + write) compiled exactly once
    assert gwt.engines[0].transfer_xla_compiles() <= 2, \
        "transfer bucket recompiled"
    # same pressure without the tier: same tokens, but the re-arrival pays
    # full recompute (no host hits) — the tier's win is the avoided prefill
    gwo = build_gateway(arch, smoke=True, c=2, data=1, replicas=1,
                        prefix_cache=True, eng=tiny)
    off_out = serve_pressure(gwo)
    assert off_out == ref_out, "tier-off pressure serve diverged"
    assert gwo.stats()["host_tier"]["hit_tokens"] == 0


def check_gateway_replicas(arch="h2o-danube-1.8b"):
    """Acceptance (multi-replica gateway): 2 engine replicas on disjoint
    4-device C=2 submeshes; prefix-aware routing sends shared-prefix
    traffic to the replica holding the pages, session affinity pins
    sessions, and every request's tokens are bit-identical to a solo
    cold-cache run on the same replica mesh."""
    from repro.engine import EngineConfig, Request
    from repro.gateway import build_gateway

    eng_cfg = EngineConfig(max_slots=2, page_size=4, pages_per_shard=32,
                           max_len=64)
    gw = build_gateway(arch, smoke=True, c=2, data=1, replicas=2,
                       prefix_cache=True, eng=eng_cfg)
    assert len(gw.engines) == 2 and gw.plan.n_devices == 4
    assert gw.engines[0].mesh.devices.ravel()[0] != \
        gw.engines[1].mesh.devices.ravel()[0]
    rng = np.random.default_rng(5)
    vocab = gw.cfg.vocab_size
    shared = rng.integers(0, vocab, 12).tolist()
    reqs = {
        "s0": Request("s0", shared + rng.integers(0, vocab, 3).tolist(), 3,
                      seed=1),
        "s1": Request("s1", shared + rng.integers(0, vocab, 5).tolist(), 3,
                      seed=2),
        "u0": Request("u0", rng.integers(0, vocab, 14).tolist(), 3, seed=3),
        "aff": Request("aff", rng.integers(0, vocab, 9).tolist(), 3, seed=4),
    }
    r0 = gw.add_request(reqs["s0"])
    gw.step()                                     # s0 registered on r0
    assert gw.add_request(reqs["s1"]) == r0, \
        "prefix-aware routing should follow s0's cached pages"
    assert gw.add_request(reqs["u0"]) != r0, \
        "load-aware routing should spread cold traffic"
    gw.add_request(reqs["aff"], session="sess")
    out = gw.run()
    aff_replica = gw._owner["aff"]
    late = Request("aff2", reqs["aff"].tokens, 3, seed=4)
    assert gw.add_request(late, session="sess") == aff_replica, \
        "session affinity should pin the replica"
    out.update(gw.run())
    assert out["aff2"] == out["aff"], "affinity rerun diverged"
    m = gw.metrics_dict()
    assert m["prefix_hit_rate"] > 0 and m["prefill_tokens_cached"] >= 12
    assert m["affinity_hits"] == 1 and sorted(m["routed"])[-1] >= 2

    # solo cold-cache runs, pinned to the replica that served each request
    cold = build_gateway(arch, smoke=True, c=2, data=1, replicas=2,
                         prefix_cache=False, eng=eng_cfg)
    for uid, r in reqs.items():
        cold.reset()
        cold.add_request(r, replica=gw._owner[uid])
        solo = cold.run()
        assert solo[uid] == out[uid], (
            f"{uid}: gateway {out[uid]} != solo cold {solo[uid]}")


def check_gateway_disagg(arch="h2o-danube-1.8b"):
    """Acceptance (disaggregated prefill/decode): one prefill-role and one
    decode-role replica on disjoint 4-device C=2 submeshes. Prompts enter
    the prefill replica only, run prefill + the first sampled token, then
    the prompt KV hands off through the connector (device -> host ->
    device) and decode resumes on the decode replica — every stream
    bit-identical to a unified gateway on an identical 4-device mesh, and
    the decode replica never prefills a raw prompt."""
    from repro.configs import registry as arch_registry
    from repro.engine import EngineConfig, Request
    from repro.gateway import build_gateway
    from repro.plan import make_serve_plan

    eng_cfg = EngineConfig(max_slots=2, page_size=4, pages_per_shard=16,
                           max_len=64)
    gw = build_gateway(arch, smoke=True, c=2, data=1,
                       roles=["prefill", "decode"], prefix_cache=True,
                       eng=eng_cfg)
    assert gw.roles == ["prefill", "decode"]
    assert all(p.n_devices == 4 and p.c == 2 for p in gw.plans)
    assert set(gw.engines[0].mesh.devices.ravel()).isdisjoint(
        gw.engines[1].mesh.devices.ravel())

    rng = np.random.default_rng(7)
    vocab = gw.cfg.vocab_size
    reqs = [
        Request("g", rng.integers(0, vocab, 11).tolist(), 4, seed=1),
        Request("s", rng.integers(0, vocab, 17).tolist(), 5,
                temperature=0.8, top_k=8, top_p=0.9, seed=3),
        Request("one", rng.integers(0, vocab, 5).tolist(), 1, seed=4),
        Request("g2", rng.integers(0, vocab, 6).tolist(), 3, seed=5),
    ]
    owners = [gw.add_request(r) for r in reqs]
    assert owners == [0] * 4, "new requests must enter the prefill replica"
    out = gw.run()
    assert all(len(out[r.uid]) == r.max_new_tokens for r in reqs)
    # 'one' finished inside its prefill (budget 1): no handoff for it
    assert gw.handoffs == 3, gw.handoffs
    tier = gw.stats()["host_tier"]
    assert tier["handoff_out_pages"] == tier["handoff_in_pages"] > 0, tier
    assert gw.engines[1].metrics.prefills == 0, \
        "decode replica must never see a raw prompt"
    assert gw.engines[1].metrics.decode_steps > 0

    # unified baseline on an identical 4-device C=2 mesh
    cfg = arch_registry.get_smoke(arch)
    uplan = make_serve_plan(cfg, arch=arch, n_devices=4, c=2,
                            decode_batch=2, page_size=4, max_len=64,
                            mesh_kind="local", prefix_cache=True)
    uni = build_gateway(arch, smoke=True, eng=eng_cfg, plan=uplan)
    for r in reqs:
        uni.add_request(r)
    ref = uni.run()
    assert out == ref, (
        f"disaggregated streams diverged from the unified gateway:\n"
        f"  unified: {ref}\n  disagg:  {out}")

    # replay on the warm disaggregated gateway: same tokens, no recompiles
    compiles = [(e.metrics.prefill_compiles, e.metrics.decode_compiles,
                 e.transfer_xla_compiles()) for e in gw.engines]
    gw.reset()
    for r in reqs:
        gw.add_request(r)
    assert gw.run() == out, "disagg replay diverged"
    assert [(e.metrics.prefill_compiles, e.metrics.decode_compiles,
             e.transfer_xla_compiles()) for e in gw.engines] == compiles, \
        "disaggregated gateway recompiled on replay"


def check_chunked_prefill_dist(arch="h2o-danube-1.8b"):
    """Acceptance (chunked prefill, C=2 mesh): splitting long prompts into
    bucket-aligned chunks across driver steps emits bit-identical tokens
    to monolithic prefill on the SP-sharded paged pool. Both engines run
    kernel_impl='pallas' (interpret mode on CPU), so every suffix chunk
    exercises the Pallas paged-prefill kernel against the sharded page
    table and the dense chunk partial runs the ragged/flash kernels — with
    zero pallas->ref fallbacks; a replay on the warm chunked engine must
    add no compiles."""
    from repro.engine import EngineConfig, Request, build_engine

    common = dict(max_slots=2, page_size=4, pages_per_shard=16, max_len=64)

    def workload(vocab):
        rng = np.random.default_rng(5)
        return [
            Request(uid="long", tokens=rng.integers(0, vocab, 23).tolist(),
                    max_new_tokens=3, seed=1),
            Request(uid="short", tokens=rng.integers(0, vocab, 5).tolist(),
                    max_new_tokens=4, temperature=0.8, top_k=8, top_p=0.9,
                    seed=2),
            Request(uid="mid", tokens=rng.integers(0, vocab, 13).tolist(),
                    max_new_tokens=2, seed=3),
        ]

    outs = {}
    engines = {}
    params = None
    for mode, chunk in (("mono", 0), ("chunked", 8)):
        eng = build_engine(arch, smoke=True, c=2, data=1, kernel="pallas",
                           eng=EngineConfig(prefill_chunk=chunk, **common),
                           params=params)
        params = eng.params
        reqs = workload(eng.cfg.vocab_size)
        eng.add_request(reqs[0])
        eng.add_request(reqs[1])
        eng.step()
        eng.add_request(reqs[2])            # joins mid-stream
        outs[mode] = eng.run()
        assert eng.pallas_fallbacks() == {}, (
            f"{mode}: pallas->ref fallbacks traced: "
            f"{eng.pallas_fallbacks()}")
        engines[mode] = eng
    assert engines["chunked"].metrics.prefill_chunks > \
        engines["chunked"].metrics.prefills, "long prompts did not chunk"
    assert outs["chunked"] == outs["mono"], (
        f"chunked tokens diverged from monolithic prefill:\n"
        f"  mono:    {outs['mono']}\n  chunked: {outs['chunked']}")

    eng = engines["chunked"]
    pc, dc = eng.metrics.prefill_compiles, eng.metrics.decode_compiles
    eng.reset()
    reqs = workload(eng.cfg.vocab_size)
    eng.add_request(reqs[0])
    eng.add_request(reqs[1])
    eng.step()
    eng.add_request(reqs[2])
    assert eng.run() == outs["chunked"], "chunked replay diverged"
    assert (eng.metrics.prefill_compiles, eng.metrics.decode_compiles) == \
        (pc, dc), "chunked engine recompiled on replay"


CHECKS.update({
    "greedy_tie": check_greedy_tie,
    "engine_sampling": check_engine_sampling,
    "engine_mixed": check_engine_mixed,
    "engine_moe": check_engine_moe,
    "paged_decode_dist": check_paged_decode_dist,
    "engine_paged_kernel": check_engine_paged_kernel,
    "gateway_prefix_cow": check_gateway_prefix_cow,
    "gateway_replicas": check_gateway_replicas,
    "gateway_disagg": check_gateway_disagg,
    "chunked_prefill_dist": check_chunked_prefill_dist,
})


# ---------------------------------------------------------------------------
# plan layer: microbatched grad accumulation, scheme cross-checks, plans
# ---------------------------------------------------------------------------

def _gqa_smoke_cfg(arch="h2o-danube-1.8b", hq=8, hkv=4):
    """Smoke config with head counts Ulysses can shard at SP=4. f32 params
    so cross-scheme deltas measure reassociation, not bf16 rounding."""
    import dataclasses as dc

    from repro.configs import registry

    return dc.replace(registry.get_smoke(arch), num_heads=hq,
                      num_kv_heads=hkv, param_dtype="float32")


def check_microbatch_equiv(arch="h2o-danube-1.8b", c=2, data=2, seq=64,
                           batch=8, tol=5e-5):
    """Gradient accumulation is bit-consistent: for a fixed global batch,
    loss/grads with microbatches=4 match microbatches=1 within f32
    accumulation tolerance (acceptance criterion)."""
    import dataclasses as dc

    from repro.configs import registry
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.core import zigzag as zz
    from repro.dist import meshes
    from repro.models.factory import build_model
    from repro.train import step as train_step

    # f32 params: the mb=4-vs-mb=1 delta must be pure f32 reassociation
    # noise, not per-microbatch bf16 rounding
    cfg = dc.replace(registry.get_smoke(arch), param_dtype="float32")
    model = build_model(cfg)
    shape = ShapeConfig("test", seq_len=seq, global_batch=batch, kind="train")
    r = 8 // (data * c * c)
    mesh = meshes.local_mesh_for_tests(c=c, r=r, data=data)

    run1 = RunConfig(c=c, seq_scheme="zigzag", microbatches=1)
    run4 = dc.replace(run1, microbatches=4)
    vg1, rt = train_step.build_value_and_grad_fn(model, mesh, run1, shape)
    vg4, _ = train_step.build_value_and_grad_fn(model, mesh, run4, shape)

    params = model.init(jax.random.PRNGKey(0))
    batch_g = model.make_batch(jax.random.PRNGKey(1), shape)
    psp = c * c * r
    perm = zz.make_positions(seq, psp, rt.st_cfg.seq_scheme).reshape(-1)
    batch_s = {k: jnp.take(v, perm, axis=1) for k, v in batch_g.items()}

    l1, g1 = jax.jit(vg1)(params, batch_s)
    l4, g4 = jax.jit(vg4)(params, batch_s)
    lerr = abs(float(l1) - float(l4))
    assert lerr < tol, f"loss mb=1 {l1} vs mb=4 {l4} (err {lerr})"
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), g1, g4)
    worst = max(jax.tree.leaves(errs))
    assert worst < tol, f"grad mismatch mb=1 vs mb=4: {worst}"


# ring / StarTrail / Ulysses compute the same attention in a different
# order of f32 partial combines; their losses agree to this (absolute)
SCHEME_LOSS_TOL = 2e-3


def check_scheme_crosscheck(data=2, seq=64, batch=4, tol=SCHEME_LOSS_TOL):
    """ulysses vs startrail vs C=1 ring: losses and grads agree on the
    8-device smoke mesh for a GQA config (satellite acceptance)."""
    from repro.configs.base import ShapeConfig
    from repro.core import zigzag as zz
    from repro.models.factory import build_model
    from repro.plan import make_plan
    from repro.train import step as train_step

    cfg = _gqa_smoke_cfg()
    model = build_model(cfg)
    shape = ShapeConfig("test", seq_len=seq, global_batch=batch, kind="train")

    results = {}
    for scheme, c in (("ring", 1), ("startrail", 2), ("ulysses", 1)):
        plan = make_plan(cfg, shape, arch="gqa-test", n_devices=8, data=data,
                         scheme=scheme, c=c, mesh_kind="local")
        mesh = plan.build_mesh()
        vg, rt = train_step.build_value_and_grad_fn(
            model, mesh, plan.run_config(), shape)
        params = model.init(jax.random.PRNGKey(0))
        batch_g = model.make_batch(jax.random.PRNGKey(1), shape)
        perm = zz.make_positions(seq, plan.sp_size,
                                 rt.st_cfg.seq_scheme).reshape(-1)
        batch_s = {k: jnp.take(v, perm, axis=1) for k, v in batch_g.items()}
        loss, grads = jax.jit(vg)(params, batch_s)
        results[scheme] = (float(loss), grads)

    l_ring, g_ring = results["ring"]
    for scheme in ("startrail", "ulysses"):
        l, g = results[scheme]
        assert abs(l - l_ring) < tol, (
            f"{scheme} loss {l} vs ring {l_ring}")
        errs = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))), g, g_ring)
        worst = max(jax.tree.leaves(errs))
        assert worst < tol, f"{scheme} grads vs ring: {worst}"


def check_ulysses_rejected():
    """Ulysses raises cleanly for the kv=1 (paligemma) config: at the plan
    layer (cost model) and at trace time in core/ulysses.py."""
    from repro.configs import registry
    from repro.configs.base import ShapeConfig
    from repro.plan import cost as plan_cost, make_plan

    cfg = registry.get_smoke("paligemma-3b")   # kv=1
    shape = ShapeConfig("test", seq_len=64, global_batch=4, kind="train")
    for fn in (lambda: plan_cost.check_scheme(cfg, 8, "ulysses"),
               lambda: make_plan(cfg, shape, n_devices=8, data=1,
                                 scheme="ulysses", mesh_kind="local")):
        try:
            fn()
        except ValueError as e:
            assert "head counts divisible" in str(e), e
        else:
            raise AssertionError("ulysses not rejected for kv=1 at plan level")

    # trace-time guard in core/ulysses.py (existing behaviour, kept)
    mesh = make_mesh(1, 8)
    cfg_st = st.StarTrailConfig(seq_len=32, axes=AXES,
                                seq_scheme="contiguous", causal=True)
    q = _rand(jax.random.PRNGKey(0), (1, 32, 4, 8))
    kv = _rand(jax.random.PRNGKey(1), (1, 32, 1, 8))
    spec = P(None, AXES, None, None)
    try:
        jax.jit(jax.shard_map(
            lambda q, k, v: ulysses_lib.ulysses_attention(q, k, v, cfg_st),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False)).lower(q, kv, kv)
    except ValueError as e:
        assert "head counts divisible" in str(e), e
    else:
        raise AssertionError("core ulysses did not raise for kv=1")


def check_plan_constructs():
    """Every emitted ExecutionPlan actually constructs: for each assigned
    arch, the cost-model plan's mesh refines and the train step lowers on
    the smoke mesh (microbatched for even per-device batches)."""
    from repro.configs import registry
    from repro.configs.base import ShapeConfig
    from repro.models.factory import build_model
    from repro.optim import adamw
    from repro.plan import make_plan

    shape = ShapeConfig("test", seq_len=64, global_batch=4, kind="train")
    meshes_built = {}
    for arch in registry.ASSIGNED_ARCHS:
        cfg = registry.get_smoke(arch)
        model = build_model(cfg)
        plan = make_plan(cfg, shape, arch=arch, n_devices=8, data=2,
                         microbatches=2, mesh_kind="local")
        assert plan.sp_size == 4 and plan.c * plan.c * plan.r == 4, plan
        key = (plan.c, plan.r, plan.data)
        if key not in meshes_built:
            meshes_built[key] = plan.build_mesh()
        adam_cfg = adamw.AdamWConfig(state_dtype=cfg.opt_dtype)
        jstep, _ = plan.build_train_step(model, adam_cfg,
                                         mesh=meshes_built[key])
        params = model.abstract()
        opt = adamw.abstract_state(params, adam_cfg)
        batch = model.input_specs(shape)
        jstep.lower(params, opt, batch)   # traces the whole island
        print(f"  plan_constructs: {arch} scheme={plan.scheme} c={plan.c} "
              f"r={plan.r} lowered", flush=True)


def check_commlog_c2(arch="h2o-danube-1.8b", seq=64):
    """obs.commlog on the C=2 smoke mesh: the compiled attention island's
    HLO collectives match the eq. 2-4 analytical wire volumes per kind
    (within the 5% gate — exactly 1.0 here), and ``CommLog.record_step``
    ticks the registry counters by precisely per_step bytes x steps."""
    from repro import obs
    from repro.configs import registry as arch_registry
    from repro.obs import commlog
    from repro.plan import cost as plan_cost
    from repro.plan import make_serve_plan

    cfg = arch_registry.get_smoke(arch)
    plan = make_serve_plan(cfg, arch=arch, n_devices=8, data=1, c=2,
                           mesh_kind="local")
    assert plan.c == 2 and plan.sp_size == 8, plan

    rep = commlog.comm_report(cfg, plan, seq_len=seq)
    assert rep["within_tolerance"], rep["per_collective"]
    live = [k for k, row in rep["per_collective"].items()
            if row["analytical_bytes"]]
    # C=2 exercises every paper term except Ulysses' all-to-all
    assert set(live) == {"all-gather", "collective-permute",
                         "reduce-scatter"}, rep["per_collective"]
    for kind in live:
        row = rep["per_collective"][kind]
        assert abs(row["ratio"] - 1.0) <= rep["tolerance"], (kind, row)
    print(f"  commlog_c2: ratios "
          f"{ {k: rep['per_collective'][k]['ratio'] for k in live} }",
          flush=True)

    # CommLog prices per-layer volumes x layers x fwd+bwd multiplier and
    # ticks the counters by exactly that per step
    reg = obs.Registry()
    log = commlog.CommLog(reg, cfg, plan, batch=2)
    per_layer = commlog.analytical_wire_volumes(cfg, plan, batch=2)
    mult = plan_cost.num_attention_layers(cfg) * log.TRAIN_STEP_MULTIPLIER
    assert log.per_step == {k: v * mult for k, v in per_layer.items()}
    steps = 3
    for _ in range(steps):
        log.record_step()
    counter = reg.get("comm_bytes_total")
    for kind, v in log.per_step.items():
        if v:
            assert counter.value(collective=kind) == v * steps, kind
    assert reg.value("comm_steps_total") == steps


def check_pipelined_bitexact(c=2, p=8, seq=64, batch=2, hq=4, hkv=2, d=8):
    """Acceptance (pipelined ring): the double-buffered scan (permute
    issued before the block kernel) and chunked ring transfers are
    *bit-identical* — np.array_equal on loss and every grad, bf16 inputs —
    to the sequential compute-then-permute baseline on the C=2 smoke mesh.
    Also covers the windowed block_skip path (where whole ring steps are
    skipped, the prefetched pack must still circulate identically)."""
    mesh = make_mesh(c, p)
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    spec = P(None, AXES, None, None)

    def run(pipeline, comm_chunks, *, scheme="zigzag", window=None,
            block_skip=False):
        cfg = st.StarTrailConfig(
            seq_len=seq, axes=AXES, seq_scheme=scheme, causal=True,
            window=window, block_skip=block_skip,
            pipeline=pipeline, comm_chunks=comm_chunks)
        dist = jax.jit(jax.shard_map(
            lambda q, k, v: st.startrail_attention(q, k, v, cfg),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False))

        def loss(q, k, v):
            return (dist(q, k, v).astype(jnp.float32) ** 2).sum()

        q = _rand(kq, (batch, seq, hq, d), jnp.bfloat16)
        k = _rand(kk, (batch, seq, hkv, d), jnp.bfloat16)
        v = _rand(kv, (batch, seq, hkv, d), jnp.bfloat16)
        l, g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
        return np.asarray(l), [np.asarray(x) for x in g]

    l0, g0 = run(False, 1)
    for pipe, cc in ((True, 1), (True, 2), (True, 4), (False, 2)):
        l1, g1 = run(pipe, cc)
        assert np.array_equal(l0, l1), (
            f"loss differs pipeline={pipe} cc={cc}: {l0} vs {l1}")
        for name, a, b in zip("qkv", g0, g1):
            assert np.array_equal(a, b), (
                f"d{name} not bit-identical pipeline={pipe} cc={cc}")

    lw0, gw0 = run(False, 1, scheme="contiguous", window=16, block_skip=True)
    lw1, gw1 = run(True, 2, scheme="contiguous", window=16, block_skip=True)
    assert np.array_equal(lw0, lw1), "windowed skip loss differs pipelined"
    for name, a, b in zip("qkv", gw0, gw1):
        assert np.array_equal(a, b), (
            f"windowed skip d{name} not bit-identical pipelined")


def check_bwd_skip_equiv(c=2, p=8, seq=64, batch=2, hq=4, hkv=2, d=8,
                         window=16, tol=2e-5):
    """block_skip over the backward ring scan: grads with dead-block
    skipping == grads without, f32 tolerance, on the windowed contiguous
    layout where whole (Q-chunk, K-chunk) ring steps fall outside the
    attention window."""
    mesh = make_mesh(c, p)
    key = jax.random.PRNGKey(4)
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (batch, seq, hq, d))
    k = _rand(kk, (batch, seq, hkv, d))
    v = _rand(kv, (batch, seq, hkv, d))
    spec = P(None, AXES, None, None)

    def run(block_skip):
        cfg = st.StarTrailConfig(
            seq_len=seq, axes=AXES, seq_scheme="contiguous", causal=True,
            window=window, block_skip=block_skip)
        dist = jax.jit(jax.shard_map(
            lambda q, k, v: st.startrail_attention(q, k, v, cfg),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False))

        def loss(q, k, v):
            return (dist(q, k, v).astype(jnp.float32) ** 2).sum()

        l, g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
        return float(l), [np.asarray(x) for x in g]

    l_skip, g_skip = run(True)
    l_full, g_full = run(False)
    # the loss sums ~4k squared terms: bound it relatively (reassociation)
    assert abs(l_skip - l_full) < 1e-6 * max(abs(l_full), 1.0), (
        f"loss skip {l_skip} vs {l_full}")
    for name, a, b in zip("qkv", g_skip, g_full):
        e = np.abs(a - b).max()
        assert e < tol, f"d{name} skip-vs-full err {e}"


def check_init_sharded(arch="h2o-danube-1.8b", seq=64):
    """Params created in their sharded layout (``Model.init_sharded``, as
    the trainer and the engine do) equal ``model.init`` of the same seed,
    bit for bit, on the C=2 data=2 smoke mesh."""
    from repro.configs import registry as arch_registry
    from repro.configs.base import ShapeConfig
    from repro.models.factory import build_model
    from repro.optim import adamw
    from repro.plan import make_plan

    cfg = arch_registry.get_smoke(arch)
    model = build_model(cfg)
    shape = ShapeConfig("test", seq_len=seq, global_batch=4, kind="train")
    plan = make_plan(cfg, shape, arch=arch, n_devices=8, data=2, c=2)
    _, sh = plan.build_train_step(model, adamw.AdamWConfig())
    for seed in (0, 3):
        want = model.init(jax.random.PRNGKey(seed))
        got = model.init_sharded(sh["params"], seed)
        for a, b, s in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                           jax.tree.leaves(sh["params"])):
            assert a.sharding == s, (a.sharding, s)
            assert np.array_equal(np.asarray(a), np.asarray(b)), seed
    print(f"  init_sharded: {len(jax.tree.leaves(want))} leaves equal",
          flush=True)


CHECKS.update({
    "init_sharded": check_init_sharded,
    "microbatch_equiv": check_microbatch_equiv,
    "scheme_crosscheck": check_scheme_crosscheck,
    "ulysses_rejected": check_ulysses_rejected,
    "plan_constructs": check_plan_constructs,
    "commlog_c2": check_commlog_c2,
    "pipelined_bitexact": check_pipelined_bitexact,
    "bwd_skip_equiv": check_bwd_skip_equiv,
})

if __name__ == "__main__":
    main(sys.argv[1:])
