"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a v5e and
the TPU compiler, which is installed with jax, lowers each kernel for it
(``interpret=False``). This catches what interpret mode cannot — block
shapes the TPU lowering refuses, unsupported vector ops, VMEM overflow —
at the widths of h2o-danube-1.8b (Hq=32, Hkv=8, D=80, window 4096) plus one
D=128 case. Nothing runs, so nothing here says anything about results or
speed.

The topology is described only inside the module-scoped fixture below:
describing it loads the TPU library, which one process at a time may hold,
so it must never happen while a module is imported. Keep these tests in
this one file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import paged_decode, paged_prefill

HQ, HKV, D, WINDOW = 32, 8, 80, 4096     # h2o-danube-1.8b
POOL_D = 128                             # D padded to whole lane tiles
PAGE, PAGES = 16, 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip cannot read back what it writes to the persistent
    # cache, so keep these compiles out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _fwd(merge, ragged, d=D):
    B, S = (2, 1024) if ragged else (1, 2048)
    pos = (B, S) if ragged else (S,)
    args = [((B, S, HQ, d), jnp.bfloat16), ((B, S, HKV, d), jnp.bfloat16),
            ((B, S, HKV, d), jnp.bfloat16), (pos, jnp.int32),
            (pos, jnp.int32)]
    if merge:
        args += [((B, S, HQ, d), jnp.float32), ((B, HQ, S), jnp.float32)]

    def fn(q, k, v, pq, pk, *acc):
        return fa.flash_attention_fwd(q, k, v, pq, pk, *acc, causal=True,
                                      window=WINDOW, interpret=False)
    return fn, args


def _bwd(ragged):
    B, S = (2, 1024) if ragged else (1, 2048)
    pos = (B, S) if ragged else (S,)
    args = [((B, S, HQ, D), jnp.bfloat16), ((B, S, HKV, D), jnp.bfloat16),
            ((B, S, HKV, D), jnp.bfloat16), ((B, S, HQ, D), jnp.bfloat16),
            ((B, HQ, S), jnp.float32), ((B, HQ, S), jnp.float32),
            (pos, jnp.int32), (pos, jnp.int32)]

    def fn(q, k, v, do, lse, delta, pq, pk):
        return fa.flash_attention_bwd(q, k, v, do, lse, delta, pq, pk,
                                      causal=True, window=WINDOW,
                                      interpret=False)
    return fn, args


def _paged_decode(layers=None):
    """One layer's pool, or — as the decode step hands it over — the pools
    of ``layers`` layers stacked, read at a traced layer index, with the
    head dim padded to the lane tile (q padded to match)."""
    B, W = 8, 64
    d = D if layers is None else POOL_D
    pool = (PAGES, PAGE, HKV, d) if layers is None else \
        (layers, PAGES, PAGE, HKV, d)
    args = [((B, 1, HQ, d), jnp.bfloat16), (pool, jnp.bfloat16),
            (pool, jnp.bfloat16), ((B, W), jnp.int32),
            ((B,), jnp.int32), ((), jnp.int32), ((), jnp.int32)]

    def fn(q, pk, pv, table, cache_len, rank, layer):
        return paged_decode.paged_decode_attention(
            q, pk, pv, table, cache_len, rank, layer, sp=1, page_size=PAGE,
            window=WINDOW, interpret=False)
    return fn, args


def _paged_prefill():
    B, Sq, W = 1, 512, 64
    args = [((B, Sq, HQ, D), jnp.bfloat16),
            ((PAGES, PAGE, HKV, D), jnp.bfloat16),
            ((PAGES, PAGE, HKV, D), jnp.bfloat16), ((B, W), jnp.int32),
            ((B,), jnp.int32), ((), jnp.int32)]

    def fn(q, pk, pv, table, cached_len, rank):
        return paged_prefill.paged_prefill_attention(
            q, pk, pv, table, cached_len, rank, sp=1, page_size=PAGE,
            window=WINDOW, interpret=False)
    return fn, args


CASES = {
    "flash_fwd": lambda: _fwd(merge=False, ragged=False),
    "flash_fwd_merge": lambda: _fwd(merge=True, ragged=False),
    "flash_bwd_shared": lambda: _bwd(ragged=False),
    "flash_bwd_ragged": lambda: _bwd(ragged=True),
    "ragged_prefill_fwd": lambda: _fwd(merge=False, ragged=True),
    "paged_decode": _paged_decode,
    "paged_decode_stacked": lambda: _paged_decode(layers=24),
    "paged_prefill": _paged_prefill,
    "flash_fwd_d128": lambda: _fwd(merge=True, ragged=False, d=128),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = CASES[case]()
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
              for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_step_updates_pool_in_place(one_chip, monkeypatch):
    """The engine's decode step at h2o-danube head widths (2 layers, 512
    pages), compiled for a described v5e, touches the KV pool only where
    it must: no copy, dynamic-slice or dynamic-update-slice anywhere in
    the program yields a layer's pool slice or the whole pool (the layer
    scan carries the stacked pool; the token scatter and the kernel index
    it in place), the donated pools alias the outputs, and the program
    needs less scratch memory than one layer's pool slice."""
    import dataclasses
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import registry
    from repro.dist.sharding import SP_AXES
    from repro.engine import engine, paged_cache
    from repro.models.factory import build_model
    from repro.plan import make_serve_plan

    # this process is on the CPU, where the kernel defaults to interpret
    # mode; the described chip needs the real lowering
    compiled_kernel = functools.partial(paged_decode.paged_decode_attention,
                                        interpret=False)
    monkeypatch.setattr(paged_decode, "paged_decode_attention",
                        compiled_kernel)
    B, W, layers = 8, 64, 2
    cfg = dataclasses.replace(registry.get("h2o-danube-1.8b"),
                              num_layers=layers)
    model = build_model(cfg)
    plan = make_serve_plan(cfg, n_devices=1, decode_batch=B,
                           page_size=PAGE, max_len=W * PAGE,
                           kernel_impl="pallas", block_impl="pallas")
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1, 1, 1),
                ("data",) + SP_AXES)
    param_specs = model.partition(plan.run_config().sharding_rules)
    fn = engine.decode_step_fn(cfg, engine.serve_runtime(model, plan), mesh,
                               param_specs, PAGE)

    def on(spec, a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    params = jax.tree.map(on, param_specs, model.abstract(),
                          is_leaf=lambda x: isinstance(x, P))
    pools = jax.tree.map(on, paged_cache.pool_partition(cfg),
                         paged_cache.pool_spec(cfg, PAGES, PAGE),
                         is_leaf=lambda x: isinstance(x, P))
    small = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P()))
    compiled = fn.lower(
        params, pools, small((B, 1), jnp.int32), small((B,), jnp.int32),
        small((B, 1, W), jnp.int32), small((B,), jnp.float32),
        small((B,), jnp.int32), small((B,), jnp.float32),
        small((B, 2), jnp.uint32), small((B,), jnp.bool_)).compile()
    hlo = compiled.as_text()

    assert "tpu_custom_call" in hlo          # the kernel, not interpreted
    slice_shape = f"{PAGES},{PAGE},{HKV},{POOL_D}]"
    moves = re.compile(r"=\s*\(?bf16\[(?:\d+,)?" + re.escape(slice_shape)
                       + r"[^=]*?\s(copy|copy-start|dynamic-slice|"
                       r"dynamic-update-slice)\(")
    bad = [line.strip()[:160] for line in hlo.splitlines()
           if moves.search(line)]
    assert not bad, "pool moved:\n" + "\n".join(bad)
    stats = compiled.memory_analysis()
    layer_bytes = PAGES * PAGE * HKV * POOL_D * 2
    assert stats.alias_size_in_bytes >= 2 * layers * layer_bytes
    assert stats.temp_size_in_bytes < layer_bytes
