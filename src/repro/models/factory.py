"""Model factory: config -> (specs, init, abstract, partition, loss, inputs)."""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist import sharding as shard_rules
from repro.models import blocks, encdec, spec, transformer
from repro.models.runtime import Runtime


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    specs: object

    # ---- parameters ------------------------------------------------------
    def init(self, key: jax.Array):
        return spec.init_tree(self.specs, key, self.cfg.param_dtype)

    def init_sharded(self, shardings, seed: int = 0):
        """``init(PRNGKey(seed))`` created where it lives: one jit whose
        outputs carry ``shardings`` (a tree of ``Sharding`` matching the
        params), so no device ever holds the whole unsharded tree."""
        return jax.jit(self.init, out_shardings=shardings)(
            jax.random.PRNGKey(seed))

    def abstract(self):
        return spec.abstract_tree(self.specs, self.cfg.param_dtype)

    def axes(self):
        return spec.axes_tree(self.specs)

    def partition(self, rules: str = "default"):
        return shard_rules.partition_tree(self.axes(), rules)

    def param_count(self) -> int:
        return spec.count_params(self.specs)

    # ---- training loss ----------------------------------------------------
    def loss(self, rt: Runtime, params, batch, *, remat: str = "attn_out"):
        if self.cfg.encdec:
            return encdec.encdec_loss(rt, params, batch, self.cfg, remat=remat)
        return transformer.lm_loss(rt, params, batch, self.cfg, remat=remat)

    # ---- inputs -----------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
        """Global-shape ShapeDtypeStruct stand-ins for every model input
        (weak-type-correct, shardable, no device allocation)."""
        b, s = shape.global_batch, shape.seq_len
        out = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
        if self.cfg.frontend_stub is not None:
            out["frontend_emb"] = jax.ShapeDtypeStruct(
                (b, s, self.cfg.d_model), jnp.dtype(self.cfg.param_dtype))
        return out

    def make_batch(self, key: jax.Array, shape: ShapeConfig):
        """Random concrete batch matching input_specs (tests/examples)."""
        ks = jax.random.split(key, 3)
        b, s = shape.global_batch, shape.seq_len
        batch = {
            "tokens": jax.random.randint(ks[0], (b, s), 0,
                                         self.cfg.vocab_size, jnp.int32),
            "labels": jax.random.randint(ks[1], (b, s), 0,
                                         self.cfg.vocab_size, jnp.int32),
        }
        if self.cfg.frontend_stub is not None:
            batch["frontend_emb"] = jax.random.normal(
                ks[2], (b, s, self.cfg.d_model), jnp.float32).astype(
                    jnp.dtype(self.cfg.param_dtype))
        return batch


def build_model(cfg: ModelConfig) -> Model:
    if cfg.encdec:
        specs = encdec.encdec_specs(cfg)
    else:
        specs = transformer.lm_specs(cfg)
    return Model(cfg=cfg, specs=specs)
