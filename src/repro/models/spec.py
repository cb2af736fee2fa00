"""Spec-first parameter trees.

Modules declare their parameters once as ``PSpec`` trees (shape + logical
axis names + initialiser); the same tree then yields
  * materialised params       (``init_tree``, for real runs / smoke tests)
  * abstract params           (``abstract_tree``, ShapeDtypeStructs for the
                               dry-run: .lower() without any allocation)
  * PartitionSpecs            (``partition_tree`` via dist.sharding rules)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class PSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]       # logical axis name per dim
    init: str = "normal"                  # normal | zeros | ones
    scale: Optional[float] = None         # stddev; default fan-in
    dtype: Optional[str] = None           # override model param_dtype

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


def _is_spec(x) -> bool:
    return isinstance(x, PSpec)


def tree_map_specs(fn: Callable[[PSpec], Any], tree):
    return jax.tree.map(fn, tree, is_leaf=_is_spec)


def init_tree(tree, key: jax.Array, default_dtype: str):
    leaves = jax.tree.leaves(tree, is_leaf=_is_spec)
    keys = jax.random.split(key, max(len(leaves), 1))
    it = iter(range(len(leaves)))

    def init_one(spec: PSpec):
        i = next(it)
        dtype = jnp.dtype(spec.dtype or default_dtype)
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dtype)
        fan_in = spec.shape[0] if spec.shape else 1
        scale = spec.scale if spec.scale is not None else fan_in ** -0.5
        z = jax.random.normal(keys[i], spec.shape, jnp.float32)
        # under jit XLA would fuse the scale into the sampler and round
        # differently; the barrier keeps jitted (sharded) init bit-equal to
        # eager init of the same seed
        z = jax.lax.optimization_barrier(z)
        return (z * scale).astype(dtype)

    return tree_map_specs(init_one, tree)


def abstract_tree(tree, default_dtype: str):
    return tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype or default_dtype)),
        tree,
    )


def axes_tree(tree):
    return tree_map_specs(lambda s: s.axes, tree)


def stack_specs(tree, n: int, axis_name: str = "layers"):
    """Prepend a stacked (scan) dimension to every spec in the tree."""
    return tree_map_specs(
        lambda s: PSpec((n,) + s.shape, (axis_name,) + s.axes, s.init, s.scale,
                        s.dtype),
        tree,
    )


def count_params(tree) -> int:
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree, is_leaf=_is_spec))
