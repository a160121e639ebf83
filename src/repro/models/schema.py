"""Declarative parameter schemas.

Every model defines its parameters once, as a pytree of ``TensorSpec``s.
From that single definition we derive:

  * ``init_params``     — materialized parameters (seeded, scaled init);
  * ``abstract_params`` — ``ShapeDtypeStruct`` stand-ins for the multi-pod
    dry-run (no allocation ever happens);
  * ``logical_axes``    — the logical sharding axes consumed by
    ``repro.parallel.sharding`` (t5x-style logical→mesh rules).

Keeping all three views generated from one schema is what makes checkpoints
mesh-agnostic (elastic restart re-shards by logical axes, not device
layout).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis per dim (None = replicated)
    init: str = "normal"             # normal | zeros | ones | embed
    scale: Optional[float] = None    # stddev; default 1/sqrt(fan_in)
    dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"axes {self.axes} do not match shape {self.shape}")


def _is_spec(x) -> bool:
    return isinstance(x, TensorSpec)


def _fan_in(shape: Tuple[int, ...]) -> int:
    return shape[-2] if len(shape) >= 2 else shape[-1]


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make_leaf(spec: TensorSpec, k, dtype):
    dt = dtype or spec.dtype
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    std = spec.scale if spec.scale is not None else _fan_in(spec.shape) ** -0.5
    if spec.init == "embed":
        std = spec.scale if spec.scale is not None else 1.0
    return (jax.random.normal(k, spec.shape, jnp.float32) * std).astype(dt)


def init_params(schema, key: jax.Array, dtype=None, sharding=None):
    """Materialize a schema into a parameter pytree (``dtype`` overrides
    every spec's own). Each leaf is drawn by one jitted program, so only
    the leaf in its final dtype becomes resident — not an f32 draw and
    its scaled copy beside it. With ``sharding`` each leaf is placed as
    soon as it is drawn, so the default device never holds the whole
    tree twice."""
    leaves, treedef = jax.tree.flatten(schema, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))

    def make(spec, k):
        leaf = _make_leaf(spec, k, dtype)
        return leaf if sharding is None else jax.device_put(leaf, sharding)

    return jax.tree.unflatten(
        treedef, [make(s, k) for s, k in zip(leaves, keys)])


def abstract_params(schema, dtype=None):
    """ShapeDtypeStruct pytree — dry-run stand-in, no allocation."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype),
        schema,
        is_leaf=_is_spec,
    )


def logical_axes(schema):
    """Pytree of logical-axis tuples matching the parameter pytree."""
    return jax.tree.map(lambda s: s.axes, schema, is_leaf=_is_spec)


def param_count(schema) -> int:
    import math

    leaves = jax.tree.leaves(schema, is_leaf=_is_spec)
    return sum(math.prod(s.shape) for s in leaves)
