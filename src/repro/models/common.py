"""Shared JAX model infrastructure: runtime config, dtype policy, logical
axes, and sharding-constraint helpers.

Every parameter is created together with a *logical axis* tuple (MaxText
style).  ``repro.parallel.sharding`` maps logical names onto mesh axes;
the same logical names are what the STAGE core's role annotations
correspond to, so the analytical planner and the compiled program shard
identically.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclass(frozen=True)
class RuntimeCfg:
    """Runtime knobs orthogonal to the architecture itself."""
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    attention_impl: str = "chunked"     # chunked: online softmax under a
                                        # checkpoint; naive: one masked
                                        # softmax; pallas: the Pallas kernel
    attn_chunk: int = 1024              # q block and kv chunk of "chunked"
    remat: str = "none"                 # none | full (checkpoint each layer)
    sp: bool = True                     # sequence-parallel activation layout
    zero1: bool = True                  # shard optimizer state over data axes
    moe_capacity: float = 1.25          # expert capacity factor of the EP
                                        # shard_map path (one chip: dropless)


def dt(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# Param trees with logical axes
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class Param:
    """An array (or abstract value) + its logical axis names."""
    __slots__ = ("value", "axes")

    def __init__(self, value, axes: tuple):
        self.value = value
        self.axes = tuple(axes)

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)

    @property
    def shape(self):
        return self.value.shape

    def __repr__(self):
        return f"Param{list(self.shape)}@{self.axes}"


def pvalue(tree: PyTree) -> PyTree:
    """Strip Param wrappers -> raw arrays."""
    return jax.tree.map(lambda p: p.value, tree,
                        is_leaf=lambda x: isinstance(x, Param))


def paxes(tree: PyTree) -> PyTree:
    """Param tree -> logical-axes tree (same structure, tuples as leaves)."""
    return jax.tree.map(lambda p: p.axes, tree,
                        is_leaf=lambda x: isinstance(x, Param))


class Initializer:
    """Deterministic fan-in-scaled normal init, usable under eval_shape."""

    def __init__(self, key: jax.Array, dtype: str):
        self.key = key
        self.dtype = dt(dtype)
        self._n = 0

    def __call__(self, name: str, shape: tuple, axes: tuple,
                 scale: Optional[float] = None, dtype=None) -> Param:
        self._n += 1
        k = jax.random.fold_in(self.key, self._n)
        fan_in = shape[0] if len(shape) > 1 else max(1, shape[-1])
        std = scale if scale is not None else 1.0 / np.sqrt(fan_in)
        if len(shape) <= 1:
            val = jnp.ones(shape, dtype or self.dtype)     # norm scales
        else:
            val = (jax.random.normal(k, shape, jnp.float32) * std) \
                .astype(dtype or self.dtype)
        assert len(axes) == len(shape), (name, shape, axes)
        return Param(val, axes)


# ---------------------------------------------------------------------------
# Activation sharding constraints via logical names
# ---------------------------------------------------------------------------

class AxisRules:
    """Maps logical axis names -> physical mesh axes (or None)."""

    def __init__(self, rules: dict[str, Any] | None):
        self.rules = dict(rules or {})

    def spec(self, axes: tuple) -> "jax.sharding.PartitionSpec":
        from jax.sharding import PartitionSpec as P
        phys = []
        used: set = set()
        for a in axes:
            m = self.rules.get(a)
            if m is None:
                phys.append(None)
                continue
            ms = tuple(m) if isinstance(m, (tuple, list)) else (m,)
            ms = tuple(x for x in ms if x not in used)
            used.update(ms)
            phys.append(ms if len(ms) != 1 else ms[0])
        while phys and phys[-1] is None:
            phys.pop()
        return P(*phys)


def constrain(x: jax.Array, rules: Optional[AxisRules], axes: tuple) -> jax.Array:
    """with_sharding_constraint by logical axes (no-op without rules)."""
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(x, rules.spec(axes))
