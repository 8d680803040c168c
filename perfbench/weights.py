"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program is handed
them in its own parameter tree, and the plain references regenerate the
same values from the same seed and layout, a list of ``(path, shape,
dtype)``.  A leaf under ``slots/`` is a stack of layers, its first dim
the layer.  Per layer: vectors are norm scales (ones), except the
``mu_*`` token-shift mixes, uniform in [0, 1); matrices are normal with
``gen.leaf_std``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.tree_util import FlattenedIndexKey

import gen


def path_str(path) -> str:
    parts = []
    for k in path:
        if isinstance(k, FlattenedIndexKey):
            continue
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def layout(abstract) -> list:
    """``[(path, shape, dtype)]`` of a tree of arrays or shape structs."""
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract)
    return [(path_str(p), tuple(x.shape), jnp.dtype(x.dtype).name)
            for p, x in flat]


def _leaf(key, path: str, shape: tuple, dtype: str, overrides: dict):
    stacked = path.split("/")[0] == "slots"
    base = shape[1:] if stacked else shape
    name = path.split("/")[-1]
    if len(base) <= 1:
        if name.startswith("mu_"):
            v = jax.random.uniform(key, shape, jnp.float32)
        else:
            v = jnp.ones(shape, jnp.float32)
    else:
        v = jax.random.normal(key, shape, jnp.float32) * gen.leaf_std(
            name, base, overrides)
    return v.astype(dtype)


def seed_key(seed: int):
    lo, hi = gen.split_seed(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def build(key, lay: list, overrides: dict) -> list:
    """The leaves of ``lay``, traceable (for use inside a jitted call)."""
    return [_leaf(jax.random.fold_in(key, i), p, s, d, overrides)
            for i, (p, s, d) in enumerate(lay)]


def make_flat(lay: list, seed: int, overrides: dict, shardings=None) -> list:
    """The leaves of ``lay`` from ``seed``, in order, in one jitted call."""
    return jax.jit(lambda k: build(k, lay, overrides),
                   out_shardings=shardings)(seed_key(seed))


def make_tree(abstract, seed: int, overrides: dict, shardings=None):
    """The program's parameter tree ``abstract`` filled from ``seed``."""
    lay = layout(abstract)
    leaves = make_flat(lay, seed, overrides,
                       None if shardings is None
                       else jax.tree_util.tree_leaves(shardings))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), leaves), lay
