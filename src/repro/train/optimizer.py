"""AdamW with bf16 params + fp32 moments, ZeRO-1 state sharding, global
grad-norm clipping, and cosine LR schedule — the training substrate the
paper's workloads assume (mixed-precision Adam is what Table V's
optimizer-memory terms model)."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import Param, pvalue
from repro.parallel.sharding import param_pspec


@dataclass(frozen=True)
class OptCfg:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def schedule(cfg: OptCfg, step):
    warm = cfg.lr * (step + 1) / max(1, cfg.warmup)
    prog = jnp.clip((step - cfg.warmup) / max(1, cfg.total_steps - cfg.warmup),
                    0.0, 1.0)
    cos = 0.1 * cfg.lr + 0.45 * cfg.lr * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < cfg.warmup, warm, cos)


def init_opt_state(params) -> dict:
    zeros = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params,
        is_leaf=lambda x: isinstance(x, Param))
    return {"m": zeros,
            "v": jax.tree.map(jnp.zeros_like, zeros),
            "step": jnp.zeros((), jnp.int32)}


def opt_state_shardings(params, rules: dict, mesh: Mesh, *,
                        zero1: bool = True,
                        data_axes: tuple = ("pod", "data")):
    """Moments sharded like params, plus (ZeRO-1) an extra data-axis shard
    on the first evenly divisible free dim."""
    deg = int(np.prod([mesh.shape[n] for n in data_axes]))

    def one(p: Param):
        spec = list(param_pspec(p, rules, mesh)) + [None] * p.value.ndim
        spec = spec[:p.value.ndim]
        if zero1:
            flat_data = [a for e in spec if e
                         for a in (e if isinstance(e, tuple) else (e,))]
            if not any(a in flat_data for a in data_axes):
                for d in range(p.value.ndim):
                    if spec[d] is None and p.shape[d] % deg == 0:
                        spec[d] = data_axes
                        break
        return NamedSharding(mesh, P(*spec))

    m = jax.tree.map(one, params, is_leaf=lambda x: isinstance(x, Param))
    return {"m": m, "v": m, "step": NamedSharding(mesh, P())}


def global_norm(grads) -> jax.Array:
    leaves = jax.tree.leaves(grads)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


def adamw_update(params, grads, opt_state, cfg: OptCfg):
    """One AdamW step.  ``params`` is a Param tree; ``grads`` matches its
    value tree.  Returns (new params, new opt state, metrics)."""
    step = opt_state["step"]
    lr = schedule(cfg, step)
    with jax.named_scope("clip"):
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))

    flat_p, treedef = jax.tree.flatten(
        params, is_leaf=lambda x: isinstance(x, Param))
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(opt_state["m"])
    flat_v = jax.tree.leaves(opt_state["v"])
    t = (step + 1).astype(jnp.float32)
    bc1 = 1 - cfg.b1 ** t
    bc2 = 1 - cfg.b2 ** t

    new_p, new_m, new_v = [], [], []
    for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        gf = g.astype(jnp.float32) * scale
        m2 = cfg.b1 * m + (1 - cfg.b1) * gf
        v2 = cfg.b2 * v + (1 - cfg.b2) * gf * gf
        upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + cfg.eps)
        decay = cfg.weight_decay if p.value.ndim > 1 else 0.0
        pv = p.value.astype(jnp.float32)
        pv = pv - lr * (upd + decay * pv)
        new_p.append(Param(pv.astype(p.value.dtype), p.axes))
        new_m.append(m2)
        new_v.append(v2)

    params2 = jax.tree.unflatten(treedef, new_p)
    mdef = jax.tree.structure(opt_state["m"])
    return params2, {"m": jax.tree.unflatten(mdef, new_m),
                     "v": jax.tree.unflatten(mdef, new_v),
                     "step": step + 1}, {"grad_norm": gnorm, "lr": lr}
