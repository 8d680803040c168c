"""Plain AdamW as the configuration states it: float32 moments, bfloat16
parameters, global gradient-norm clipping, linear warm-up then cosine
decay, decoupled weight decay on every stored tensor of rank > 1."""
from __future__ import annotations

import math

import jax.numpy as jnp


def lr_at(o: dict, step: int) -> float:
    if step < o["warmup"]:
        return o["lr"] * (step + 1) / max(1, o["warmup"])
    prog = min(1.0, max(0.0, (step - o["warmup"])
                        / max(1, o["total_steps"] - o["warmup"])))
    return 0.1 * o["lr"] + 0.45 * o["lr"] * (1 + math.cos(math.pi * prog))


def clip_scale(grads: dict, o: dict):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in grads.values()))
    return jnp.minimum(1.0, o["clip_norm"] / (gnorm + 1e-9))


def step_scalars(o: dict, step: int) -> tuple:
    """Learning rate and bias corrections of step ``step`` (from 0)."""
    return (lr_at(o, step), 1 - o["b1"] ** (step + 1),
            1 - o["b2"] ** (step + 1))


def update(params: dict, grads: dict, m: dict, v: dict, scalars, o: dict):
    """One step with ``scalars = step_scalars(o, step)``; returns
    (params, m, v)."""
    lr, bc1, bc2 = scalars
    scale = clip_scale(grads, o)
    P, M, V = {}, {}, {}
    for k, p in params.items():
        g = grads[k].astype(jnp.float32) * scale
        M[k] = o["b1"] * m[k] + (1 - o["b1"]) * g
        V[k] = o["b2"] * v[k] + (1 - o["b2"]) * g * g
        upd = (M[k] / bc1) / (jnp.sqrt(V[k] / bc2) + o["eps"])
        decay = o["weight_decay"] if p.ndim > 1 else 0.0
        pf = p.astype(jnp.float32)
        P[k] = (pf - lr * (upd + decay * pf)).astype(p.dtype)
    return P, M, V
