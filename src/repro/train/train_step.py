"""The jit-able training step: loss -> grad -> (compress) -> AdamW.

The step's ops carry the named scopes ``grad`` (forward and backward,
with the model's own scopes inside) and ``adamw`` (with ``clip``, the
global-norm clip, inside) in their HLO ``op_name``.

Microbatched gradient accumulation runs as a ``lax.scan`` over batch
splits (pipeline-style utilization without PP's bubbles on a 2-D mesh);
the optional top-k gradient compression with error feedback sits between
accumulation and the optimizer (a distributed-optimization trick for
bandwidth-starved pods)."""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.models.common import AxisRules, Param, RuntimeCfg
from repro.obs import runtime_hooks
from .compress import topk_compress_decompress
from .optimizer import OptCfg, adamw_update


def make_train_step(spec, rt: RuntimeCfg, opt_cfg: OptCfg,
                    rules: Optional[AxisRules] = None, *,
                    grad_accum: int = 1, compress_ratio: float = 0.0):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    ``opt_state`` may carry an ``ef`` error-feedback buffer when
    compression is enabled."""
    runtime_hooks()

    def loss(params, batch):
        return lm.loss_fn(params, batch, spec, rt, rules)

    def grads_of(params, batch):
        if grad_accum <= 1:
            return jax.value_and_grad(loss)(params, batch)
        b = batch["tokens"].shape[0]
        mb = b // grad_accum

        def split(x):
            return x.reshape((grad_accum, mb) + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def unwrap(g):
            return jax.tree.map(lambda x: x.value if isinstance(x, Param) else x,
                                g, is_leaf=lambda x: isinstance(x, Param))

        def body(carry, mbatch):
            l, g = jax.value_and_grad(loss)(params, mbatch)
            acc_l, acc_g = carry
            return (acc_l + l,
                    jax.tree.map(jnp.add, acc_g, unwrap(g))), None

        zero_g = unwrap(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params,
            is_leaf=lambda x: isinstance(x, Param)))
        (tl, tg), _ = jax.lax.scan(body, (jnp.zeros(()), zero_g), micro)
        scale = 1.0 / grad_accum
        return tl * scale, jax.tree.map(lambda g: g * scale, tg)

    def train_step(params, opt_state, batch):
        with jax.named_scope("grad"):
            l, grads = grads_of(params, batch)
        grads = jax.tree.map(lambda g: getattr(g, "value", g), grads,
                             is_leaf=lambda x: isinstance(x, Param))
        metrics = {"loss": l}
        if compress_ratio > 0:
            ef = opt_state.get("ef")
            grads, ef = topk_compress_decompress(grads, ef,
                                                 ratio=compress_ratio)
            opt_state = {**opt_state, "ef": ef}
        ef = opt_state.pop("ef", None) if isinstance(opt_state, dict) else None
        core = {k: opt_state[k] for k in ("m", "v", "step")}
        with jax.named_scope("adamw"):
            params, core, om = adamw_update(params, grads, core, opt_cfg)
        new_opt = dict(core)
        if ef is not None:
            new_opt["ef"] = ef
        metrics.update(om)
        return params, new_opt, metrics

    return train_step
