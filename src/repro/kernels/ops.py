"""Public jit'd wrappers adapting model-layout tensors to the kernels.

On TPU the Pallas kernels run compiled; everywhere else (CPU tests,
dry-run lowering) ``interpret=True`` or the jnp reference path is used.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .cost_reduce import cost_reduce_bet
from .flash_attention import flash_attention_bhsd
from .rwkv6_scan import wkv6_bhsd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def cost_reduce(x, w, *, interpret: Optional[bool] = None) -> jax.Array:
    """Batched cost reduction ``out[b, e] = sum_t x[b, t] * w[e, t]``.

    Shaped for the batched DSE backend's busy-group contraction: x [B, K]
    per-slot durations, w [G, K] static busy-group membership rows.  That
    backend does not call it: its contraction is a float64 XLA dot,
    because this kernel accumulates in float32 and misses the backend's
    1e-6 parity budget.  On TPU the Pallas MXU kernel runs compiled;
    elsewhere the jnp reference contraction runs in the input dtype.
    ``interpret=True`` forces the Pallas kernel through the interpreter
    (CI correctness tests for the kernel itself)."""
    if interpret is None:
        if not _on_tpu():
            return x @ w.T.astype(x.dtype)
        return cost_reduce_bet(x, w).astype(x.dtype)
    return cost_reduce_bet(x, w, interpret=interpret).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None, q_offset: int = 0,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Model-layout flash attention: q [B,S,N,G,D], k/v [B,Sk,N,D]."""
    b, s, n, g, d = q.shape
    sk = k.shape[1]
    qh = q.transpose(0, 2, 3, 1, 4).reshape(b, n * g, s, d)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
    itp = (not _on_tpu()) if interpret is None else interpret
    out = flash_attention_bhsd(qh, kh, vh, causal=causal, window=window,
                               softcap=softcap, q_offset=q_offset,
                               interpret=itp)
    return out.reshape(b, n, g, s, d).transpose(0, 3, 1, 2, 4)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, state0, *, chunk: int = 32,
         interpret: Optional[bool] = None):
    """Model-layout RWKV6 scan: r/k/v/w [B,S,N,D], u [N,D],
    state0 [B,N,D,D] -> (out [B,S,N,D] fp32, final state)."""
    tr = lambda t: t.transpose(0, 2, 1, 3)
    itp = (not _on_tpu()) if interpret is None else interpret
    out, st = wkv6_bhsd(tr(r), tr(k), tr(v), tr(w), u, state0,
                        chunk=chunk, interpret=itp)
    return out.transpose(0, 2, 1, 3), st
