"""Plain decoder LM with grouped-query attention (one KV head for
granite-34b: multi-query) and a plain GELU MLP: full forward in float32.

GPTBigCode (granite-34b-code-base) in the form the runtime states;
departures from the published model, shared with the runtime: RMS norms
(eps 1e-6) for layer norms, rotary positions (theta 1e4, halves rotated)
for learned ones, no biases.  The MLP's activation is the published
``gelu_pytorch_tanh``.  Weights are a dict ``path -> array`` in the
benchmark's layout; layers are stacked on the first dim of every
``slots/0/attn/*`` and ``slots/0/ffn/*`` leaf.  ``forward`` runs one
layer per call, so the float32 copy of one layer is all it adds.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from numerics import HI, mm, rms


def rope(x, pos, theta: float = 10000.0):
    """x [B,S,...,D]; rotates the two halves of the last dim."""
    half = x.shape[-1] // 2
    freqs = (1.0 / theta) ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, :, None].astype(jnp.float32) * freqs
    shape = ang.shape[:2] + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(p: dict, x, mode: str):
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = rms(x, p["attn/ln"])
    q = rope(mm("bsh,hngd->bsngd", h, p["attn/w_q"], mode), pos)
    k = rope(mm("bsh,hnd->bsnd", h, p["attn/w_k"], mode), pos)
    v = mm("bsh,hnd->bsnd", h, p["attn/w_v"], mode)
    sc = jnp.einsum("bsngd,btnd->bngst", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    a = jnp.einsum("bngst,btnd->bsngd", jax.nn.softmax(sc, -1), v,
                   precision=HI)
    x = x + mm("bsngd,ngdh->bsh", a, p["attn/w_o"], mode)
    h = rms(x, p["ffn/ln"])
    return x + mm("bsf,fh->bsh", gelu_tanh(mm("bsh,hf->bsf", h,
                                               p["ffn/w_up"], mode)),
                  p["ffn/w_down"], mode)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(ln, w, x, mode: str):
    return mm("bsh,hv->bsv", rms(x, ln), w, mode)


def forward(params: dict, tokens, mode: str = "f32"):
    """tokens [B,S] -> logits [B,S,V] float32."""
    x = params["embed"].astype(jnp.float32)[tokens]
    pre = "slots/0/"
    stack = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
    for i in range(next(iter(stack.values())).shape[0]):
        x = _layer({k: v[i] for k, v in stack.items()}, x, mode)
    return _head(params["ln_f"], params["lm_head"], x, mode)
