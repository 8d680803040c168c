"""Plain DeepSeek-V2 decoder: full forward in float32.

Multi-head latent attention with yarn RoPE, a dense SiLU-gated first
layer, then layers of routed plus shared SiLU-gated experts, as the
published ``modeling_deepseek.py`` and ``config.json``
(deepseek-ai/DeepSeek-V2, arXiv:2405.04434) have them:

- attention: q = W_uq(norm(W_dq h)) split into a 128-wide nope and a
  64-wide rope part per head; the latent c = norm(W_dkv h) [512] and one
  rope key W_kr h [64] shared by all heads; keys [W_uk c, rope(k_r)],
  values W_uv c, expanded per head; softmax scale mscale(40, 0.707)^2 /
  sqrt(192); RoPE with yarn frequencies (factor 40 over 4096 original
  positions, beta_fast 32, beta_slow 1) and a cos/sin factor of
  mscale(0.707) / mscale(0.707) = 1.
- router: softmax in float32 over all experts; group_limited_greedy:
  the 3 of 8 groups whose best expert scores highest, the top 6 experts
  within them; gates not renormalized, times 16.

Departures, shared with the runtime: RMS norms (eps 1e-6) and RoPE that
rotates the two halves of the rope dims (the published code permutes
interleaved pairs into halves first; random weights cannot tell the
layouts apart).  The cut, as the configuration states it: each MoE layer
holds one routing group, experts ``HELD_GROUP`` x held .. +held of all
the router's outputs (held = the first dim of ``w_egate``), and adds
only their part for the tokens routed to them, plus the shared experts.

Weights are a dict ``path -> array`` in the benchmark's layout (layer 0
under ``prefix/0/``, the MoE layers stacked on the first dim of every
``slots/0/`` leaf).  ``forward`` runs one layer per call and attention one
sequence and one block of queries at a time, so a 1,536-token request
fits beside the weights; it returns the logits on the host.  Matrix
products with weights go through ``numerics.mm``, so the control can run
them in float8.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from numerics import HI, mm, rms

# published config.json values; the sizes come from the weights
ROPE_THETA, ROPE_FACTOR, ROPE_ORIGINAL_MAX = 10000.0, 40.0, 4096
BETA_FAST, BETA_SLOW, MSCALE, MSCALE_ALL_DIM = 32, 1, 0.707, 0.707
N_GROUP, TOPK_GROUP, TOP_K, ROUTED_SCALE = 8, 3, 6, 16.0
HELD_GROUP = 0              # the routing group this chip holds
Q_BLOCK = 256               # queries per attention block


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies [dim/2]."""
    extra = 1.0 / ROPE_THETA ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / ROPE_FACTOR

    def correction_dim(rotations):
        return (dim * math.log(ROPE_ORIGINAL_MAX / (rotations * 2 * math.pi))
                / (2 * math.log(ROPE_THETA)))
    low = max(math.floor(correction_dim(BETA_FAST)), 0)
    high = min(math.ceil(correction_dim(BETA_SLOW)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp                     # 1 keeps the original frequency
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(q_head_dim: int) -> float:
    return q_head_dim ** -0.5 * yarn_mscale(ROPE_FACTOR, MSCALE_ALL_DIM) ** 2


def rope(x, pos, inv_freq):
    """x [S, ..., D]; rotates the two halves of the last dim."""
    ang = pos[:, None].astype(jnp.float32) * inv_freq
    cs = yarn_mscale(ROPE_FACTOR, MSCALE) / yarn_mscale(ROPE_FACTOR,
                                                        MSCALE_ALL_DIM)
    shape = ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:]
    cos = (jnp.cos(ang) * cs).reshape(shape)
    sin = (jnp.sin(ang) * cs).reshape(shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention_one(p: dict, x, mode: str):
    """One sequence x [S, H] -> attention output [S, H]."""
    s = x.shape[0]
    pos = jnp.arange(s)
    h = rms(x, p["attn/ln"])
    cq = rms(mm("sh,hr->sr", h, p["attn/w_dq"], mode), p["attn/ln_q"])
    dr = p["attn/w_kr"].shape[-1]
    inv_freq = yarn_inv_freq(dr)
    q = jnp.concatenate([mm("sr,rnd->snd", cq, p["attn/w_uq_n"], mode),
                         rope(mm("sr,rnd->snd", cq, p["attn/w_uq_r"], mode),
                              pos, inv_freq)], -1)
    c = rms(mm("sh,hr->sr", h, p["attn/w_dkv"], mode), p["attn/ln_kv"])
    kr = rope(mm("sh,hd->sd", h, p["attn/w_kr"], mode), pos, inv_freq)
    kn = mm("sr,rnd->snd", c, p["attn/w_uk"], mode)
    k = jnp.concatenate([kn, jnp.broadcast_to(
        kr[:, None], kn.shape[:2] + (dr,))], -1)
    v = mm("sr,rnd->snd", c, p["attn/w_uv"], mode)
    scale = softmax_scale(q.shape[-1])
    nb = -(-s // Q_BLOCK)
    q = jnp.pad(q, ((0, nb * Q_BLOCK - s), (0, 0), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        sc = jnp.einsum("qnd,tnd->nqt", qb, k, precision=HI) * scale
        causal = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None] \
            >= jnp.arange(s)[None, :]
        sc = jnp.where(causal, sc, -jnp.inf)
        return jnp.einsum("nqt,tnd->qnd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    a = jax.lax.map(block, jnp.arange(nb)).reshape(nb * Q_BLOCK, *v.shape[1:])
    return mm("snd,ndh->sh", a[:s], p["attn/w_o"], mode)


def _attention(p: dict, x, mode: str):
    return x + jax.lax.map(lambda xi: _attention_one(p, xi, mode), x)


def swiglu(h, w_gate, w_up, w_down, mode: str):
    return mm("tf,fh->th", jax.nn.silu(mm("th,hf->tf", h, w_gate, mode))
              * mm("th,hf->tf", h, w_up, mode), w_down, mode)


def route(h, w_router):
    """Gates [T, K] and experts [T, K] of tokens h [T, H]."""
    scores = jax.nn.softmax(jnp.einsum("th,he->te", h, w_router.astype(
        jnp.float32), precision=HI), -1)
    t, e = scores.shape
    group_scores = scores.reshape(t, N_GROUP, e // N_GROUP).max(-1)
    _, group_idx = jax.lax.top_k(group_scores, TOPK_GROUP)
    group_mask = jnp.zeros((t, N_GROUP)).at[
        jnp.arange(t)[:, None], group_idx].set(1.0)
    score_mask = jnp.repeat(group_mask, e // N_GROUP, axis=1)
    weight, idx = jax.lax.top_k(jnp.where(score_mask > 0, scores, 0.0), TOP_K)
    return weight * ROUTED_SCALE, idx


def routed_part(h, gates, idx, w_gate, w_up, w_down, first: int, mode: str):
    """Experts ``first`` .. ``first`` + n (n = the first dim of the expert
    weights): each applied to every token, weighted by the token's gate
    for it (0 where it was not chosen)."""
    def one(acc, e):
        g = jnp.sum(jnp.where(idx == first + e[0], gates, 0.0), -1)
        y = swiglu(h, e[1], e[2], e[3], mode)
        return acc + g[:, None] * y, None
    n = w_gate.shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (jnp.arange(n), w_gate, w_up, w_down))
    return out


def moe(p: dict, h, mode: str):
    """h [T, H] normed -> routed part of the held group + shared experts."""
    gates, idx = route(h, p["moe/w_router"])
    held = p["moe/w_egate"].shape[0]
    return routed_part(h, gates, idx, p["moe/w_egate"], p["moe/w_eup"],
                       p["moe/w_edown"], HELD_GROUP * held, mode) \
        + swiglu(h, p["moe/shared/w_gate"], p["moe/shared/w_up"],
                 p["moe/shared/w_down"], mode)


@functools.partial(jax.jit, static_argnums=(2,))
def _dense_layer(p: dict, x, mode: str):
    x = _attention(p, x, mode)
    b, s, H = x.shape
    h = rms(x, p["ffn/ln"]).reshape(b * s, H)
    return x + swiglu(h, p["ffn/w_gate"], p["ffn/w_up"], p["ffn/w_down"],
                      mode).reshape(b, s, H)


@functools.partial(jax.jit, static_argnums=(2,))
def _moe_layer(p: dict, x, mode: str):
    x = _attention(p, x, mode)
    b, s, H = x.shape
    h = rms(x, p["moe/ln"]).reshape(b * s, H)
    return x + moe(p, h, mode).reshape(b, s, H)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(ln, w, x, mode: str):
    return mm("sh,hv->sv", rms(x, ln), w, mode)


def forward(params: dict, tokens, mode: str = "f32") -> np.ndarray:
    """tokens [B,S] -> logits [B,S,V] float32, on the host."""
    x = params["embed"].astype(jnp.float32)[tokens]
    x = _dense_layer({k[len("prefix/0/"):]: v for k, v in params.items()
                      if k.startswith("prefix/0/")}, x, mode)
    pre = "slots/0/"
    stack = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
    for i in range(next(iter(stack.values())).shape[0]):
        x = _moe_layer({k: v[i] for k, v in stack.items()}, x, mode)
    b, s = tokens.shape
    out = np.empty((b, s, params["lm_head"].shape[1]), np.float32)
    for j in range(b):
        out[j] = np.asarray(_head(params["ln_f"], params["lm_head"], x[j],
                                  mode))
    return out
