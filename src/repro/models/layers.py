"""JAX layer library for the assigned architectures.

Pure functions over ``{name: Param}`` subtrees.  Shapes follow the STG
templates in ``repro.core.modules`` so the analytical planner and the
compiled program describe the same computation:

* GQA weights keep head structure: ``w_q [H, NKV, G, DH]``.
* Attention uses an online-softmax **chunked** implementation by default
  (sub-quadratic memory; what the Pallas kernel computes on TPU).
* RWKV6 / Mamba use chunked linear-recurrence scans carrying an O(1)
  state — memory O(B·C²) per chunk instead of O(B·S·D·D).
* Each layer kind traces under a ``jax.named_scope`` of its function's
  name (``gqa_attention``, ``mla_attention``, ``ffn``, ``moe_ffn``,
  ``mamba_layer``, ``rwkv6_time_mix``, ``rwkv6_channel_mix``), so the
  HLO ``op_name`` of each of its ops, and the profiler's device events,
  say which layer they belong to.  Trace time only: no arithmetic moves.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .common import AxisRules, Initializer, Param, RuntimeCfg, constrain, dt

# Logical axis names (map to mesh axes via parallel.sharding rules)
EMB, HEADS, KV, QGRP, HDIM = "embed", "heads", "kv_heads", "q_grp", "head_dim"
FFN, VOCAB, EXP, LORA = "ffn", "vocab", "experts", "lora"
BATCH, SEQ, KVSEQ = "act_batch", "act_seq", "act_kv"


def cast(x, rt: RuntimeCfg):
    return x.astype(dt(rt.compute_dtype))


def scoped(fn):
    """Trace ``fn`` inside a ``jax.named_scope`` of its own name."""
    @functools.wraps(fn)
    def wrapper(*args, **kw):
        with jax.named_scope(fn.__name__):
            return fn(*args, **kw)
    return wrapper


# ---------------------------------------------------------------------------
# Norms & RoPE
# ---------------------------------------------------------------------------

def rms_norm(w: Param, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * w.value.astype(jnp.float32)
    return out.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, *, theta: float = 10000.0,
         inv_freq=None) -> jax.Array:
    """Rotary embedding over the last dim; positions [B, S].  Frequencies
    theta^(-2i/d) unless ``inv_freq`` [d/2] is given."""
    d = x.shape[-1]
    half = d // 2
    if inv_freq is None:
        freqs = (1.0 / theta) ** (jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs         # [B,S,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    extra = x.ndim - 3                                              # head dims
    cos = cos.reshape(cos.shape[:2] + (1,) * extra + (half,))
    sin = sin.reshape(sin.shape[:2] + (1,) * extra + (half,))
    x1, x2 = x[..., :half], x[..., half:2 * half]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if 2 * half != d:
        rot = jnp.concatenate([rot, x[..., 2 * half:]], axis=-1)
    return rot.astype(x.dtype)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def mla_rope(m) -> np.ndarray:
    """``inv_freq`` [rope_dim/2] of an MLA spec's RoPE part.  With
    ``rope_factor`` > 1, yarn as the published deepseek-v2 modeling code
    has it: theta^(-2i/d) and the same over ``rope_factor`` blended along
    a linear ramp between the dims whose wavelengths turn ``beta_fast``
    and ``beta_slow`` times in ``rope_original_max`` positions (10 and 23
    for deepseek-v2).  Its cos/sin factor, mscale(mscale) /
    mscale(mscale_all_dim), is 1 there (both 0.707) and is left out."""
    d = m.rope_dim
    base = 1.0 / (m.rope_theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    if m.rope_factor <= 1:
        return base

    def dim_of(rotations):
        return d * math.log(m.rope_original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(m.rope_theta))
    lo = max(math.floor(dim_of(m.beta_fast)), 0)
    hi = min(math.ceil(dim_of(m.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    return (base / m.rope_factor * ramp + base * (1.0 - ramp)).astype(np.float32)


def mla_softmax_scale(m) -> float:
    """1/sqrt(nope + rope dims), times mscale(mscale_all_dim)^2 under yarn
    (deepseek-v2: 1.2608^2 / sqrt(192) = 0.114721)."""
    scale = 1.0 / math.sqrt(m.nope_dim + m.rope_dim)
    if m.mscale_all_dim:
        scale *= _yarn_mscale(m.rope_factor, m.mscale_all_dim) ** 2
    return scale


def _softcap(x: jax.Array, cap: float) -> jax.Array:
    return (cap * jnp.tanh(x / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------

def attn_naive(q, k, v, *, causal: bool, window: Optional[int],
               softcap: Optional[float], q_offset: int = 0,
               scale: Optional[float] = None) -> jax.Array:
    """q [B,Sq,N,G,D], k/v [B,Sk,N,D] -> [B,Sq,N,G,D]; scores scaled by
    ``scale`` (1/sqrt(D) if None)."""
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bsngd,bknd->bngsk", q, k).astype(jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    sq, sk = q.shape[1], k.shape[1]  # note: v may have a different head dim
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bngsk,bknd->bsngd", p, v)


def attn_chunked(q, k, v, *, causal: bool, window: Optional[int],
                 softcap: Optional[float], chunk: int = 1024,
                 q_offset=0, scale: Optional[float] = None) -> jax.Array:
    """Online-softmax (flash) attention: q blocked via lax.map where the
    chunk divides the queries, kv scanned.

    Live memory O(chunk²) per step instead of O(Sq·Sk) — this is the jnp
    rendering of the Pallas kernel in ``repro.kernels.flash_attention``."""
    b, sq, n, g, d = q.shape
    qb = chunk
    if sq > qb and sq % qb == 0:
        nb = sq // qb
        qblocks = q.reshape(b, nb, qb, n, g, d).transpose(1, 0, 2, 3, 4, 5)
        offs = q_offset + jnp.arange(nb) * qb

        def one(args):
            qi, off = args
            return _attn_flash(qi, k, v, causal=causal, window=window,
                               softcap=softcap, chunk=chunk, q_offset=off,
                               scale=scale)

        out = jax.lax.map(one, (qblocks, offs))
        return out.transpose(1, 0, 2, 3, 4, 5).reshape(
            b, sq, n, g, out.shape[-1])
    return _attn_flash(q, k, v, causal=causal, window=window,
                       softcap=softcap, chunk=chunk, q_offset=q_offset,
                       scale=scale)


def _attn_flash(q, k, v, *, causal: bool, window: Optional[int],
                softcap: Optional[float], chunk: int, q_offset=0,
                scale: Optional[float] = None) -> jax.Array:
    b, sq, n, g, d = q.shape
    sk = k.shape[1]
    if sk <= chunk and isinstance(q_offset, int):
        return attn_naive(q, k, v, causal=causal, window=window,
                          softcap=softcap, q_offset=q_offset, scale=scale)
    nchunks = -(-sk // chunk)
    pad = nchunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, nchunks, chunk, n, k.shape[-1]).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nchunks, chunk, n, v.shape[-1]).transpose(1, 0, 2, 3, 4)
    scale = scale or 1.0 / math.sqrt(d)
    qpos = jnp.arange(sq) + q_offset

    def body(carry, ckv):
        m, l, acc, ci = carry
        kci, vci = ckv
        s = jnp.einsum("bsngd,bknd->bngsk", q, kci).astype(jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        kpos = ci * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < sk
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] \
            + jnp.einsum("bngsk,bknd->bngsd", p.astype(q.dtype), vci)
        return (m_new, l_new, acc_new, ci + 1), None

    dv = v.shape[-1]
    m0 = jnp.full((b, n, g, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, n, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, n, g, sq, dv), jnp.float32)
    # checkpoint the chunk body: backward recomputes the probability
    # block per chunk instead of stacking O(Sq x chunk) f32 residuals
    (m, l, acc, _), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), (m0, l0, acc0, 0), (kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)     # [B,Sq,N,G,D]


def attn_core(q, k, v, rt: RuntimeCfg, *, causal: bool, window=None,
              softcap=None, q_offset: int = 0,
              scale: Optional[float] = None) -> jax.Array:
    if rt.attention_impl == "pallas":
        from repro.kernels import ops as kops
        if scale is not None:         # the kernel scales by 1/sqrt(D)
            q = q * (scale * math.sqrt(q.shape[-1]))
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    softcap=softcap, q_offset=q_offset)
    if rt.attention_impl == "chunked":
        # flash semantics: backward recomputes from q/k/v instead of
        # stashing per-chunk probability matrices (O(S·chunk) residuals
        # would otherwise dominate training memory)
        fn = jax.checkpoint(
            functools.partial(attn_chunked, causal=causal, window=window,
                              softcap=softcap, chunk=rt.attn_chunk,
                              q_offset=q_offset, scale=scale),
            prevent_cse=False)
        return fn(q, k, v)
    return attn_naive(q, k, v, causal=causal, window=window,
                      softcap=softcap, q_offset=q_offset, scale=scale)


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------
# Each layer kind builds its cache beside its params (``init_*_cache``) and
# is the only code that reads or writes it.  A prefix layer gets its own
# cache; a layer of a scanned slot gets the stack of the slot's layers (a
# leading layer axis) and its index ``layer`` in it.  The next position to
# write is one scalar for the whole model, handed to each layer by
# ``lm.decode_step``.

def layer_of(cache: dict, layer=None) -> dict:
    """Layer ``layer``'s arrays of a stacked cache (``cache`` itself when
    ``layer`` is None)."""
    if layer is None:
        return cache
    return {k: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for k, a in cache.items()}


def put_layer(cache: dict, new: dict, layer=None) -> dict:
    """``cache`` with layer ``layer``'s arrays replaced whole by ``new``
    (``new`` itself when ``layer`` is None)."""
    if layer is None:
        return new
    return {k: jax.lax.dynamic_update_index_in_dim(a, new[k], layer, 0)
            for k, a in cache.items()}


def write_cache(cache: dict, new: dict, pos, axis: int,
                layer=None) -> tuple[dict, dict]:
    """Write each ``new[k]`` into ``cache[k]`` from position ``pos`` along
    ``axis`` of one layer's array.  Returns (the written cache, this
    layer's arrays after the write).

    With ``layer`` each write is one ``dynamic_update_slice`` at
    (``layer``, ``pos``) of the stack, which XLA makes in place in a
    donated or carried buffer, and the layer is read back from the
    updated stack."""
    if layer is None:
        out = {k: jax.lax.dynamic_update_slice_in_dim(cache[k], v, pos, axis)
               for k, v in new.items()}
        return out, out
    out = {}
    for k, v in new.items():
        start = [0] * cache[k].ndim
        start[0], start[1 + axis] = layer, pos
        out[k] = jax.lax.dynamic_update_slice(cache[k], v[None], start)
    # one copy of the layer for all of its products: XLA would otherwise
    # slice it out of the stack once for each
    return out, jax.lax.optimization_barrier(layer_of(out, layer))


# ---------------------------------------------------------------------------
# GQA attention layer (granite/gemma2/qwen3/minitron/whisper/internvl/jamba)
# ---------------------------------------------------------------------------

def init_gqa(ini: Initializer, spec, prefix: str = "") -> dict:
    H, DHd = spec.d_model, spec.head_dim
    nkv = max(1, spec.n_kv_heads)
    g = max(1, spec.n_heads // nkv)
    p = {
        "ln": ini(prefix + "ln", (H,), (EMB,)),
        "w_q": ini(prefix + "w_q", (H, nkv, g, DHd), (EMB, KV, QGRP, HDIM)),
        "w_k": ini(prefix + "w_k", (H, nkv, DHd), (EMB, KV, HDIM)),
        "w_v": ini(prefix + "w_v", (H, nkv, DHd), (EMB, KV, HDIM)),
        "w_o": ini(prefix + "w_o", (nkv, g, DHd, H), (KV, QGRP, HDIM, EMB),
                   scale=1.0 / np.sqrt(H)),
    }
    if spec.qk_norm:
        p["qn"] = ini(prefix + "qn", (DHd,), (HDIM,))
        p["kn"] = ini(prefix + "kn", (DHd,), (HDIM,))
    return p


def init_gqa_cache(spec, rt: RuntimeCfg, batch: int, kv_len: int,
                   window: Optional[int] = None) -> dict:
    """``k`` and ``v`` [B,T,NKV,DH] of ``kv_len`` positions, or of the
    ``window`` last ones where that is shorter (a ring, rewritten whole
    each step).  Also the cross-attention cache, of the encoder's
    positions."""
    t = min(kv_len, window) if window else kv_len
    shape = (batch, t, max(1, spec.n_kv_heads), spec.head_dim)
    return {"k": jnp.zeros(shape, dt(rt.compute_dtype)),
            "v": jnp.zeros(shape, dt(rt.compute_dtype))}


@scoped
def gqa_attention(p: dict, x: jax.Array, spec, rt: RuntimeCfg,
                  rules: Optional[AxisRules], *, positions=None,
                  window: Optional[int] = None, causal: bool = True,
                  cross_kv: Optional[jax.Array] = None,
                  cache: Optional[dict] = None, pos=None,
                  layer=None) -> tuple[jax.Array, Optional[dict]]:
    """Grouped-query attention.  Decoding at ``pos`` (the position of x's
    first token), it writes x's keys and values into its self-attention
    ``cache`` (``init_gqa_cache``) and attends over it; a ``cache`` with
    no ``pos`` holds the cross-attention keys and values, read only.
    With ``layer`` the cache is the stack of a scanned slot's layers and
    this is layer ``layer`` of it.  Returns (x, the new cache or None)."""
    h = rms_norm(p["ln"], x)
    h = constrain(h, rules, (BATCH, SEQ, EMB))
    q = jnp.einsum("bsh,hngd->bsngd", h, cast(p["w_q"].value, rt))
    if p.get("qn") is not None:
        q = rms_norm(p["qn"], q)
    q = constrain(q, rules, (BATCH, SEQ, KV, QGRP, HDIM))

    new_cache = None
    if pos is not None:                          # self-attn decode
        k_new = jnp.einsum("bsh,hnd->bsnd", h, cast(p["w_k"].value, rt))
        v_new = jnp.einsum("bsh,hnd->bsnd", h, cast(p["w_v"].value, rt))
        if p.get("kn") is not None:
            k_new = rms_norm(p["kn"], k_new)
        if positions is None:
            positions = pos + jnp.zeros(x.shape[:2], jnp.int32)
        k_new = rope(k_new, positions)
        q = rope(q, positions)
        klen = cache["k"].shape[-3]
        s_new = x.shape[1]
        if window is not None and klen <= window:
            # ring(-ish) cache for sliding-window layers: shift + append
            old = layer_of(cache, layer)
            k = jnp.concatenate([old["k"][:, s_new:], k_new], axis=1)
            v = jnp.concatenate([old["v"][:, s_new:], v_new], axis=1)
            new_cache = put_layer(cache, {"k": k, "v": v}, layer)
            filled = jnp.minimum(pos + s_new, klen)
            valid = jnp.arange(klen) >= (klen - filled)
            scale = 1.0 / math.sqrt(q.shape[-1])
            s = jnp.einsum("bsngd,bknd->bngsk", q, k).astype(jnp.float32) * scale
            if spec.attn_softcap:
                s = _softcap(s, spec.attn_softcap)
            s = jnp.where(valid[None, None, None, None], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            out5 = jnp.einsum("bngsk,bknd->bsngd", pr, v)
        else:
            new_cache, kv = write_cache(cache, {"k": k_new, "v": v_new}, pos,
                                        1, layer)
            out5 = attn_core(q, kv["k"], kv["v"], rt, causal=True, window=window,
                             softcap=spec.attn_softcap, q_offset=pos)
    elif cache is not None:                      # cached cross-attn (k/v only)
        kv = layer_of(cache, layer)
        new_cache = cache
        out5 = attn_core(q, kv["k"], kv["v"], rt, causal=False, window=None,
                         softcap=spec.attn_softcap)
    else:
        src = cross_kv if cross_kv is not None else h
        k = jnp.einsum("bth,hnd->btnd", src, cast(p["w_k"].value, rt))
        v = jnp.einsum("bth,hnd->btnd", src, cast(p["w_v"].value, rt))
        if p.get("kn") is not None:
            k = rms_norm(p["kn"], k)
        if cross_kv is None:
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
            q, k = rope(q, positions), rope(k, positions)
        out5 = attn_core(q, k, v, rt, causal=causal and cross_kv is None,
                         window=window, softcap=spec.attn_softcap)
    out = jnp.einsum("bsngd,ngdh->bsh", out5, cast(p["w_o"].value, rt))
    return x + constrain(out, rules, (BATCH, SEQ, EMB)), new_cache


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v2)
# ---------------------------------------------------------------------------

def init_mla(ini: Initializer, spec, prefix: str = "") -> dict:
    m = spec.mla
    H, N = spec.d_model, spec.n_heads
    return {
        "ln": ini(prefix + "ln", (H,), (EMB,)),
        "w_dq": ini(prefix + "w_dq", (H, m.q_lora), (EMB, LORA)),
        "ln_q": ini(prefix + "ln_q", (m.q_lora,), (LORA,)),
        "w_uq_n": ini(prefix + "w_uq_n", (m.q_lora, N, m.nope_dim), (LORA, HEADS, HDIM)),
        "w_uq_r": ini(prefix + "w_uq_r", (m.q_lora, N, m.rope_dim), (LORA, HEADS, HDIM)),
        "w_dkv": ini(prefix + "w_dkv", (H, m.kv_lora), (EMB, LORA)),
        "ln_kv": ini(prefix + "ln_kv", (m.kv_lora,), (LORA,)),
        "w_kr": ini(prefix + "w_kr", (H, m.rope_dim), (EMB, HDIM)),
        "w_uk": ini(prefix + "w_uk", (m.kv_lora, N, m.nope_dim), (LORA, HEADS, HDIM)),
        "w_uv": ini(prefix + "w_uv", (m.kv_lora, N, m.v_dim), (LORA, HEADS, HDIM)),
        "w_o": ini(prefix + "w_o", (N, m.v_dim, H), (HEADS, HDIM, EMB),
                   scale=1.0 / np.sqrt(H)),
    }


def init_mla_cache(spec, rt: RuntimeCfg, batch: int, kv_len: int) -> dict:
    """The normed latent ``ckv`` [T,B,kv_lora] and the rotated ``kr``
    [T,B,rope_dim], time major: the layout ``_mla_latent``'s products
    read."""
    m, cdt = spec.mla, dt(rt.compute_dtype)
    return {"ckv": jnp.zeros((kv_len, batch, m.kv_lora), cdt),
            "kr": jnp.zeros((kv_len, batch, m.rope_dim), cdt)}


@scoped
def mla_attention(p: dict, x: jax.Array, spec, rt: RuntimeCfg,
                  rules: Optional[AxisRules], *, positions=None,
                  cache: Optional[dict] = None, pos=None,
                  layer=None) -> tuple[jax.Array, Optional[dict]]:
    """Multi-head latent attention.  Decoding at ``pos`` (the position of
    x's first token), it writes x's latents into ``cache``
    (``init_mla_cache``; with ``layer`` the stack of a scanned slot's
    layers) and attends in the latent space (``mla_decode``); without a
    position (prefill, training) keys and values are expanded per
    head."""
    m = spec.mla
    h = rms_norm(p["ln"], x)
    h = constrain(h, rules, (BATCH, SEQ, EMB))
    cq = rms_norm(p["ln_q"], jnp.einsum("bsh,hr->bsr", h, cast(p["w_dq"].value, rt)))
    qn = jnp.einsum("bsr,rnd->bsnd", cq, cast(p["w_uq_n"].value, rt))
    qr = jnp.einsum("bsr,rnd->bsnd", cq, cast(p["w_uq_r"].value, rt))

    ckv_new = rms_norm(p["ln_kv"], jnp.einsum("bsh,hr->bsr", h, cast(p["w_dkv"].value, rt)))
    kr_new = jnp.einsum("bsh,hd->bsd", h, cast(p["w_kr"].value, rt))
    if positions is None:
        positions = (0 if pos is None else pos) + jnp.broadcast_to(
            jnp.arange(x.shape[1]), x.shape[:2])
    inv_freq = mla_rope(m)
    qr = rope(qr, positions, inv_freq=inv_freq)
    kr_new = rope(kr_new[:, :, None], positions, inv_freq=inv_freq)[:, :, 0]
    scale = mla_softmax_scale(m)
    if pos is not None:
        new_cache, lat = write_cache(
            cache, {"ckv": ckv_new.transpose(1, 0, 2),
                    "kr": kr_new.transpose(1, 0, 2)}, pos, 0, layer)
        with jax.named_scope("mla_decode"):
            ctx = _mla_latent(p, qn, qr, lat["ckv"], lat["kr"], pos, scale, rt)
    else:
        kn = jnp.einsum("btr,rnd->btnd", ckv_new, cast(p["w_uk"].value, rt))
        vv = jnp.einsum("btr,rnd->btnd", ckv_new, cast(p["w_uv"].value, rt))
        # nope+rope as one head dim; the rope key is shared by all heads
        qq = jnp.concatenate([qn, qr], axis=-1)[:, :, :, None, :]   # [B,S,N,1,D]
        kk = jnp.concatenate([kn, jnp.broadcast_to(
            kr_new[:, :, None], kr_new.shape[:2] + (kn.shape[2], m.rope_dim))],
            axis=-1)
        ctx = attn_core(qq, kk, vv, rt, causal=True, scale=scale)[:, :, :, 0]
        new_cache = None
    out = jnp.einsum("bsnd,ndh->bsh", ctx, cast(p["w_o"].value, rt))
    return x + constrain(out, rules, (BATCH, SEQ, EMB)), new_cache


def _mla_latent(p: dict, qn, qr, ckv, kr, pos, scale: float,
                rt: RuntimeCfg) -> jax.Array:
    """Attention against the latent cache: the query absorbs ``w_uk``,
    scores are (q_nope W_uk) . ckv + q_rope . kr, the weighted sum of
    ``ckv`` goes through ``w_uv`` after.  ckv [T,B,R] and kr [T,B,Dr]
    (time major) are read once; no per-head key or value is formed.
    Positions past ``pos`` + the query's own are masked."""
    q_lat = jnp.einsum("bsnd,rnd->bsnr", qn, cast(p["w_uk"].value, rt))
    f32 = jnp.float32
    # the cache is the scores' first operand, so that both products read
    # it in its own layout (R minor) and the step relays none of it out
    s = (jnp.einsum("tbr,bsnr->bstn", ckv, q_lat, preferred_element_type=f32)
         + jnp.einsum("tbd,bsnd->bstn", kr, qr, preferred_element_type=f32)) * scale
    qpos = pos + jnp.arange(qn.shape[1])
    seen = jnp.arange(ckv.shape[0])[None, :] <= qpos[:, None]       # [S, T]
    s = jnp.where(seen[:, :, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=2).astype(ckv.dtype)
    ctx = jnp.einsum("bstn,tbr->bsnr", pr, ckv)
    return jnp.einsum("bsnr,rnd->bsnd", ctx, cast(p["w_uv"].value, rt))


# ---------------------------------------------------------------------------
# FFN / MoE
# ---------------------------------------------------------------------------

def init_ffn(ini: Initializer, spec, width: Optional[int] = None,
             prefix: str = "", gated: Optional[bool] = None) -> dict:
    H = spec.d_model
    f = width or spec.d_ff
    gated = spec.gated_ffn if gated is None else gated
    p = {
        "ln": ini(prefix + "ln_f", (H,), (EMB,)),
        "w_up": ini(prefix + "w_up", (H, f), (EMB, FFN)),
        "w_down": ini(prefix + "w_down", (f, H), (FFN, EMB), scale=1.0 / np.sqrt(f)),
    }
    if gated:
        p["w_gate"] = ini(prefix + "w_gate", (H, f), (EMB, FFN))
    return p


@scoped
def ffn(p: dict, x: jax.Array, spec, rt: RuntimeCfg,
        rules: Optional[AxisRules]) -> jax.Array:
    h = rms_norm(p["ln"], x)
    h = constrain(h, rules, (BATCH, SEQ, EMB))
    up = jnp.einsum("bsh,hf->bsf", h, cast(p["w_up"].value, rt))
    if "w_gate" in p:
        gate = jnp.einsum("bsh,hf->bsf", h, cast(p["w_gate"].value, rt))
        act = jax.nn.silu(gate) * up
    else:
        act = jax.nn.gelu(up)
    act = constrain(act, rules, (BATCH, SEQ, FFN))
    down = jnp.einsum("bsf,fh->bsh", act, cast(p["w_down"].value, rt))
    return x + constrain(down, rules, (BATCH, SEQ, EMB))


EXPERT_WEIGHTS = ("w_egate", "w_eup", "w_edown")


def init_moe(ini: Initializer, spec, prefix: str = "") -> dict:
    """The router over all ``n_experts``; the ``held`` experts' weights."""
    H = spec.d_model
    mo = spec.moe
    p = {
        "ln": ini(prefix + "ln_moe", (H,), (EMB,)),
        "w_router": ini(prefix + "w_router", (H, mo.n_experts), (EMB, "router"),
                        dtype=jnp.float32),
        "w_egate": ini(prefix + "w_egate", (mo.held, H, mo.d_expert),
                       (EXP, EMB, FFN)),
        "w_eup": ini(prefix + "w_eup", (mo.held, H, mo.d_expert),
                     (EXP, EMB, FFN)),
        "w_edown": ini(prefix + "w_edown", (mo.held, mo.d_expert, H),
                       (EXP, FFN, EMB), scale=1.0 / np.sqrt(mo.d_expert)),
    }
    if mo.n_shared:
        sw = mo.n_shared * mo.d_expert
        p["shared"] = init_ffn(ini, spec, width=sw, prefix=prefix + "sh_", gated=True)
    return p


def route(h: jax.Array, wr: jax.Array, mo) -> tuple[jax.Array, jax.Array]:
    """Gates [T,K] and experts [T,K] of tokens ``h`` [T,H]: softmax over
    all experts (router in float32); with ``topk_group`` < ``n_group``
    only the ``topk_group`` groups with the best single scores stay
    (group_limited_greedy); top ``top_k`` of what stays; gates
    renormalized if ``norm_topk``, else times ``routed_scale``."""
    probs = jax.nn.softmax(jnp.einsum("th,he->te", h.astype(jnp.float32), wr),
                           axis=-1)
    G = mo.n_group
    if 0 < mo.topk_group < G:
        t, e = probs.shape
        best = probs.reshape(t, G, e // G).max(-1)                  # [T,G]
        _, gi = jax.lax.top_k(best, mo.topk_group)
        keep = jax.nn.one_hot(gi, G, dtype=jnp.bool_).any(1)        # [T,G]
        probs = jnp.where(jnp.repeat(keep, e // G, axis=1), probs, 0.0)
    gates, idx = jax.lax.top_k(probs, mo.top_k)
    if mo.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    else:
        gates = gates * mo.routed_scale
    return gates, idx


def _held(idx: jax.Array, mo) -> tuple[jax.Array, jax.Array]:
    """Each assignment's expert among the held ones, ``held`` where this
    layer does not hold it; and the tokens routed to each held expert."""
    local = idx - mo.held_group * mo.held
    mine = (local >= 0) & (local < mo.held)
    local = jnp.where(mine, local, mo.held).reshape(-1)
    counts = jnp.bincount(local, length=mo.held + 1)[:mo.held]
    return local, counts.astype(jnp.int32)


def _held_experts(h: jax.Array, gates, idx, wg, wu, wd, mo,
                  layer=None) -> tuple:
    """The held experts' part of the result for the tokens [T,H] routed
    to them, dropless: assignments sorted by expert, one ragged matmul
    per projection over the held assignments only.  Returns that part
    and the tokens routed to each held expert.

    With ``layer`` the expert weights are the stack of all MoE layers
    [layers, held, ...] and this is layer ``layer`` of it: the ragged
    matmuls take the whole stack with the other layers' groups empty,
    so no layer's experts are sliced out of it (a copy) first."""
    T, K = idx.shape
    local, counts = _held(idx, mo)
    groups, sizes = mo.held, counts
    if layer is not None:
        groups = wg.shape[0] * mo.held
        wg, wu, wd = (w.reshape((groups,) + w.shape[2:]) for w in (wg, wu, wd))
        local = jnp.where(local < mo.held, local + layer * mo.held, groups)
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros(groups, jnp.int32), counts, (layer * mo.held,))
    order = jnp.argsort(local)
    grp, tok = local[order], order // K
    xs = h[tok]                                          # [T*K, H]
    act = jax.nn.silu(jax.lax.ragged_dot(xs, wg, sizes)) \
        * jax.lax.ragged_dot(xs, wu, sizes)
    eo = jax.lax.ragged_dot(act, wd, sizes)
    g = gates.reshape(-1)[order].astype(h.dtype)
    eo = jnp.where((grp < groups)[:, None], eo * g[:, None], 0)
    return jnp.zeros_like(h).at[tok].add(eo), counts


def _route_and_compute(h, wr, wg, wu, wd, *, mo, capacity_factor: float,
                       a2a_axis: str, gather_axes: tuple = (),
                       count_axes: tuple = ()):
    """Expert-parallel block, run under ``shard_map``: local routing,
    capacity dispatch of the held experts' assignments, the explicit
    ``jax.lax.all_to_all`` pair over the expert axis (the EP pattern the
    STG matcher predicts, Table IV), the expert matmuls on this shard's
    slice [E_loc, H, F] of the held experts, and the combine.  Returns
    the result for this shard's tokens [b_loc, s, H] and the tokens
    routed to each held expert over all shards (``count_axes``: the mesh
    axes the tokens are split over)."""
    b, s, H = h.shape
    if gather_axes:
        # expert weights stored ZeRO-3-sharded over the data axes; gather
        # the full expert slice just-in-time (FSDP inside the EP block)
        wg = jax.lax.all_gather(wg, gather_axes, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, gather_axes, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, gather_axes, axis=1, tiled=True)
    T, Kk, E = b * s, mo.top_k, mo.held
    gates, idx = route(h.reshape(T, H), wr, mo)
    gates = gates.astype(h.dtype)
    flat_idx, counts = _held(idx, mo)
    if count_axes:
        counts = jax.lax.psum(counts, count_axes)

    C = max(1, int(math.ceil(T * Kk / E * capacity_factor)))
    flat_tok = jnp.repeat(jnp.arange(T), Kk)
    order = jnp.argsort(flat_idx)
    se, st = flat_idx[order], flat_tok[order]
    same = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            (se[1:] == se[:-1]).astype(jnp.int32)])
    seg_start = jnp.where(same == 0, jnp.arange(T * Kk), 0)
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start)
    rank = jnp.arange(T * Kk) - seg_start
    keep = (rank < C) & (se < E)
    hx = h.reshape(T, H)
    dispatched = jnp.zeros((E, C, H), h.dtype)
    dispatched = dispatched.at[jnp.where(keep, se, 0),
                               jnp.where(keep, rank, 0)].add(
        jnp.where(keep[:, None], hx[st], 0))

    ep = jax.lax.axis_size(a2a_axis)
    e_loc = E // ep
    # send each expert-group's tokens to its owner; receive everyone's
    d4 = dispatched.reshape(ep, e_loc, C, H)
    d4 = jax.lax.all_to_all(d4, a2a_axis, split_axis=0, concat_axis=2,
                            tiled=True)
    dispatched = d4.reshape(e_loc, ep * C, H)

    eg = jnp.einsum("ech,ehf->ecf", dispatched, wg)
    eu = jnp.einsum("ech,ehf->ecf", dispatched, wu)
    ea = jax.nn.silu(eg) * eu
    eo = jnp.einsum("ecf,efh->ech", ea, wd)

    y4 = eo.reshape(e_loc, ep, C, H)
    y4 = jax.lax.all_to_all(y4, a2a_axis, split_axis=1, concat_axis=0,
                            tiled=True)
    eo = y4.reshape(E, C, H)

    flat_gate = gates.reshape(T * Kk)[order]
    token_out = jnp.zeros((T, H), h.dtype)
    token_out = token_out.at[st].add(
        jnp.where(keep[:, None], eo[jnp.minimum(se, E - 1),
                                    jnp.minimum(rank, C - 1)]
                  * flat_gate[:, None], 0))
    return token_out.reshape(b, s, H), counts


@scoped
def moe_ffn(p: dict, x: jax.Array, spec, rt: RuntimeCfg,
            rules: Optional[AxisRules], *, with_counts: bool = False,
            layer=None):
    """Routed experts (``route``) plus the shared experts once.

    On one device the held experts' product is dropless
    (``_held_experts``).  With a mesh attached to ``rules`` the block
    runs under ``shard_map``: tokens stay local to their data shard,
    the held experts are sharded over the expert (model) axis with a
    static capacity per expert (``rt.moe_capacity``), and
    dispatch/combine are explicit AllToAlls — the production EP pattern
    (and the one the STG matcher emits).  ``with_counts`` also returns
    the tokens routed to each held expert [held] (int32).  ``layer``: the
    expert weights in ``p`` are the stack of all MoE layers and this is
    layer ``layer`` of it."""
    mo = spec.moe
    b, s, H = x.shape
    h = rms_norm(p["ln"], x)
    h = constrain(h, rules, (BATCH, SEQ, EMB))
    wr = p["w_router"].value
    wg, wu, wd = (cast(p[k].value, rt) for k in EXPERT_WEIGHTS)

    mesh = getattr(rules, "mesh", None) if rules is not None else None
    ep_axis = rules.rules.get("experts") if rules is not None else None
    if mesh is not None and ep_axis in getattr(mesh, "shape", {}) \
            and mo.held % mesh.shape[ep_axis] == 0 \
            and mesh.shape[ep_axis] > 1:
        from jax.sharding import PartitionSpec as P
        if layer is not None:
            wg, wu, wd = wg[layer], wu[layer], wd[layer]
        from jax import shard_map
        da = rules.rules.get("act_batch") or ()
        da = tuple(a for a in (da if isinstance(da, (tuple, list)) else (da,))
                   if a in mesh.shape)
        deg = int(np.prod([mesh.shape[a] for a in da])) if da else 1
        ep = mesh.shape[ep_axis]
        # tokens: batch over data axes; sequence over the expert axis too
        # (otherwise every expert-axis peer routes identical tokens)
        if da and b % deg == 0 and s % ep == 0 and s > 1:
            bspec, count_axes = P(da, ep_axis), da + (ep_axis,)
        elif da and b % deg == 0:
            bspec, count_axes = P(da), da
        else:
            bspec, count_axes = P(), ()
        # expert weights: experts over the ep axis + ZeRO-3 over data axes
        gather = da if all(w.shape[1] % deg == 0
                           for w in (wg, wu)) and da else ()
        wspec = P(ep_axis, gather if gather else None)
        if gather:
            wg = jax.lax.with_sharding_constraint(
                wg, jax.sharding.NamedSharding(mesh, wspec))
        with jax.named_scope("moe_experts"):
            fn = shard_map(
                functools.partial(_route_and_compute, mo=mo,
                                  capacity_factor=rt.moe_capacity,
                                  a2a_axis=ep_axis, gather_axes=gather,
                                  count_axes=count_axes),
                mesh=mesh,
                in_specs=(bspec, P(), wspec, wspec, wspec),
                out_specs=(bspec, P()), check_vma=False)
            out, counts = fn(h, wr, wg, wu, wd)
    else:
        hx = h.reshape(b * s, H)
        with jax.named_scope("moe_route"):
            gates, idx = route(hx, wr, mo)
        with jax.named_scope("moe_experts"):
            out, counts = _held_experts(hx, gates, idx, wg, wu, wd, mo,
                                        layer)
        out = out.reshape(b, s, H)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            hs = jnp.einsum("bsh,hf->bsf", h, cast(p["shared"]["w_gate"].value, rt))
            hu = jnp.einsum("bsh,hf->bsf", h, cast(p["shared"]["w_up"].value, rt))
            so = jnp.einsum("bsf,fh->bsh", jax.nn.silu(hs) * hu,
                            cast(p["shared"]["w_down"].value, rt))
        out = out + so
    out = x + constrain(out, rules, (BATCH, SEQ, EMB))
    return (out, counts) if with_counts else out


# ---------------------------------------------------------------------------
# Mamba (selective SSM) — chunked scan with O(1) carried state
# ---------------------------------------------------------------------------

def init_mamba(ini: Initializer, spec, prefix: str = "") -> dict:
    H = spec.d_model
    ss = spec.ssm
    din = ss.expand * H
    dtr = ss.dt_rank or H // 16
    return {
        "ln": ini(prefix + "ln_ssm", (H,), (EMB,)),
        "w_in": ini(prefix + "w_in", (H, 2 * din), (EMB, FFN)),
        "conv": ini(prefix + "conv", (4, din), ("conv", FFN), scale=0.5),
        "w_xdb": ini(prefix + "w_xdb", (din, dtr + 2 * ss.d_state), (FFN, LORA)),
        "w_dt": ini(prefix + "w_dt", (dtr, din), (LORA, FFN)),
        "A_log": ini(prefix + "A_log", (din, ss.d_state), (FFN, "state"),
                     scale=1.0, dtype=jnp.float32),
        "D": ini(prefix + "D", (din,), (FFN,)),
        "w_out": ini(prefix + "w_out", (din, H), (FFN, EMB), scale=1.0 / np.sqrt(din)),
    }


def init_mamba_cache(spec, rt: RuntimeCfg, batch: int) -> dict:
    """The last 3 conv inputs [B,3,Din] and the SSM state [B,Din,P],
    rewritten whole each step."""
    din = spec.ssm.expand * spec.d_model
    return {"conv": jnp.zeros((batch, 3, din), dt(rt.compute_dtype)),
            "ssm": jnp.zeros((batch, din, spec.ssm.d_state), jnp.float32)}


def _ssm_scan(dA: jax.Array, dBx: jax.Array, h0: jax.Array,
              chunk: int) -> tuple[jax.Array, jax.Array]:
    """h_t = dA_t * h_{t-1} + dBx_t over axis 1; returns (all h, last h).

    dA/dBx: [B, S, D, P]; h0 [B, D, P].  lax.scan over chunks keeps live
    memory O(B·chunk·D·P)."""
    b, s, d_, p_ = dA.shape
    nchunks = max(1, s // chunk) if s % chunk == 0 else 1
    if s % chunk != 0:
        chunk = s
        nchunks = 1
    dAc = dA.reshape(b, nchunks, chunk, d_, p_).transpose(1, 0, 2, 3, 4)
    dBxc = dBx.reshape(b, nchunks, chunk, d_, p_).transpose(1, 0, 2, 3, 4)

    def chunk_body(h, inp):
        a, x = inp                                # [B,C,D,P]
        def combine(c1, c2):
            a1, x1 = c1
            a2, x2 = c2
            return a1 * a2, x1 * a2 + x2
        aa, xx = jax.lax.associative_scan(combine, (a, x), axis=1)
        hs = xx + aa * h[:, None]
        return hs[:, -1], hs

    h_last, hs = jax.lax.scan(chunk_body, h0, (dAc, dBxc))
    hs = hs.transpose(1, 0, 2, 3, 4).reshape(b, s, d_, p_)
    return hs, h_last


@scoped
def mamba_layer(p: dict, x: jax.Array, spec, rt: RuntimeCfg,
                rules: Optional[AxisRules], *, cache: Optional[dict] = None,
                layer=None) -> tuple[jax.Array, Optional[dict]]:
    """Selective SSM.  Decoding, it continues from ``cache``
    (``init_mamba_cache``; with ``layer`` the stack of a scanned slot's
    layers) and returns it with this layer's state after x."""
    ss = spec.ssm
    b, s, H = x.shape
    din = ss.expand * H
    dtr = ss.dt_rank or H // 16
    h = rms_norm(p["ln"], x)
    h = constrain(h, rules, (BATCH, SEQ, EMB))
    xz = jnp.einsum("bsh,hi->bsi", h, cast(p["w_in"].value, rt))
    xs, z = xz[..., :din], xz[..., din:]

    conv_w = cast(p["conv"].value, rt)
    mine = None if cache is None else layer_of(cache, layer)
    if mine is not None:
        prev = mine["conv"]                        # [B, 3, Din]
        xpad = jnp.concatenate([prev, xs], axis=1)
        new_conv = xpad[:, -3:]
    else:
        xpad = jnp.pad(xs, ((0, 0), (3, 0), (0, 0)))
        new_conv = xpad[:, -3:]
    xc = sum(xpad[:, i:i + s] * conv_w[i] for i in range(4))
    xc = jax.nn.silu(xc)

    xdb = jnp.einsum("bsi,ir->bsr", xc, cast(p["w_xdb"].value, rt))
    dt0, Bt, Ct = (xdb[..., :dtr], xdb[..., dtr:dtr + ss.d_state],
                   xdb[..., dtr + ss.d_state:])
    dtt = jax.nn.softplus(jnp.einsum("bsr,ri->bsi", dt0, cast(p["w_dt"].value, rt))
                          .astype(jnp.float32))
    A = -jnp.exp(p["A_log"].value)                  # [Din, P]
    dA = jnp.exp(dtt[..., None] * A[None, None])    # [B,S,Din,P]
    dBx = (dtt * xc.astype(jnp.float32))[..., None] * Bt[:, :, None, :].astype(jnp.float32)
    h0 = mine["ssm"] if mine is not None else jnp.zeros((b, din, ss.d_state),
                                                        jnp.float32)
    hs, h_last = _ssm_scan(dA, dBx, h0, chunk=min(s, 256))
    y = jnp.einsum("bsip,bsp->bsi", hs, Ct.astype(jnp.float32)).astype(x.dtype)
    y = y + xc * cast(p["D"].value, rt)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bsi,ih->bsh", y, cast(p["w_out"].value, rt))
    new_cache = None if cache is None else put_layer(
        cache, {"conv": new_conv, "ssm": h_last}, layer)
    return x + constrain(out, rules, (BATCH, SEQ, EMB)), new_cache


# ---------------------------------------------------------------------------
# RWKV6 (Finch) — chunked linear attention with data-dependent decay
# ---------------------------------------------------------------------------

def init_rwkv6(ini: Initializer, spec, prefix: str = "") -> dict:
    H = spec.d_model
    nh, dh = spec.n_heads, spec.head_dim
    rk = spec.rwkv_decay_rank
    p = {"ln": ini(prefix + "ln_tm", (H,), (EMB,)),
         "u": ini(prefix + "u", (nh, dh), (HEADS, HDIM), scale=1.0)}
    for nm in ("r", "k", "v", "g"):
        p[f"mu_{nm}"] = ini(prefix + f"mu_{nm}", (H,), (EMB,), scale=1.0)
        p[f"w_{nm}"] = ini(prefix + f"w_{nm}", (H, nh, dh), (EMB, HEADS, HDIM))
    p["mu_w"] = ini(prefix + "mu_w", (H,), (EMB,), scale=1.0)
    p["w_dec1"] = ini(prefix + "w_dec1", (H, rk), (EMB, LORA))
    p["w_dec2"] = ini(prefix + "w_dec2", (rk, nh, dh), (LORA, HEADS, HDIM))
    p["gn"] = ini(prefix + "gn", (dh,), (HDIM,))
    p["w_tmo"] = ini(prefix + "w_tmo", (nh, dh, H), (HEADS, HDIM, EMB),
                     scale=1.0 / np.sqrt(H))
    # channel mix
    p["ln_cm"] = ini(prefix + "ln_cm", (H,), (EMB,))
    p["mu_ck"] = ini(prefix + "mu_ck", (H,), (EMB,), scale=1.0)
    p["mu_cr"] = ini(prefix + "mu_cr", (H,), (EMB,), scale=1.0)
    p["w_ck"] = ini(prefix + "w_ck", (H, spec.d_ff), (EMB, FFN))
    p["w_cv"] = ini(prefix + "w_cv", (spec.d_ff, H), (FFN, EMB),
                    scale=1.0 / np.sqrt(spec.d_ff))
    p["w_cr"] = ini(prefix + "w_cr", (H, H), (EMB, EMB))
    return p


def init_rwkv6_cache(spec, rt: RuntimeCfg, batch: int) -> dict:
    """The WKV state [B,N,D,D] and the last normed inputs of the time and
    channel mixes [B,H], rewritten whole each step."""
    nh, dh, cdt = spec.n_heads, spec.head_dim, dt(rt.compute_dtype)
    return {"wkv": jnp.zeros((batch, nh, dh, dh), jnp.float32),
            "shift_tm": jnp.zeros((batch, spec.d_model), cdt),
            "shift_cm": jnp.zeros((batch, spec.d_model), cdt)}


def _token_shift(x: jax.Array, prev: Optional[jax.Array]) -> jax.Array:
    """x_{t-1} stream ([B,S,H]); ``prev`` is the carried last token."""
    if prev is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return jnp.concatenate([prev[:, None], x], axis=1)[:, :-1]


def _wkv_chunk(r, k, v, w, u, state):
    """One chunk of RWKV6: r/k/v/w [B,C,N,D] (w = decay in (0,1)),
    state [B,N,D,D] -> (out [B,C,N,D], new state).

    The intra-chunk term factorizes the pairwise decay
    ``exp(Σ_{j<l<=t} log w_l)`` as ``exp(cum_t)·exp(-cum_j)``; to keep the
    positive exponent finite the per-step log-decay is floored at
    ``-80/C`` *for the factorization only* (exact whenever decays are
    milder than e^{-80/C}/step; stronger decays saturate at e^{-80},
    i.e. 0 in fp32 terms).  State decay uses the true (unfloored) value."""
    C = r.shape[1]
    lw = jnp.log(jnp.maximum(w, 1e-30))                   # [B,C,N,D], true
    cum = jnp.cumsum(lw, axis=1)                          # inclusive
    cum_excl = cum - lw
    # inter-chunk: r_t · (decay-to-t ∘ state)  — exponent <= 0, stable
    r_dec = r * jnp.exp(cum_excl)
    inter = jnp.einsum("bcnd,bnde->bcne", r_dec, state)
    # intra-chunk: s_tj = sum_d r_td k_jd exp(cum_excl_t - cum_j)  (j < t)
    lwc = jnp.maximum(lw, -80.0 / C)
    cumc = jnp.cumsum(lwc, axis=1)
    rt = r * jnp.exp(cumc - lwc)
    kt = k * jnp.exp(-cumc)
    s = jnp.einsum("bcnd,bjnd->bncj", rt, kt)
    cix = jnp.arange(C)
    mask = cix[:, None] > cix[None, :]
    s = jnp.where(mask[None, None], s, 0.0)
    intra = jnp.einsum("bncj,bjne->bcne", s, v)
    # current-token bonus
    bonus = jnp.einsum("bcnd,bcnd,bcne->bcne", r, u[None, None] * k, v)
    out = inter + intra + bonus
    # state update: S' = decay_total ∘ S + sum_j (k_j decay_{j->end})^T v_j
    total = cum[:, -1]                                    # [B,N,D]
    kdec = k * jnp.exp(total[:, None] - cum)
    upd = jnp.einsum("bjnd,bjne->bnde", kdec, v)
    new_state = state * jnp.exp(total)[..., None] + upd
    return out, new_state


@scoped
def rwkv6_time_mix(p: dict, x: jax.Array, spec, rt: RuntimeCfg,
                   rules: Optional[AxisRules], *, chunk: int,
                   cache: Optional[dict]) -> tuple:
    """Time mix: token shift, r/k/v/g and decay projections, the chunked
    WKV scan, group norm and output.  Returns (x, WKV state after the
    last token, the normed input ``h``)."""
    b, s, H = x.shape
    nh, dh = spec.n_heads, spec.head_dim
    h = rms_norm(p["ln"], x)
    h = constrain(h, rules, (BATCH, SEQ, EMB))
    shifted = _token_shift(h, cache["shift_tm"] if cache is not None else None)

    def mix(nm):
        mu = cast(p[f"mu_{nm}"].value, rt)
        return h + (shifted - h) * mu

    r = jnp.einsum("bsh,hnd->bsnd", mix("r"), cast(p["w_r"].value, rt)).astype(jnp.float32)
    k = jnp.einsum("bsh,hnd->bsnd", mix("k"), cast(p["w_k"].value, rt)).astype(jnp.float32)
    v = jnp.einsum("bsh,hnd->bsnd", mix("v"), cast(p["w_v"].value, rt)).astype(jnp.float32)
    g = jnp.einsum("bsh,hnd->bsnd", mix("g"), cast(p["w_g"].value, rt))
    d1 = jnp.einsum("bsh,hr->bsr", mix("w"), cast(p["w_dec1"].value, rt))
    dec = jnp.einsum("bsr,rnd->bsnd", d1, cast(p["w_dec2"].value, rt)).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(dec))                             # (0,1) decay

    state0 = cache["wkv"] if cache is not None \
        else jnp.zeros((b, nh, dh, dh), jnp.float32)
    cs = min(chunk, s)
    nchunks = s // cs if s % cs == 0 else 1
    if s % cs != 0:
        cs, nchunks = s, 1
    u = p["u"].value.astype(jnp.float32)

    def body(state, inp):
        rc, kc, vc, wc = inp
        out, st = _wkv_chunk(rc, kc, vc, wc, u, state)
        return st, out

    resh = lambda t: t.reshape(b, nchunks, cs, nh, dh).transpose(1, 0, 2, 3, 4)
    state_last, outs = jax.lax.scan(body, state0, (resh(r), resh(k), resh(v), resh(w)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, nh, dh).astype(x.dtype)

    out = rms_norm(p["gn"], out)                           # per-head groupnorm
    out = out * jax.nn.silu(g)
    tm = jnp.einsum("bsnd,ndh->bsh", out, cast(p["w_tmo"].value, rt))
    return x + constrain(tm, rules, (BATCH, SEQ, EMB)), state_last, h


@scoped
def rwkv6_channel_mix(p: dict, x: jax.Array, rt: RuntimeCfg,
                      rules: Optional[AxisRules], *,
                      cache: Optional[dict]) -> tuple:
    """Channel mix: token shift, squared-ReLU key, sigmoid receptance.
    Returns (x, the normed input ``hc``)."""
    hc = rms_norm(p["ln_cm"], x)
    shifted_c = _token_shift(hc, cache["shift_cm"] if cache is not None else None)
    mk = hc + (shifted_c - hc) * cast(p["mu_ck"].value, rt)
    mr = hc + (shifted_c - hc) * cast(p["mu_cr"].value, rt)
    kk = jnp.einsum("bsh,hf->bsf", mk, cast(p["w_ck"].value, rt))
    kk = jnp.square(jax.nn.relu(kk))
    vv = jnp.einsum("bsf,fh->bsh", kk, cast(p["w_cv"].value, rt))
    rr = jax.nn.sigmoid(jnp.einsum("bsh,hg->bsg", mr, cast(p["w_cr"].value, rt)))
    return x + constrain(vv * rr, rules, (BATCH, SEQ, EMB)), hc


def rwkv6_layer(p: dict, x: jax.Array, spec, rt: RuntimeCfg,
                rules: Optional[AxisRules], *, chunk: int = 32,
                cache: Optional[dict] = None,
                layer=None) -> tuple[jax.Array, Optional[dict]]:
    """RWKV-6 time and channel mix.  Decoding, it continues from
    ``cache`` (``init_rwkv6_cache``; with ``layer`` the stack of a scanned
    slot's layers) and returns it with this layer's state after x."""
    mine = None if cache is None else layer_of(cache, layer)
    x, state_last, h = rwkv6_time_mix(p, x, spec, rt, rules, chunk=chunk,
                                      cache=mine)
    x, hc = rwkv6_channel_mix(p, x, rt, rules, cache=mine)
    if cache is None:
        return x, None
    return x, put_layer(cache, {"wkv": state_last, "shift_tm": h[:, -1],
                                "shift_cm": hc[:, -1]}, layer)
