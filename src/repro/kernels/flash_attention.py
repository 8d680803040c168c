"""Pallas TPU flash-attention kernel (online softmax, MXU-aligned tiles).

TPU adaptation of the memory-hierarchy insight behind FlashAttention:
instead of CUDA shared-memory tiling, q/k/v blocks are staged
HBM->VMEM via BlockSpecs with 128-multiple tile edges so the 128x128 MXU
runs dense;  the kv axis is the innermost *sequential* grid dimension
("arbitrary" semantics) with the softmax running-max/sum/accumulator
carried in VMEM scratch across kv steps.

Layout: q [B, H, Sq, D], k/v [B, H, Sk, D] -> out [B, H, Sq, D].
Causal/window masking and gemma-style softcap are fused in-kernel.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: Optional[int],
                 softcap: Optional[float], block_q: int, block_k: int,
                 seq_k: int, q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                  # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ()))).astype(jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)) \
            .astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: Optional[int] = None,
                         softcap: Optional[float] = None, q_offset: int = 0,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    """Flash attention on [B, H, S, D] tensors (D padded to 128 inside)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, max(8, sq))
    block_k = min(block_k, max(128, 1))

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    pad_d = (-d) % 128
    if pad_q or pad_d:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, pad_d)))
    if pad_k or pad_d:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, pad_d)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, pad_d)))
    bq, bk, dd = block_q, block_k, d + pad_d
    nq, nk = q.shape[2] // bq, k.shape[2] // bk

    kern = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=bq, block_k=bk, seq_k=sk, q_offset=q_offset)
    out = pl.pallas_call(
        kern,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dd), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, dd), lambda b_, h_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, dd), lambda b_, h_, i, j: (b_, h_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dd), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max
            pltpu.VMEM((bq, 1), jnp.float32),      # running sum
            pltpu.VMEM((bq, dd), jnp.float32),     # output accum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq, :d]
