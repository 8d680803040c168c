"""Pallas TPU kernel for the RWKV6 (Finch) WKV recurrence.

TPU adaptation of the CUDA wkv6 kernel: instead of per-thread registers
holding one head's state, the [D, D] per-head state lives in VMEM
scratch and is carried across a *sequential* time-chunk grid dimension.
All within-chunk work is phrased as dense [C,C]/[C,D] matmuls (cumsums
via a lower-triangular ones matrix) so the MXU does the heavy lifting —
the GPU kernel's warp-level scan has no TPU analogue, and this
chunked-matmul form is the TPU-native equivalent.

Semantics (matching ``repro.kernels.ref.ref_wkv``):
    out_t  = r_t · (S_{t-1} + diag(u) k_t^T v_t)
    S_t    = diag(w_t) S_{t-1} + k_t^T v_t
with data-dependent decay w in (0,1).  The intra-chunk pairwise decay is
factorized with a per-step log-decay floor of -80/C (exact unless a
single-step decay is stronger than e^{-80/C}; such contributions are
<= e^-80 anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sout_ref,
                state_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    C = chunk
    r = r_ref[0, 0].astype(jnp.float32)                   # [C, D]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                      # [1, D]

    lw = jnp.log(jnp.maximum(w, 1e-30))
    ii = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    lt_incl = (jj <= ii).astype(jnp.float32)              # inclusive lower-tri
    cum = jax.lax.dot_general(lt_incl, lw, (((1,), (0,)), ((), ())))
    cum_excl = cum - lw

    state = state_scr[...]
    inter = jax.lax.dot_general(r * jnp.exp(cum_excl), state,
                                (((1,), (0,)), ((), ())))
    lwc = jnp.maximum(lw, -80.0 / C)
    cumc = jax.lax.dot_general(lt_incl, lwc, (((1,), (0,)), ((), ())))
    rt = r * jnp.exp(cumc - lwc)
    kt = k * jnp.exp(-cumc)
    s = jax.lax.dot_general(rt, kt, (((1,), (1,)), ((), ())))   # [C, C]
    s = jnp.where(jj < ii, s, 0.0)
    intra = jax.lax.dot_general(s, v, (((1,), (0,)), ((), ())))
    coef = jnp.sum(r * u * k, axis=1, keepdims=True)
    out = inter + intra + coef * v
    o_ref[0, 0] = out.astype(o_ref.dtype)

    total = cum[C - 1:C, :]                               # [1, D]
    kdec = k * jnp.exp(total - cum)
    state_scr[...] = state * jnp.exp(total)[0][:, None] \
        + jax.lax.dot_general(kdec, v, (((0,), (0,)), ((), ())))

    @pl.when(ci == nc - 1)
    def _fin():
        sout_ref[0, 0] = state_scr[...]


def wkv6_bhsd(r, k, v, w, u, state0, *, chunk: int = 64,
              interpret: bool = False):
    """RWKV6 scan on [B, H, S, D] tensors; u [H, D]; state0 [B, H, D, D].

    Returns (out [B,H,S,D] fp32, final state [B,H,D,D] fp32)."""
    b, h, s, d = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, "sequence must divide the chunk size"
    pad_d = (-d) % 128
    if pad_d:
        padseq = ((0, 0), (0, 0), (0, 0), (0, pad_d))
        r, k, v = (jnp.pad(t, padseq) for t in (r, k, v))
        w = jnp.pad(w, padseq, constant_values=1.0)       # pad decay = 1
        u = jnp.pad(u, ((0, 0), (0, pad_d)))
        state0 = jnp.pad(state0, ((0, 0), (0, 0), (0, pad_d), (0, pad_d)))
    dd = d + pad_d
    nc = s // chunk

    kern = functools.partial(_wkv_kernel, chunk=chunk)
    out, sout = pl.pallas_call(
        kern,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, dd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, dd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, dd), lambda b_, h_, c: (b_, h_, c, 0)),
            # u as [H, 1, dd]: the last two block dims then equal the
            # array's, as the TPU tiling rule requires
            pl.BlockSpec((1, 1, dd), lambda b_, h_, c: (h_, 0, 0)),
            pl.BlockSpec((1, 1, dd, dd), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, dd, dd), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, dd, dd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dd, dd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, dd), state0)
    return out[..., :d], sout[..., :d, :d]
