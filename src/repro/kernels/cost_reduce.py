"""Pallas TPU cost-reduction kernel for the batched DSE backend.

The batched evaluator (repro.core.batched) turns "sum local tensor
bytes over a node's accessed set" into a dense contraction
``out[b, e] = sum_t x[b, t] * w[e, t]`` — a [B, T] x [E, T]^T matmul
where B is the config-batch and T the structure class's tensor table.
That reduction dominates the per-batch cost once B x E is large, so it
is tiled for the 128x128 MXU here: batch and entry axes are parallel
grid dimensions, the tensor axis is the innermost sequential one with a
``pl.when(k == 0)`` zero-init accumulate into the output block.

On CPU/CI the interpreter mode of this same kernel is the reference
(tests pin it against the jnp dot); the public wrapper in ops.py picks
the compiled kernel only on TPU.  The batched evaluator itself does not
call it: it accumulates in float32, and the evaluator's 1e-6 parity
budget needs float64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cost_reduce_kernel(x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)                    # [bb, bt]
    w = w_ref[...].astype(jnp.float32)                    # [be, bt]
    o_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_e", "block_t",
                                    "interpret"))
def cost_reduce_bet(x: jax.Array, w: jax.Array, *, block_b: int = 128,
                    block_e: int = 128, block_t: int = 128,
                    interpret: bool = False) -> jax.Array:
    """``out[b, e] = sum_t x[b, t] * w[e, t]`` via the Pallas kernel.

    x [B, T] config-batch local costs, w [E, T] static selection/count
    rows -> [B, E] float32.  Shapes are zero-padded up to tile multiples
    (zeros contribute nothing to the sum) and the result sliced back.
    """
    b, t = x.shape
    e, t2 = w.shape
    assert t == t2, (x.shape, w.shape)
    bp, ep, tp = _pad_to(b, block_b), _pad_to(e, block_e), _pad_to(t, block_t)
    xf = jnp.zeros((bp, tp), jnp.float32).at[:b, :t].set(
        x.astype(jnp.float32))
    wf = jnp.zeros((ep, tp), jnp.float32).at[:e, :t].set(
        w.astype(jnp.float32))
    grid = (bp // block_b, ep // block_e, tp // block_t)
    out = pl.pallas_call(
        _cost_reduce_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, block_t), lambda i, j, k: (i, k)),
                  pl.BlockSpec((block_e, block_t), lambda i, j, k: (j, k))],
        out_specs=pl.BlockSpec((block_b, block_e), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, ep), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xf, wf)
    return out[:b, :e]
