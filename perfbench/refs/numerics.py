"""Matrix products of the plain references, in the precision asked for.

``f32``: float32 operands at ``Precision.HIGHEST`` (on a TPU a float32
product otherwise runs in bfloat16 passes).  ``fp8``: the control, the
precision below the configuration's bfloat16, as float8 training runs:
each operand scaled per tensor to the float8 e4m3 range and rounded to
it, the product exact, and in the backward pass the product's cotangent
scaled per tensor and rounded to float8 e5m2 (the roundings themselves
pass gradients straight through).  ``bf16``: operands and product
rounded to bfloat16, the configuration's own precision; it is no
reference, only a witness of how far rounding alone moves a reading.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _scaled_round(x, dtype, max_value: float):
    x = x.astype(jnp.float32)
    s = max_value / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def fp8_round(x):
    return _scaled_round(x, jnp.float8_e4m3fn, E4M3_MAX)


fp8_round.defvjp(lambda x: (fp8_round(x), None), lambda _, ct: (ct,))


@jax.custom_vjp
def fp8_grad(y):
    """Identity; rounds the cotangent to scaled float8 e5m2."""
    return y


fp8_grad.defvjp(lambda y: (y, None), lambda _, ct: (
    _scaled_round(ct, jnp.float8_e5m2, E5M2_MAX),))


def bf16_round(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def mm(eq: str, a, b, mode: str = "f32"):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        return fp8_grad(jnp.einsum(eq, fp8_round(a), fp8_round(b),
                                   precision=HI))
    if mode == "bf16":
        return bf16_round(jnp.einsum(eq, bf16_round(a), bf16_round(b),
                                     precision=HI))
    if mode != "f32":
        raise ValueError(f"mode {mode!r} not in f32|fp8|bf16")
    return jnp.einsum(eq, a, b, precision=HI)


def rms(x, w, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def cross_entropy(logits, labels):
    """Mean next-token cross entropy; logits [..., V] float32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
