"""Operations and bytes the benchmark's shares are computed from, from
the configuration's sizes alone.

``matmul_params`` counts the weights one token multiplies through: every
layer's matrices plus the output head, not the embedding (a gather).
A training token costs 6 of these per parameter (forward 2, backward 4),
recomputation not counted; a served token 2.
"""
from __future__ import annotations


def layer_matmul_params(spec: dict) -> int:
    """Matrix parameters of one layer of ``spec`` (configuration keys)."""
    H, dff = spec["d_model"], spec["d_ff"]
    nh = spec["n_heads"]
    dh = spec.get("d_head") or H // nh
    if spec.get("block", "gqa") == "rwkv6":
        rk = spec["rwkv_decay_rank"]
        time_mix = 4 * H * nh * dh + H * rk + rk * nh * dh + nh * dh * H
        channel_mix = 2 * H * dff + H * H
        return time_mix + channel_mix
    nkv = max(1, spec["n_kv_heads"])
    attn = 2 * H * nh * dh + 2 * H * nkv * dh
    mlp = (3 if spec.get("gated_ffn", True) else 2) * H * dff
    return attn + mlp


def head_params(spec: dict) -> int:
    return spec["d_model"] * spec["vocab"]


def matmul_params(spec: dict, layers: int) -> int:
    return layers * layer_matmul_params(spec) + head_params(spec)


def train_flops_per_token(spec: dict, layers: int) -> float:
    return 6.0 * matmul_params(spec, layers)


BF16 = 2     # bytes of the served weights and of the K/V cache


def decode_step(spec: dict, layers: int, rows: int, filled: int) -> dict:
    """What one decode step needs for ``rows`` live sequences whose cache
    holds ``filled`` positions: 2 FLOPs per matrix parameter per row plus
    attention over the filled positions (scores and weighted sum), and
    every matrix weight read once plus the filled K/V of the live rows,
    all in bfloat16."""
    H, nh = spec["d_model"], spec["n_heads"]
    dh = spec.get("d_head") or H // nh
    nkv = max(1, spec["n_kv_heads"])
    n = matmul_params(spec, layers)
    attn_flops = 4.0 * layers * rows * filled * nh * dh
    kv_bytes = 2.0 * layers * rows * filled * nkv * dh * BF16
    return {"flops": 2.0 * n * rows + attn_flops,
            "bytes": float(n * BF16) + kv_bytes}


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
