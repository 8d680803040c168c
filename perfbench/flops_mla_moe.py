"""Operations and bytes of a decode step of a model with latent attention
(MLA) and routed experts (deepseek-v2), from the configuration's sizes
alone.

``always_on_params`` counts the weights every row multiplies through:
each layer's attention (the absorbed decode applies ``w_uk`` to the
query and ``w_uv`` to the latent context, as many products as the
weights), the dense first layer's MLP, each MoE layer's shared experts,
and the output head.  Not the embedding (a gather) and not the router
(hidden x experts in float32 per MoE layer, 0.2% of the bytes).  A
routed assignment adds one expert's weights; latent attention adds
``latent_flops_per_position`` for every filled position of every layer:
scores against the 512-wide latent and the 64-wide rope key for each
head, and the weighted sum of the latent for each head.
"""
from __future__ import annotations

from flops import BF16


def mla_params(spec: dict) -> int:
    """Matrix parameters of one layer's attention."""
    H, N, m = spec["d_model"], spec["n_heads"], spec["mla"]
    return (H * m["q_lora"] + m["q_lora"] * N * (m["nope_dim"] + m["rope_dim"])
            + H * (m["kv_lora"] + m["rope_dim"])
            + m["kv_lora"] * N * (m["nope_dim"] + m["v_dim"])
            + N * m["v_dim"] * H)


def expert_params(spec: dict) -> int:
    """One SiLU-gated expert: gate, up and down."""
    return 3 * spec["d_model"] * spec["moe"]["d_expert"]


def moe_layers(spec: dict, layers: int) -> int:
    return layers - (1 if spec["moe"].get("first_dense") else 0)


def always_on_params(spec: dict, layers: int) -> int:
    H, mo = spec["d_model"], spec["moe"]
    dense = layers - moe_layers(spec, layers)
    return (layers * mla_params(spec) + dense * 3 * H * spec["d_ff"]
            + moe_layers(spec, layers) * mo["n_shared"] * expert_params(spec)
            + H * spec["vocab"])


def held_experts(spec: dict) -> int:
    mo = spec["moe"]
    return mo.get("n_held") or mo["n_experts"]


def weight_bytes(spec: dict, layers: int) -> int:
    """Every matrix weight a step reads once: the always-on weights and
    the held experts of each MoE layer, in bfloat16."""
    held = moe_layers(spec, layers) * held_experts(spec) * expert_params(spec)
    return (always_on_params(spec, layers) + held) * BF16


def latent_flops_per_position(spec: dict) -> int:
    N, m = spec["n_heads"], spec["mla"]
    return 2 * N * (m["kv_lora"] + m["rope_dim"]) + 2 * N * m["kv_lora"]


def latent_bytes_per_position(spec: dict) -> int:
    m = spec["mla"]
    return (m["kv_lora"] + m["rope_dim"]) * BF16


def decode_step(spec: dict, layers: int, rows: int, filled: int,
                routed: float) -> dict:
    """What one decode step needs for ``rows`` live sequences whose cache
    holds ``filled`` positions and ``routed`` assignments of tokens to
    held experts (over all MoE layers): 2 FLOPs per always-on weight per
    row and per held expert's weight per assignment, plus latent
    attention over the filled positions; every weight read once plus the
    filled latent cache of the live rows."""
    flops = (2.0 * always_on_params(spec, layers) * rows
             + 2.0 * expert_params(spec) * routed
             + float(latent_flops_per_position(spec)) * layers * rows * filled)
    nbytes = float(weight_bytes(spec, layers)) \
        + float(latent_bytes_per_position(spec)) * layers * rows * filled
    return {"flops": flops, "bytes": nbytes}


def window_routed(spec: dict, layers: int, per_step_means: list) -> list:
    """Each step's routed assignments from the engine's
    ``moe.expert_tokens`` samples (tokens per held expert per layer)."""
    k = held_experts(spec) * moe_layers(spec, layers)
    return [x * k for x in per_step_means]
