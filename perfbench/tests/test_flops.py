"""The FLOP and byte functions against counts made by hand.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_flops.py
"""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH)]

import flops  # noqa: E402


def spec(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())["spec"]


def test_rwkv6_7b_counts():
    s = spec("rwkv6-7b")
    H, F, rk = 4096, 14336, 128
    # r, k, v, g and the output: 5 H x H; decay LoRA 2 H x rk; channel
    # mix H x F twice and H x H
    assert flops.layer_matmul_params(s) == 5 * H * H + 2 * H * rk \
        + 2 * H * F + H * H == 219_152_384
    assert flops.head_params(s) == 268_435_456
    assert flops.train_flops_per_token(s, 1) == 6 * (219_152_384
                                                     + 268_435_456)


def test_granite_34b_counts():
    s = spec("granite-34b")
    H, F = 6144, 24576
    # q and o 48 x 128 wide, one K and one V head, plain (ungated) MLP
    assert flops.layer_matmul_params(s) == 2 * H * 6144 + 2 * H * 128 \
        + 2 * H * F == 379_060_224
    assert flops.head_params(s) == 301_989_888
    assert flops.matmul_params(s, 8) == 3_334_471_680


def test_decode_step_bytes_and_bound():
    s = spec("granite-34b")
    d = flops.decode_step(s, 8, rows=64, filled=100)
    kv = 2 * 8 * 64 * 100 * 1 * 128 * 2
    assert d["bytes"] == 3_334_471_680 * 2 + kv
    assert d["flops"] == 2 * 3_334_471_680 * 64 + 4 * 8 * 64 * 100 * 48 * 128
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(d["flops"], d["bytes"], peak)
    assert bound == "memory" and t == d["bytes"] / 819e9
