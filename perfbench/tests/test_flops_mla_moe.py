"""The MLA + MoE decode counts against counts made by hand at the
deepseek-v2 cell's sizes, and the three readers built on them on a
synthetic window and registry.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_flops_mla_moe.py

The registry is filled as a run leaves it: set-up's warm-up steps first,
with values no window sample has, then the window's own samples.  Each
reader has to read the window's samples alone, and nothing where the
registry holds fewer samples than the window's steps (as on a program
that keeps no ``moe.expert_tokens``).
"""
import json
import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import flops_mla_moe as fm  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "deepseek-v2.json").read_text())
SPEC, LAYERS = CONFIG["spec"], CONFIG["runtime_layers"]
H, V, N, FF, FE = 5120, 102400, 128, 12288, 1536
MLA = (H * 1536 + 1536 * N * (128 + 64) + H * (512 + 64)
       + 512 * N * (128 + 128) + N * 128 * H)
EXPERT = 3 * H * FE
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_hand_counts_at_the_cells_sizes():
    assert LAYERS == 5
    assert fm.mla_params(SPEC) == MLA == 149_225_472
    assert fm.expert_params(SPEC) == EXPERT == 23_592_960
    # 5 attentions, 1 dense MLP, 4 x 2 shared experts, the head
    always = 5 * MLA + 3 * H * FF + 4 * 2 * EXPERT + H * V
    assert fm.always_on_params(SPEC, LAYERS) == always == 1_647_902_720
    # ... and 4 x 20 held experts, all in bfloat16: 7.07 GB a step
    assert fm.weight_bytes(SPEC, LAYERS) == 2 * (always + 4 * 20 * EXPERT) \
        == 7_070_679_040
    # scores over 512 + 64 and the weighted sum over 512, 128 heads
    assert fm.latent_flops_per_position(SPEC) == 2 * N * 576 + 2 * N * 512 \
        == 278_528
    d = fm.decode_step(SPEC, LAYERS, rows=128, filled=700, routed=384.0)
    assert d["flops"] == 2 * always * 128 + 2 * EXPERT * 384 \
        + 278_528 * 5 * 128 * 700
    assert d["bytes"] == 7_070_679_040 + 576 * 2 * 5 * 128 * 700


# ---- readers ---------------------------------------------------------------

WIN = {"waves": 2, "attempted": 8, "steps_per_wave": [5, 6],
       "live_rows": [4, 4, 4, 3, 1, 4, 4, 3, 3, 2, 1], "seconds": 0.5}
N_STEPS = sum(WIN["steps_per_wave"])
WARM = 1e3
rng = np.random.default_rng(0)
SAMPLES = rng.uniform(2.0, 8.0, N_STEPS).tolist()
SERVE_S = 0.25


class Dev:
    device_kind = "TPU v5 lite"


def reading():
    from harness import Context, Reading
    ctx = Context(name="deepseek-v2.serve_chat", seed=1, config=CONFIG,
                  traffic={"slots": 4}, limits={})
    trace = {"modules": {"jit_serve_step(123)": {"seconds": SERVE_S,
                                                 "count": N_STEPS}}}
    return Reading(ctx=ctx, window=dict(WIN), trace=trace, compile_s=0.0,
                   devs=[Dev()])


@pytest.fixture
def registry():
    from repro.obs import metrics
    metrics.reset()
    h = metrics.histogram("moe.expert_tokens")
    for x in [WARM] * 3 + SAMPLES:
        h.observe(x)
    yield metrics
    metrics.reset()


def steps():
    """(rows, filled, routed) of each window step, by hand."""
    out, i = [], 0
    for n in WIN["steps_per_wave"]:
        for k in range(n):
            out.append((WIN["live_rows"][i], k + 1, SAMPLES[i] * 20 * 4))
            i += 1
    return out


def expected(metric: str) -> float:
    always = 1_647_902_720
    if metric == "expert_tokens.serve":
        return statistics.fmean(SAMPLES)
    f = [2 * always * r + 2 * EXPERT * a + 278_528 * 5 * r * p
         for r, p, a in steps()]
    if metric == "mfu_mla_moe.serve":
        return 100 * sum(f) / WIN["seconds"] / 197e12
    b = [7_070_679_040 + 576 * 2 * 5 * r * p for r, p, _ in steps()]
    t = sum(max(fi / 197e12, bi / 819e9) for fi, bi in zip(f, b))
    return 100 * t / SERVE_S


METRICS = ["expert_tokens.serve", "mfu_mla_moe.serve",
           "decode_roofline_mla_moe.serve"]


@pytest.mark.parametrize("metric", METRICS)
def test_reader_reads_the_window_alone(registry, metric):
    from harness import load_reader
    got = load_reader(metric)(reading())
    assert got == pytest.approx(expected(metric), rel=1e-9)


@pytest.mark.parametrize("metric", METRICS)
def test_reader_finds_nothing_without_samples(metric):
    from harness import load_reader
    from repro.obs import metrics
    metrics.reset()
    assert load_reader(metric)(reading()) is None
