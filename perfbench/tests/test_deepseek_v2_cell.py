"""The ``deepseek-v2.serve_chat`` comparison that decides ``correct``,
shown to pass and to fail at a size a CPU holds.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_deepseek_v2_cell.py

A run of the cell (set-up, window, release, check) on the cell's own
loop, reference and limits at small widths, with the published routing
(32 experts in 8 groups of 4, this share holding one group): sound, it
comes out correct; with a fault planted in the program's routing (gates
renormalized; the group limit left out), not correct; and the control
(the reference in float8, ``loops/serve_routed.readings``) fails a
limit too.
"""
import copy
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH), str(BENCH / "refs")]

CELL = "deepseek-v2.serve_chat"
SEED = 2**31 + 12345          # larger than 32 signed bits


def load(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def tiny_cell(**moe) -> dict:
    cfg, tr = load("configs", "deepseek-v2"), load("traffic", "serve_chat")
    cfg = copy.deepcopy(cfg)
    cfg["spec"].update(d_model=256, n_heads=4, n_kv_heads=4, d_head=32,
                       d_ff=512, vocab=4096)
    cfg["spec"]["mla"].update(kv_lora=64, q_lora=96, rope_dim=16,
                              nope_dim=32, v_dim=32)
    cfg["spec"]["moe"].update(n_experts=32, n_held=4, d_expert=64, **moe)
    cfg["runtime_layers"] = 3
    cfg["init_std"] = {"w_egate": 1 / 16, "w_eup": 1 / 16, "w_edown": 1 / 8}
    tr.update(slots=8, kv_len=256, check_requests=8,
              prompt_len={"mean": 16.53, "sigma": 0.8, "lo": 4, "hi": 40},
              output_len={"mean": 44.07, "sigma": 0.8, "lo": 2, "hi": 96})
    return {"config": cfg, "traffic": tr, "limits": load("limits", CELL),
            "cell": {"chips": 1},
            "end_to_end": [{"name": "setup_s", "unit": "s"}], "per_layer": []}


def run_tiny(**moe) -> dict:
    import jax

    import run
    return run.run_cell(tiny_cell(**moe), CELL, SEED, 1.5, False,
                        jax.devices())


def test_sound():
    out = run_tiny()
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [{"norm_topk": True}, {"topk_group": 0}],
                         ids=["gates_renormalized", "no_group_limit"])
def test_routing_fault_fails(fault):
    assert not run_tiny(**fault)["correct"]


def test_control_fails():
    import harness
    cfg = tiny_cell()
    ctx = harness.Context(name=CELL, seed=SEED, config=cfg["config"],
                          traffic=cfg["traffic"], limits=cfg["limits"])
    r = harness.load_loop(cfg["traffic"]["loop"]).readings(ctx, 1.5)
    assert all(v <= cfg["limits"][k] for k, v in r["program"].items()), r
    assert any(v > cfg["limits"][k] for k, v in r["control"].items()), r
