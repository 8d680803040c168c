"""The comparison that decides ``correct``, shown to fail.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_faults.py

Each test skips the harness's look for a chip and drives the rest of a
run (set-up, window, release, check) of a cell's loop at a size a CPU
holds, with the timed path broken underneath the loop: a step that
returns its state unchanged, half of the batch left out with the mean
taken over the rest, and an answer altered where it is produced.  The
run has to come out not correct; unbroken, it has to come out correct.
The exchange between chips is not a fault these one-chip cells can have.
A second group checks that each control (the reference in the precision
below the configuration's) fails one of the cell's limits at this size
too.
"""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH), str(BENCH / "refs")]

SEED = 2**31 + 12345          # larger than 32 signed bits


def load(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def tiny_cell(cell: str) -> dict:
    config, traffic = cell.split(".")
    cfg, tr = load("configs", config), load("traffic", traffic)
    if tr["loop"] == "train":
        cfg["spec"].update(d_model=128, n_heads=4, n_kv_heads=4, d_head=32,
                           d_ff=256, vocab=512, rwkv_decay_rank=16)
        # x W1 W2 about N(0, 0.2^2), as the configuration's init states
        cfg["init_std"]["w_dec2"] = 0.2 / 16 ** 0.5
        tr["seq"] = 2048      # enough tokens that the loss gap averages as at size
    elif tr["loop"] == "serve":
        # wide and long enough that the control's widest gap, as on
        # the chip, lies above the limit
        cfg["spec"].update(d_model=256, n_heads=8, d_head=32, d_ff=512,
                           vocab=4096)
        cfg["runtime_layers"] = 2
        tr.update(slots=8, kv_len=256, check_requests=8,
                  prompt_len={"mean": 16.53, "sigma": 0.8, "lo": 4, "hi": 40},
                  output_len={"mean": 44.07, "sigma": 0.8, "lo": 2, "hi": 96})
    return {"config": cfg, "traffic": tr, "limits": load("limits", cell),
            "cell": {"chips": 1},
            "end_to_end": [{"name": "setup_s", "unit": "s"}], "per_layer": []}


def run_tiny(cell: str, seconds: float = 1.5) -> dict:
    import jax

    import run
    return run.run_cell(tiny_cell(cell), cell, SEED, seconds, False,
                        jax.devices())


# ---- train ---------------------------------------------------------------

TRAIN = "rwkv6-7b.train_4k"


def test_train_sound():
    out = run_tiny(TRAIN)
    assert out["correct"], out["checks"]


def test_train_state_unchanged(monkeypatch):
    import repro.train as tr_mod
    orig = tr_mod.make_train_step

    def broken(*a, **k):
        step = orig(*a, **k)

        def same(params, opt, batch):
            return params, opt, step(params, opt, batch)[2]
        return same
    monkeypatch.setattr(tr_mod, "make_train_step", broken)
    assert not run_tiny(TRAIN)["correct"]


def test_train_half_batch(monkeypatch):
    from repro.models import lm
    orig = lm.loss_fn

    def half(params, batch, *a, **k):
        n = batch["tokens"].shape[1] // 2
        return orig(params, {k_: v[:, :n] for k_, v in batch.items()},
                    *a, **k)
    monkeypatch.setattr(lm, "loss_fn", half)
    assert not run_tiny(TRAIN)["correct"]


def test_train_answer_altered(monkeypatch):
    import jax
    from repro.train import train_step
    orig = train_step.adamw_update

    def altered(params, grads, opt_state, cfg):
        new, *rest = orig(params, grads, opt_state, cfg)
        p, tdef = jax.tree.flatten(params)
        n = jax.tree.leaves(new)
        n[-1] = n[-1] + (n[-1] - p[-1])      # one leaf's update doubled
        return (jax.tree.unflatten(tdef, n), *rest)
    monkeypatch.setattr(train_step, "adamw_update", altered)
    assert not run_tiny(TRAIN)["correct"]


# ---- serve ---------------------------------------------------------------

SERVE = "granite-34b.serve"


def _break_serve_step(monkeypatch, fn):
    from repro.serve import engine
    orig = engine.make_serve_step

    def broken(*a, **k):
        step = orig(*a, **k)
        return lambda params, cache, tokens: fn(params, cache, tokens, step)
    monkeypatch.setattr(engine, "make_serve_step", broken)


def test_serve_sound():
    out = run_tiny(SERVE)
    assert out["correct"], out["checks"]


def test_serve_state_unchanged(monkeypatch):
    _break_serve_step(monkeypatch, lambda p, c, t, step: (step(p, c, t)[0],
                                                           c))
    assert not run_tiny(SERVE)["correct"]


def test_serve_half_batch(monkeypatch):
    def half(p, c, t, step):
        logits, cache = step(p, c, t)
        return logits.at[logits.shape[0] // 2:].set(0), cache
    _break_serve_step(monkeypatch, half)
    assert not run_tiny(SERVE)["correct"]


def test_serve_answer_altered(monkeypatch):
    def bumped(p, c, t, step):
        logits, cache = step(p, c, t)
        return logits.at[:, :, 7].add(1e4), cache
    _break_serve_step(monkeypatch, bumped)
    assert not run_tiny(SERVE)["correct"]


# ---- controls --------------------------------------------------------------

@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_control_fails(cell):
    import control
    cfg = tiny_cell(cell)
    r = control.readings(cfg, cell, SEED, 1.5)
    assert any(v > cfg["limits"][k] for k, v in r["control"].items()), r
