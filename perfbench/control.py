#!/usr/bin/env python3
"""Readings of a cell's control: the plain reference in the precision
below the configuration's, put in the program's place, compared as a run
compares the program.  Run on the chip at the cell's own size; the
benchmark's own runs never run it.

    python3 perfbench/control.py --workload granite-34b.serve --seeds 1,2,3

- train: the program's first steps (one short window), the reference in
  fp8, the reference in bfloat16 (a witness of rounding alone) and the
  float32 reference with a fault planted (half of the batch left out;
  one leaf's update doubled), each against the reference in float32
  (``loops/train.gaps``), with every leaf's first-gradient norm.
- serve: one short window of the program per seed, then for the same
  prompts and served tokens the widest gap, under the float32 reference,
  of the tokens the program served and of those the fp8 reference puts
  first.  The program's reading comes from the same process.

Prints one JSON line per seed, then the largest program reading and the
smallest control reading.
"""
import argparse
import contextlib
import importlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(BENCH), str(BENCH / "refs")]
    import run
    cell = run.load_cell(args.workload)
    run.use_cache()
    import jax
    if jax.devices()[0].platform == "cpu":
        run.fail("no accelerator: jax sees only the CPU")
    rows = [readings(cell, args.workload, int(s), args.seconds)
            for s in args.seeds.split(",")]
    for r in rows:
        print(json.dumps(r), flush=True)
    keys = rows[0]["control"].keys()
    print(json.dumps({
        "program_max": {k: max(r["program"].get(k, float("nan"))
                               for r in rows) for k in keys},
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in keys}}))


def readings(cell: dict, name: str, seed: int, seconds: float) -> dict:
    import harness
    ctx = harness.Context(name=name, seed=seed, config=cell["config"],
                          traffic=cell["traffic"], limits=cell["limits"])
    kind = cell["traffic"]["loop"]
    return {"seed": seed, **globals()[f"_{kind}"](ctx, seconds)}


def _train(ctx, seconds) -> dict:
    import gc
    from harness import load_loop as load
    drv = load("train")
    st = drv.setup(ctx)
    win = drv.window(ctx, st, seconds, False)
    drv.release(ctx, st)
    del st
    gc.collect()
    lay = win["lay"]
    ref32 = drv.reference(ctx, lay, "f32")
    ref8 = drv.reference(ctx, lay, "fp8")
    ref16 = drv.reference(ctx, lay, "bf16")

    g = {"program": drv.gaps(win, ref32), "control": drv.gaps(ref8, ref32),
         "witness_bf16": drv.gaps(ref16, ref32)}
    for fault, plant in _TRAIN_FAULTS.items():
        with plant(ctx):
            g[fault] = drv.gaps(drv.reference(ctx, lay, "f32"), ref32)
    return {**{k: {n: v[n] for n in drv.COMPARED} for k, v in g.items()},
            "grad_norm_rel_worst": {k: v["grad_norm_rel_worst"]
                                    for k, v in g.items()},
            "leaves": [p for p, _, _ in lay],
            "grad_norms": {"program": win["grad_norms"],
                           "f32": ref32["grad_norms"],
                           "bf16": ref16["grad_norms"],
                           "fp8": ref8["grad_norms"]},
            "losses": {"program": win["losses"], "f32": ref32["losses"],
                       "fp8": ref8["losses"]}}


@contextlib.contextmanager
def _half_batch(ctx):
    """The loss over the first half of each row's tokens only."""
    ref = importlib.import_module(ctx.config["reference"])
    orig = ref.loss

    def half(params, tokens, labels, mode):
        n = tokens.shape[1] // 2
        return orig(params, tokens[:, :n], labels[:, :n], mode)
    ref.loss = half
    try:
        yield
    finally:
        ref.loss = orig


@contextlib.contextmanager
def _update_doubled(ctx):
    """The last leaf's update applied twice."""
    import adamw
    import jax.numpy as jnp
    orig = adamw.update

    def doubled(params, grads, m, v, scalars, o):
        P, M, V = orig(params, grads, m, v, scalars, o)
        k = list(params)[-1]
        new, old = P[k].astype(jnp.float32), params[k].astype(jnp.float32)
        P[k] = (2 * new - old).astype(P[k].dtype)
        return P, M, V
    adamw.update = doubled
    try:
        yield
    finally:
        adamw.update = orig


# faults planted in the reference put in the program's place (a state
# left unchanged reads 1 by construction and needs no run)
_TRAIN_FAULTS = {"fault_half_batch": _half_batch,
                 "fault_update_doubled": _update_doubled}


def _serve(ctx, seconds) -> dict:
    import gc

    import jax.numpy as jnp
    import numpy as np
    from harness import load_loop as load
    drv = load("serve")
    st = drv.setup(ctx)
    win = drv.window(ctx, st, seconds, False)
    drv.release(ctx, st)
    del st
    gc.collect()
    seqs = drv.sample(ctx, win["served"])
    lg32, pos = drv.ref_logits(ctx, win["lay"], seqs, "f32")
    prog = drv.gaps(lg32, pos, [o for _, o in seqs])
    lg8, _ = drv.ref_logits(ctx, win["lay"], seqs, "fp8")
    first8 = [np.asarray(jnp.argmax(lg8[j, p], -1)) for j, p in
              enumerate(pos)]
    ctrl = drv.gaps(lg32, pos, first8)
    return {"program": {"served_logit_gap": float(prog.max())},
            "control": {"served_logit_gap": float(ctrl.max())},
            "tokens": int(sum(len(o) for _, o in seqs)),
            "fp8_first_differs": int(sum(
                int((np.asarray(o) != f).sum()) for (_, o), f in
                zip(seqs, first8)))}


if __name__ == "__main__":
    main()
