#!/usr/bin/env python3
"""Readings of the control of a cell on the ``serve_routed`` loop: per
seed, one short window of the program, then both of the loop's numbers,
under the float32 reference, of the tokens the program served and of
those the reference in float8 puts first (``loops/serve_routed.py``'s
``readings``).  Run on the chip at the cell's own size; the benchmark's
own runs never run it.

    python3 perfbench/control_routed.py --workload deepseek-v2.serve_chat --seeds 1,2,3

Prints one JSON line per seed, then the largest program reading and the
smallest control reading of each number.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.5)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(BENCH), str(BENCH / "refs")]
    import harness
    import run
    cell = run.load_cell(args.workload)
    run.use_cache()
    import jax
    if jax.devices()[0].platform == "cpu":
        run.fail("no accelerator: jax sees only the CPU")
    drv = harness.load_loop(cell["traffic"]["loop"])
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(name=args.workload, seed=seed,
                              config=cell["config"], traffic=cell["traffic"],
                              limits=cell["limits"])
        rows.append({"seed": seed, **drv.readings(ctx, args.seconds)})
        print(json.dumps(rows[-1]), flush=True)
    keys = rows[0]["control"].keys()
    print(json.dumps({
        "program_max": {k: max(r["program"][k] for r in rows) for k in keys},
        "control_min": {k: min(r["control"][k] for r in rows) for k in keys}}))


if __name__ == "__main__":
    main()
