#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python3 perfbench/run.py --workload rwkv6-7b.train_4k --seed 7 \\
        --seconds 30 --trace 0

The cell is looked up in ``BENCHMARK.json`` at the checkout's root.  Its
configuration (``perfbench/configs/<config>.json``), traffic mix
(``perfbench/traffic/<traffic>.json``, which names the generic loop in
``perfbench/loops/``), correctness limits (``perfbench/limits/<cell>.json``)
and per-layer metric readers (``perfbench/metrics/<metric>.py``) are all
found by name, so a new cell or metric is new files only.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Both check the
window's outputs against a plain reference after the window has closed.
The last line of stdout is one JSON object; the numbers compared are
printed beside their limits as the last lines of stderr.  With no
accelerator, or fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import time

T_PROCESS = time.perf_counter()          # set-up is timed from here
T_DEVICES = T_PROCESS                    # when jax has found the chips

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the compile cache lives at a fixed path inside the checkout, so a later
# run of the same checkout finds it and two checkouts share nothing
CACHE_DIR = ROOT / ".jax_cache"


def fail(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def load_cell(name: str) -> dict:
    """The cell's entries from BENCHMARK.json and its data files."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    files = {"config": BENCH / "configs" / f"{cell['config']}.json",
             "traffic": BENCH / "traffic" / f"{cell['traffic']}.json",
             "limits": BENCH / "limits" / f"{name}.json"}
    data = {}
    for key, path in files.items():
        if not path.is_file():
            fail(f"{name}: missing {path.relative_to(ROOT)}")
        data[key] = json.loads(path.read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    return {**data, "cell": cell, "end_to_end": e2e, "per_layer": per_layer}


def use_cache() -> None:
    """The persistent compile cache at ``CACHE_DIR``, for every program
    (also those that compile fast), without eviction; set before jax is
    imported so that no setting of the machine's applies."""
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ.update({
        "JAX_COMPILATION_CACHE_DIR": str(CACHE_DIR),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
        "JAX_COMPILATION_CACHE_MAX_SIZE": "-1"})


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    cell = load_cell(args.workload)
    use_cache()
    sys.path[:0] = [str(ROOT / "src"), str(BENCH), str(BENCH / "refs")]

    import jax
    devs = jax.devices()
    global T_DEVICES
    T_DEVICES = time.perf_counter()
    if devs[0].platform == "cpu":
        fail("no accelerator: jax sees only the CPU; nothing was run")
    if len(devs) < cell["cell"]["chips"]:
        fail(f"{args.workload} needs {cell['cell']['chips']} chips, jax "
             f"sees {len(devs)}")
    print(f"perfbench: {args.workload} seed {args.seed} on "
          f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}",
          file=sys.stderr, flush=True)
    result = run_cell(cell, args.workload, args.seed, args.seconds,
                      bool(args.trace), devs)
    emit(result)


def run_cell(cell: dict, name: str, seed: int, seconds: float, trace: bool,
             devs) -> dict:
    """Set up, measure, free, check; returns the result line's object."""
    import jax
    import harness
    from harness import CompileClock

    clock = CompileClock()
    ctx = harness.Context(name=name, seed=seed, config=cell["config"],
                          traffic=cell["traffic"], limits=cell["limits"])
    drv = harness.load_loop(cell["traffic"]["loop"])

    state = drv.setup(ctx)
    compile_setup = clock.secs
    t_setup = time.perf_counter() - T_PROCESS
    print(f"perfbench: set-up {t_setup!r} s: to the chips "
          f"{T_DEVICES - T_PROCESS!r} s, the cell's set-up "
          f"{time.perf_counter() - T_DEVICES!r} s ({compile_setup!r} s "
          f"compiling)", file=sys.stderr, flush=True)
    win = drv.window(ctx, state, seconds, trace)
    compile_window = clock.secs - compile_setup
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs[:cell["cell"]["chips"]])
    # the program's state goes before the reference runs
    drv.release(ctx, state)
    del state
    gc.collect()
    checks = drv.check(ctx, win)

    attempted, failed = win["attempted"], win["failed"]
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  and math.isfinite(c["value"])
                                  for c in checks.values())
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    metrics: dict = {}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed}
    if trace:
        tr = win.get("trace")
        if tr is None:
            raise RuntimeError(f"{name}: traced run produced no trace")
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        reading = harness.Reading(ctx=ctx, window=win, trace=tr,
                                  compile_s=compile_setup, devs=devs)
        for m in cell["per_layer"]:
            v = harness.load_reader(m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    else:
        for m in cell["end_to_end"]:
            v = t_setup if m["name"] == "setup_s" else win["metrics"][m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out.update(metrics=metrics, device=device)
    # nothing may compile inside the window; shown, not judged
    print(f"perfbench: compile seconds in set-up {compile_setup!r}, in the "
          f"window {compile_window!r}", file=sys.stderr)
    out["checks"] = checks
    return out


def emit(out: dict) -> None:
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
