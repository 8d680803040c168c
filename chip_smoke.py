#!/usr/bin/env python3
"""Bring-up smoke test: the main path of both stacks, once, on the TPU.

    python chip_smoke.py             # one chip: phases serve, train, sweep
    python chip_smoke.py --chips 4   # four chips: the sharded train phase only

One process holds the chip and runs every phase through the entry points
a user calls (``repro.launch.serve.main``, ``repro.launch.train.main``,
``Scenario.sweep``), at published widths with depth cut, random weights
from a fixed seed.  Each phase checks its outputs; any failed check or
exception exits non-zero.  The wall / compile / peak-memory lines are
bring-up observations of this script, not benchmark metrics.  The last
line of stdout is the JSON result.  With no TPU, it exits non-zero before
any phase and prints no result.
"""
import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / ".chip_smoke"          # checkpoints of this run; gitignored
REL_SWEEP = 1e-6      # batched vs compiled simulator: the repo's parity budget
# bf16 compute: 8-bit mantissas round each op at ~4e-3, and the cache path
# sums attention and the residual stream in another order than the full
# forward, across 8 layers; agreement is judged relative to the largest logit
REL_DECODE = 5e-2
# bf16 compute again: the 2x2 mesh splits every contraction over the model
# axis and reduces partial sums in another order than one chip does
REL_LOSS4 = 1e-2


def check(ok: bool, what: str) -> None:
    print(f"  [check] {what}: {'ok' if ok else 'FAILED'}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def compile_s() -> float:
    """Seconds jax has spent in backend compilation so far (persistent-
    cache hits skip it, so a warm second run shows less)."""
    from repro.obs import metrics
    return metrics.histogram("jit.compile_s").total


def run_phase(name: str, fn, dev) -> None:
    print(f"[chip_smoke] phase {name}", flush=True)
    t0, c0 = time.perf_counter(), compile_s()
    fn()
    wall = time.perf_counter() - t0
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[chip_smoke phase timing] {name}: wall {wall:.1f} s, compile "
          f"{compile_s() - c0:.1f} s, process peak_bytes_in_use {peak}",
          flush=True)


def phase_serve() -> None:
    """granite-34b at published widths, 8 of 88 layers (~9.7 GB bf16)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve
    from repro.models import lm
    from repro.serve.engine import make_serve_step

    engine, done = serve.main([
        "--arch", "granite-34b", "--layers", "8", "--slots", "8",
        "--kv-len", "4096", "--requests", "8", "--max-new", "16"])
    check(sorted(r.rid for r in done) == list(range(8))
          and all(len(r.out) == 16 for r in done),
          "8/8 requests answered with 16 tokens each")
    spec, rt, params = engine.spec, engine.rt, engine.params
    prompt = jnp.asarray(done[0].prompt, jnp.int32)[None]
    step = jax.jit(make_serve_step(spec, rt))
    cache = lm.init_cache(spec, rt, 1, 4096)
    for t in range(prompt.shape[1]):
        logits, cache = step(params, cache, prompt[:, t:t + 1])
    got = np.asarray(logits[0, 0], np.float32)
    want = np.asarray(jax.jit(lambda p, x: lm.forward(p, x, spec, rt)[0, -1])(
        params, prompt), np.float32)
    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          "decode and forward logits finite")
    err = float(np.abs(got - want).max() / np.abs(want).max())
    check(err <= REL_DECODE,
          f"prefill-by-decode logits vs lm.forward, {prompt.shape[1]} "
          f"tokens: max|diff|/max|ref| {err} <= {REL_DECODE}")


def phase_train() -> None:
    """rwkv6-7b at published widths, 1 of 32 layers (period 1), seq 4096."""
    import jax
    import numpy as np
    from repro.configs import cut_depth, get
    from repro.launch import train
    from repro.models import init_params

    ckpt = OUT / "ckpt_train"
    shutil.rmtree(ckpt, ignore_errors=True)
    spec = cut_depth(get("rwkv6-7b").spec, 1)
    rt = train.runtime_cfg(4096)
    head0 = np.asarray(jax.jit(
        lambda k: init_params(spec, rt, k)["lm_head"].value[:8, :8])(
            jax.random.PRNGKey(0)), np.float32)
    # batch 1: the most that memory_analysis() fits in 16 GiB at seq 4096
    res = train.main(["--arch", "rwkv6-7b", "--layers", "1", "--steps", "5",
                      "--seq", "4096", "--batch", "1",
                      "--ckpt-dir", str(ckpt)])
    losses = res["losses"]
    check(len(losses) == 5 and all(math.isfinite(v) for v in losses),
          f"5 finite losses {losses}")
    state = jax.tree.leaves((res["params"], res["opt"]))
    print(f"  train state {sum(x.nbytes for x in state)} bytes")
    head = np.asarray(res["params"]["lm_head"].value[:8, :8], np.float32)
    check(bool(np.abs(head - head0).max() > 0), "parameters updated")


def phase_sweep() -> None:
    """Batched simulator on the device over the paper's Fig-8 model."""
    import jax
    import numpy as np
    from benchmarks.paper_models import GPT3_5B
    from benchmarks.perf_smoke import BATCH_MBS, BATCH_WORLDS
    from repro import TPU_V5E, Scenario
    from repro.api import _batched_engines
    from repro.core.batched import MIN_ROWS
    from repro.obs import metrics

    sc = Scenario(GPT3_5B).train(batch=3840, seq=2048)
    metrics.reset()
    n = 0
    for world in BATCH_WORLDS:
        got = sc.with_backend("batched").sweep(
            world, TPU_V5E, max_pp=1, microbatches=BATCH_MBS)
        ref = {p.label: p for p in sc.sweep(
            world, TPU_V5E, max_pp=1, microbatches=BATCH_MBS)}
        check(len(got) == len(ref) > 0 and {p.label for p in got} == set(ref),
              f"world {world}: same {len(ref)} points as the compiled backend")
        worst = 0.0
        for p in got:
            q = ref[p.label]
            # compute and comm time are what the busy-group contraction
            # feeds; step time and bubble come from the two-stream scan
            for a, b in ((p.sim.step_time, q.sim.step_time),
                         (p.sim.bubble_fraction, q.sim.bubble_fraction),
                         (p.sim.compute_time, q.sim.compute_time),
                         (p.sim.comm_time, q.sim.comm_time),
                         (p.mem.peak_bytes, q.mem.peak_bytes)):
                if a != b:
                    worst = max(worst, abs(a - b) / abs(b) if b else math.inf)
        check(worst <= REL_SWEEP, f"world {world}: step time, bubble, "
              f"compute, comm, peak memory within rel {REL_SWEEP} "
              f"(worst {worst})")
        n += len(got)
    counters = metrics.REGISTRY.collect()["counters"]
    fallbacks = {k: v for k, v in counters.items()
                 if k.startswith("batched.fallback_")}
    check(not any(fallbacks.values()), f"{n} points, fallbacks {fallbacks}")
    check(counters.get("batched.kernel_calls", 0) > 0,
          f"{counters.get('batched.kernel_calls')} batched kernel calls")
    backend = _batched_engines.engine(sc.spec, sc.mode, sc.env())
    places = set()
    for kern in backend._kernels.values():
        # MIN_ROWS rows: the shape the sweep compiled, not a new one
        out = kern.run_async(np.ones((MIN_ROWS, len(kern.axes))),
                             np.ones(MIN_ROWS), TPU_V5E)
        places |= {(d.platform, str(v.dtype)) for v in out.values()
                   for d in v.devices()}
    check(places == {("tpu", "float64")},
          f"{len(backend._kernels)} kernels' outputs on {places}")
    print(f"  x64 left off for the rest of the process: "
          f"{not jax.config.jax_enable_x64}")


def phase_train4() -> None:
    """granite-34b at published widths, 4 layers (~2.7 B params, ~33 GB
    of train state) on a 2x2 mesh; step 0 against one chip."""
    import jax
    from repro.configs import cut_depth, get
    from repro.data import DataCfg, TokenPipeline
    from repro.launch import train
    from repro.models import init_params, lm

    ckpt = OUT / "ckpt_train4"
    shutil.rmtree(ckpt, ignore_errors=True)
    spec = cut_depth(get("granite-34b").spec, 4)
    rt = train.runtime_cfg(4096)
    batch = TokenPipeline(DataCfg(global_batch=2, seq_len=4096,
                                  vocab=spec.vocab, seed=0)).batch(0)
    with jax.default_device(jax.devices()[0]):
        params = jax.jit(lambda k: init_params(spec, rt, k))(
            jax.random.PRNGKey(0))
        ref = float(jax.jit(lambda p, b: lm.loss_fn(p, b, spec, rt))(
            params, batch))
        del params
    print(f"  one-chip reference loss {ref}")
    res = train.main(["--arch", "granite-34b", "--layers", "4", "--steps",
                      "3", "--seq", "4096", "--batch", "2",
                      "--ckpt-dir", str(ckpt)])
    check(dict(res["mesh"].shape) == {"data": 2, "model": 2},
          f"mesh {dict(res['mesh'].shape)}")
    losses = res["losses"]
    check(len(losses) == 3 and all(math.isfinite(v) for v in losses),
          f"3 finite losses {losses}")
    err = abs(losses[0] - ref) / abs(ref)
    check(err <= REL_LOSS4, f"step-0 loss {losses[0]} vs one-chip {ref}: "
          f"rel {err} <= {REL_LOSS4}")
    state = jax.tree.leaves((res["params"], res["opt"]))
    total = sum(x.nbytes for x in state)
    held = {d: 0 for d in jax.devices()}
    for x in state:
        for s in x.addressable_shards:
            held[s.device] += s.data.nbytes
    for d in jax.devices():
        print(f"  device {d.id}: state {held[d]} bytes, bytes_in_use "
              f"{(d.memory_stats() or {}).get('bytes_in_use')}")
    check(max(held.values()) < total,
          f"no device holds the whole {total}-byte state")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax sees {devs[0].platform}); "
                 f"nothing was run")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but jax sees {len(devs)}")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch.compile_cache import use_compile_cache
    print(f"[chip_smoke] compile cache {use_compile_cache()}")
    from repro.obs import runtime_hooks
    runtime_hooks()                 # the jit.compile_s histogram
    OUT.mkdir(parents=True, exist_ok=True)
    phases = ([("train4", phase_train4)] if args.chips == 4 else
              [("serve", phase_serve), ("train", phase_train),
               ("sweep", phase_sweep)])
    for name, fn in phases:
        run_phase(name, fn, devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
