"""Closed loop of serving waves through the runtime serve engine.

Set-up builds ``repro.serve.Engine`` as ``repro.launch.serve`` does
(naive attention, ``slots`` rows, a ``kv_len`` cache) on the benchmark's
seeded weights, and warms it with one short wave.  The window submits a
wave of ``slots`` requests together and steps ``Engine.run`` one decode
step per call until the last request of the wave finishes; the next wave
starts after the engine's cache is reset.  The window closes at the end
of the first wave that ends after ``--seconds``, so every request in it
is whole.  Waves are the only traffic the engine serves correctly today:
its cache position is one scalar for all rows, so only requests admitted
together, all starting at position 0, are each their own sequence.

Each wave has the same (prompt, output) lengths (``gen.wave_sizes``,
the mid-quantiles of the traffic's length distributions), so every seed
does the same work; the seed sets their slot order and the prompts'
token ids.  After the
window a seeded sample of the finished requests, the longest among them,
is run through the plain reference's full forward pass (prompt and served
tokens), and every served token is judged by how far its reference logit
lies below the reference's best at that position.

Traffic keys: ``slots``, ``kv_len``, ``prompt_len``/``output_len``
(``mean``, ``sigma``, ``lo``, ``hi`` of a clipped lognormal),
``check_requests``.
"""
from __future__ import annotations

import contextlib
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

import gen
import weights

TOKENS, TTFT = "serve_tokens_per_s", "ttft_p95_ms"


def build(ctx, spec):
    from repro.models import RuntimeCfg, init_params
    from repro.serve import Engine
    tr = ctx.traffic
    rt = RuntimeCfg(attention_impl="naive")
    abstract = jax.eval_shape(lambda k: init_params(spec, rt, k),
                              jax.random.PRNGKey(0))
    params, lay = weights.make_tree(abstract, ctx.seed,
                                    ctx.config.get("init_std", {}))
    engine = Engine(spec, rt, params, batch_slots=tr["slots"],
                    kv_len=tr["kv_len"])
    return {"engine": engine, "lay": lay, "spec": spec, "rt": rt}


def reset_cache(st) -> None:
    """A fresh cache for the next wave (the old one is dropped first)."""
    from repro.models import lm
    eng = st["engine"]
    eng.cache = None
    eng.cache = lm.init_cache(st["spec"], st["rt"], len(eng.slots),
                              eng.kv_len)


def serve_wave(st, reqs: list, w=None) -> dict:
    """Submit ``reqs`` together and step the engine until all finish.
    Returns per-request first-token times and per-step live rows."""
    from repro.serve import Request
    eng = st["engine"]
    phase = w.phase if w is not None else (
        lambda name: contextlib.nullcontext())
    t_sub = time.perf_counter()
    objs = [Request(rid=i, prompt=p, max_new=o) for i, (p, o) in
            enumerate(reqs)]
    for r in objs:
        eng.submit(r)
    first: dict = {}
    live_rows: list = []
    done = 0
    limit = max(len(p) + o for p, o in reqs) + 2
    with phase("serve.wave"):
        for _ in range(limit):
            with phase("serve.step"):
                fin = eng.run(max_steps=1)
            t = time.perf_counter()
            done += len(fin)
            live_rows.append(sum(s is not None for s in eng.slots) + len(fin))
            for r in objs:
                if r.out and r.rid not in first:
                    first[r.rid] = t
            if done == len(objs):
                break
    return {"requests": objs, "ttft": [first.get(r.rid, np.inf) - t_sub
                                       for r in objs],
            "live_rows": live_rows, "finished": done}


def setup(ctx) -> dict:
    from harness import runtime_spec
    spec = runtime_spec(ctx.config)
    st = build(ctx, spec)
    # one short wave compiles every program the window uses
    rng = np.random.default_rng(0)
    warm = [(rng.integers(1, spec.vocab, size=2).astype(np.int32), 2)
            for _ in range(ctx.traffic["slots"])]
    serve_wave(st, warm)
    reset_cache(st)
    jax.block_until_ready(st["engine"].cache)
    return st


def window(ctx, st, seconds: float, trace: bool) -> dict:
    from harness import Window
    ttft, served, live, steps_per_wave = [], [], [], []
    attempted = failed = tokens = 0
    i = 0
    with Window(seconds, trace) as w:
        while w.open():
            reqs = gen.wave(ctx.traffic, st["spec"].vocab, ctx.seed, i)
            res = serve_wave(st, reqs, w)
            with w.phase("serve.cache_reset"):
                reset_cache(st)
            i += 1
            for r, t in zip(res["requests"], res["ttft"]):
                attempted += 1
                ok = r.done and len(r.out) == r.max_new
                failed += not ok
                tokens += len(r.out)
                ttft.append(t)
                served.append((r.prompt, list(r.out)))
            live.extend(res["live_rows"])
            steps_per_wave.append(len(res["live_rows"]))
        jax.block_until_ready(st["engine"].cache)
    print(f"perfbench: {i} waves, {attempted} requests, steps per wave "
          f"{steps_per_wave}, window {w.elapsed:.3f} s", flush=True)
    return {"attempted": attempted, "failed": failed, "waves": i,
            "tokens": tokens, "seconds": w.elapsed, "trace": w.reduced,
            "live_rows": live, "steps_per_wave": steps_per_wave,
            "metrics": {TOKENS: tokens / w.elapsed,
                        TTFT: float(np.percentile(ttft, 95)) * 1e3},
            "served": served, "lay": st["lay"]}


def release(ctx, st) -> None:
    st["engine"].params = st["engine"].cache = None
    st.clear()


def sample(ctx, served: list) -> list:
    """A seeded sample of the served requests, the longest among them."""
    n = min(ctx.traffic["check_requests"], len(served))
    sizes = [len(p) + len(o) for p, o in served]
    longest = int(np.argmax(sizes))
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng([ctx.seed, 7])
    pick = [longest] + sorted(rng.choice(rest, n - 1, replace=False).tolist())
    return [served[i] for i in pick]


def ref_logits(ctx, lay: list, seqs: list, mode: str) -> tuple:
    """The reference's logits over each request's prompt and served
    tokens (one batch, padded at the end), and the positions that
    predicted each served token."""
    ref = importlib.import_module(ctx.config["reference"])
    params = dict(zip([p for p, _, _ in lay], weights.make_flat(
        lay, ctx.seed, ctx.config.get("init_std", {}))))
    L = max(len(p) + len(o) - 1 for p, o in seqs)
    toks = np.zeros((len(seqs), L), np.int32)
    for j, (p, o) in enumerate(seqs):
        full = np.concatenate([p, np.asarray(o[:-1], np.int32)])
        toks[j, :len(full)] = full
    logits = ref.forward(params, jnp.asarray(toks), mode)
    return logits, [np.arange(len(p) - 1, len(p) - 1 + len(o))
                    for p, o in seqs]


def gaps(logits, positions: list, chosen: list) -> np.ndarray:
    """Per sequence, the widest gap by which a chosen token's reference
    logit lies below the reference's best at its position."""
    out = []
    for j, (pos, toks) in enumerate(zip(positions, chosen)):
        lg = np.asarray(logits[j, pos], np.float64)
        out.append(float(np.max(lg.max(-1) - lg[np.arange(len(pos)),
                                                  np.asarray(toks)])))
    return np.asarray(out)


def check(ctx, win: dict) -> dict:
    t0 = time.perf_counter()
    seqs = sample(ctx, win["served"])
    logits, positions = ref_logits(ctx, win["lay"], seqs, "f32")
    g = gaps(logits, positions, [o for _, o in seqs])
    n_tok = sum(len(o) for _, o in seqs)
    print(f"perfbench: reference {time.perf_counter() - t0:.1f} s over "
          f"{len(seqs)} requests, {n_tok} served tokens; per-request "
          f"widest gaps {g.tolist()}", flush=True)
    return {"served_logit_gap": {"value": float(g.max()),
                                 "limit": ctx.limits["served_logit_gap"]}}
