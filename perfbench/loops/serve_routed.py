"""Serving waves of ``loops/serve.py`` for a model with routed experts,
checked by two numbers.

Set-up, window and release are the serve loop's own.  After the window
the same seeded sample of finished requests goes through the plain
reference's full forward, and each served token is judged by the gap
by which its reference logit lies below the reference's best there:
``served_logit_gap`` is the widest gap, as the serve loop reports it,
and ``served_logit_gap_mean`` the mean gap over every served token of
the sample.

Why both: top-k routing turns on near-ties.  Rounding the hidden state
to bfloat16 moves a token to another expert now and then (on
deepseek-v2 at published widths ~6% of the tokens of a layer, ~1.5%
into or out of the held group), and that token's logits move by up to
~1.  The widest gap over thousands of served tokens reads such a move
in every run, and the reference in float8 reads no wider, so the widest
gap alone cannot tell a program that computes in the configuration's
precision from one that computes below it.  The mean weighs each token
once: routing moves stay rare, while float8 rounding moves every token's
logits.  The widest gap, with a limit above every sound reading, still
catches a fault that hits a few tokens hard.

``readings`` gives the control for this loop: the program's two numbers
and those of the tokens the float8 reference puts first, both under the
float32 reference.

Traffic keys: those of ``loops/serve.py``.
"""
from __future__ import annotations

import gc
import time

import jax.numpy as jnp
import numpy as np

from harness import load_loop

serve = load_loop("serve")
TOKENS, TTFT = serve.TOKENS, serve.TTFT
setup, window, release = serve.setup, serve.window, serve.release


def token_gaps(logits, positions: list, chosen: list) -> list:
    """Per sequence, each chosen token's gap below the reference's best
    at its position."""
    out = []
    for j, (pos, toks) in enumerate(zip(positions, chosen)):
        lg = np.asarray(logits[j, pos], np.float64)
        out.append(lg.max(-1) - lg[np.arange(len(pos)), np.asarray(toks)])
    return out


def numbers(gaps: list) -> dict:
    flat = np.concatenate(gaps)
    return {"served_logit_gap": float(flat.max()),
            "served_logit_gap_mean": float(flat.mean())}


def check(ctx, win: dict) -> dict:
    t0 = time.perf_counter()
    seqs = serve.sample(ctx, win["served"])
    logits, positions = serve.ref_logits(ctx, win["lay"], seqs, "f32")
    g = token_gaps(logits, positions, [o for _, o in seqs])
    n_tok = sum(len(o) for _, o in seqs)
    print(f"perfbench: reference {time.perf_counter() - t0:.1f} s over "
          f"{len(seqs)} requests, {n_tok} served tokens; per-request "
          f"widest gaps {[float(x.max()) for x in g]}, mean gaps "
          f"{[float(x.mean()) for x in g]}", flush=True)
    return {k: {"value": v, "limit": ctx.limits[k]}
            for k, v in numbers(g).items()}


def readings(ctx, seconds: float) -> dict:
    """One short window of the program, then for the same prompts and
    served tokens both numbers, under the float32 reference, of the
    tokens the program served and of those the float8 reference puts
    first."""
    st = setup(ctx)
    win = window(ctx, st, seconds, False)
    release(ctx, st)
    del st
    gc.collect()
    seqs = serve.sample(ctx, win["served"])
    lg32, pos = serve.ref_logits(ctx, win["lay"], seqs, "f32")
    prog = numbers(token_gaps(lg32, pos, [o for _, o in seqs]))
    lg8, _ = serve.ref_logits(ctx, win["lay"], seqs, "fp8")
    first8 = [np.asarray(jnp.argmax(lg8[j, p], -1)) for j, p in
              enumerate(pos)]
    return {"program": prog,
            "control": numbers(token_gaps(lg32, pos, first8)),
            "tokens": int(sum(len(o) for _, o in seqs))}
