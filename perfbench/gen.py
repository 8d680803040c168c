"""Inputs from the seed: training token batches, serving waves, and the
seeded random weights.  The token hash is a copy of the program's
synthetic data pipeline (``repro.data.pipeline._hash_tokens``), kept here
so that the yardstick does not move when the program does."""
from __future__ import annotations

import math

import numpy as np


def hash_tokens(step: int, rows: np.ndarray, seq: int, vocab: int,
                seed: int) -> np.ndarray:
    """Counter-based token synthesis: tokens = h(step, row, col) % vocab."""
    col = np.arange(seq, dtype=np.uint64)[None, :]
    row = rows.astype(np.uint64)[:, None]
    x = (row * np.uint64(2654435761) ^ col * np.uint64(40503)
         ^ np.uint64((step * 997 + seed * 1_000_003 + 12345) % 2**64))
    x ^= x >> np.uint64(33)
    x *= np.uint64(0xFF51AFD7ED558CCD)
    x ^= x >> np.uint64(33)
    return (x % np.uint64(vocab)).astype(np.int32)


def train_batch(step: int, batch: int, seq: int, vocab: int,
                seed: int) -> dict:
    """Global batch ``step``: every step's rows differ."""
    t = hash_tokens(step, np.arange(batch), seq + 1, vocab, seed)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


def lognormal_quantiles(n: int, mean: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 1/2) / n of the lognormal
    of this ``mean`` and ``sigma``, clipped to [lo, hi]: the same sizes
    for every seed."""
    from statistics import NormalDist
    median = mean * math.exp(-sigma ** 2 / 2)
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def wave_sizes(tr: dict) -> list:
    """The fixed (prompt, output) length pairs of one serving wave: the
    prompt quantiles paired with the output quantiles in a fixed
    shuffled order, so sizes are uncorrelated and identical per seed."""
    n = tr["slots"]
    p = lognormal_quantiles(n, **tr["prompt_len"])
    o = lognormal_quantiles(n, **tr["output_len"])
    o = o[np.random.default_rng(12345).permutation(n)]
    return list(zip(p.tolist(), o.tolist()))


def wave(tr: dict, vocab: int, seed: int, index: int) -> list:
    """Wave ``index`` of a run: its size pairs in a seeded slot order,
    each with a seeded prompt of uniform token ids in [1, vocab)."""
    rng = np.random.default_rng([seed, index])
    sizes = wave_sizes(tr)
    order = rng.permutation(len(sizes))
    return [(rng.integers(1, vocab, size=sizes[i][0]).astype(np.int32),
             sizes[i][1]) for i in order]


def split_seed(seed: int) -> tuple:
    """A seed of any size as two 31-bit words (jax keys take 32 bits)."""
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def leaf_std(name: str, shape: tuple, overrides: dict) -> float:
    """Fan-in scaled normal, as the program initialises: embedding 1;
    output projections over all their input dims; others over dim 0."""
    if name in overrides:
        return float(overrides[name])
    if name == "embed":
        return 1.0
    if name in ("w_o", "w_tmo"):
        return 1.0 / math.sqrt(math.prod(shape[:-1]))
    return 1.0 / math.sqrt(shape[0])
