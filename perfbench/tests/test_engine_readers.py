"""Self-check of the serve engine's per-layer readers on a fake reading.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_engine_readers.py

The program's registry (``repro.obs.metrics.REGISTRY``) is filled as a
run leaves it: set-up's warm-up wave first, with values no window sample
has, then the window's own samples.  Each reader has to read the
window's samples alone, by the window's own counts, and nothing where
the registry holds fewer samples than the window counts.
"""
import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

SLOTS = 4
WIN = {"waves": 3, "attempted": 12, "steps_per_wave": [5, 6, 4]}
N_STEPS = sum(WIN["steps_per_wave"])
WARM = 1e3                     # every warm-up sample reads this

rng = np.random.default_rng(0)
SAMPLES = {                    # histogram: (warm-up count, window samples)
    "engine.admit_s": (1, rng.uniform(1.0, 4.0, WIN["waves"]).tolist()),
    "engine.queue_wait_s": (SLOTS, rng.uniform(0.1, 4.0,
                                               WIN["attempted"]).tolist()),
    "engine.host_gap_s": (3, rng.uniform(1e-3, 3e-3, N_STEPS).tolist()),
    "engine.useful_rows": (3, rng.integers(0, SLOTS + 1,
                                           N_STEPS).astype(float).tolist()),
}


def reading():
    from harness import Context, Reading
    ctx = Context(name="granite-34b.serve", seed=1, config={},
                  traffic={"slots": SLOTS}, limits={})
    return Reading(ctx=ctx, window=dict(WIN), trace={}, compile_s=0.0)


@pytest.fixture
def registry():
    from repro.obs import metrics
    metrics.reset()
    for name, (n_warm, xs) in SAMPLES.items():
        h = metrics.histogram(name)
        for x in [WARM] * n_warm + xs:
            h.observe(x)
    yield metrics
    metrics.reset()


def expected(metric: str) -> float:
    xs = {m: SAMPLES[h][1] for m, h in (
        ("admit_ms.serve", "engine.admit_s"),
        ("queue_wait_ms_p95.serve", "engine.queue_wait_s"),
        ("host_gap_ms.serve", "engine.host_gap_s"),
        ("useful_row_share.serve", "engine.useful_rows"))}[metric]
    if metric == "queue_wait_ms_p95.serve":
        return float(np.percentile(xs, 95)) * 1e3
    if metric == "useful_row_share.serve":
        return 100.0 * sum(xs) / (SLOTS * N_STEPS)
    return statistics.median(xs) * 1e3


METRICS = ["admit_ms.serve", "queue_wait_ms_p95.serve", "host_gap_ms.serve",
           "useful_row_share.serve"]


@pytest.mark.parametrize("metric", METRICS)
def test_reader_reads_the_window_alone(registry, metric):
    from harness import load_reader
    got = load_reader(metric)(reading())
    assert got == pytest.approx(expected(metric), rel=1e-12)


@pytest.mark.parametrize("metric", METRICS)
def test_reader_finds_nothing_without_samples(metric):
    from harness import load_reader
    from repro.obs import metrics
    metrics.reset()
    assert load_reader(metric)(reading()) is None
