"""Self-check of the trace reduction on a small trace recorded on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_xplane.py

A jitted program runs between host sleeps, each in its own annotation;
the reduction has to find the program's operations busy, the sleeps idle
and named, and the window's length as the host measured it.
"""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH)]

SLEEP_S = 0.05


@pytest.fixture(scope="module")
def reduced():
    import jax
    import jax.numpy as jnp

    from harness import Window

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    with Window(10.0, True) as w:
        for _ in range(4):
            with w.phase("test.compute"):
                f(x).block_until_ready()
            with w.phase("test.sleep"):
                time.sleep(SLEEP_S)
    return w.reduced, w.elapsed


def test_window_matches_host_clock(reduced):
    r, elapsed = reduced
    assert 4 * SLEEP_S < r["window_s"] <= elapsed * 1.01


def test_busy_and_idle(reduced):
    r, _ = reduced
    assert 0 < r["busy_s"] < r["window_s"] - 4 * SLEEP_S * 0.9


def test_idle_gaps_named_by_host_phase(reduced):
    r, _ = reduced
    gaps = dict(r["idle_gaps"])
    assert gaps.get("test.sleep", 0) >= 4 * SLEEP_S * 0.9
    assert max(gaps, key=gaps.get) == "test.sleep"


def test_device_ops_and_programs(reduced):
    r, _ = reduced
    assert r["device_ops"] and all(t > 0 for _, t in r["device_ops"])
    progs = {n: m for n, m in r["modules"].items() if "lambda" in n}
    assert sum(m["count"] for m in progs.values()) == 4
    assert r["phase_counts"] == {"test.compute": 4, "test.sleep": 4}
