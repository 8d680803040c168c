"""Helpers shared by the per-layer metric readers (``<metric>.py``)."""
from __future__ import annotations

import flops


def idle_share(r) -> float:
    """Percent of the traced window in which no operation ran on the
    device (averaged over the chips used)."""
    t = r.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def runtime_spec(r) -> tuple:
    """The configuration's sizes and the depth the runtime cells run."""
    return r.ctx.config["spec"], r.ctx.config["runtime_layers"]


def peak_share(flops_total: float, seconds: float, r) -> float:
    return 100.0 * flops_total / seconds / r.peak()["bf16_flops_per_s"]


__all__ = ["flops", "idle_share", "runtime_spec", "peak_share"]
