"""Architecture registry: one module per assigned arch, each exposing

* ``SPEC``    — full-size :class:`repro.core.ModelSpec` (exact assignment),
* ``SMOKE``   — reduced same-family spec for CPU tests,
* ``RUNTIME`` — :class:`repro.models.common.RuntimeCfg`,
* ``SHAPES``  — which workload shapes apply (+ skip reasons).

``--arch <id>`` everywhere resolves through :func:`get`.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass
from typing import Optional

from repro.core import ModelSpec
from repro.models.common import RuntimeCfg

ARCHS = (
    "granite-34b", "gemma2-27b", "qwen3-14b", "minitron-8b", "whisper-medium",
    "deepseek-moe-16b", "deepseek-v2-236b", "internvl2-26b", "jamba-v0.1-52b",
    "rwkv6-7b",
)


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str              # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

# long_500k needs sub-quadratic sequence handling: a full-attention
# decoder would hold and read every one of the 524,288 positions in each
# layer's KV cache.  Run it only for the SSM / hybrid / sliding-window
# archs, whose recurrent states and windows bound most layers' caches.
LONG_OK = {"rwkv6-7b", "jamba-v0.1-52b", "gemma2-27b"}


@dataclass(frozen=True)
class Arch:
    name: str
    spec: ModelSpec
    smoke: ModelSpec
    runtime: RuntimeCfg
    skip: dict            # shape name -> reason (absent = runs)

    def shapes(self):
        for s in SHAPES.values():
            if s.name not in self.skip:
                yield s


def get(name: str) -> Arch:
    mod = importlib.import_module(
        "repro.configs." + name.replace("-", "_").replace(".", "_"))
    skip = dict(getattr(mod, "SKIP", {}))
    if name not in LONG_OK and "long_500k" not in skip:
        skip["long_500k"] = ("pure full-attention decoder: 524k dense-KV "
                             "decode skipped per assignment")
    return Arch(name=name, spec=mod.SPEC, smoke=mod.SMOKE,
                runtime=getattr(mod, "RUNTIME", RuntimeCfg()), skip=skip)


def all_archs():
    return [get(a) for a in ARCHS]


def cut_depth(spec: ModelSpec, n_layers: int) -> ModelSpec:
    """``spec`` at its published widths with ``n_layers`` decoder layers.

    Only whole periods of the layer pattern (after any unstacked prefix)
    are kept, so the cut model runs the same mix of layer kinds; any
    other depth raises ``ValueError``."""
    from repro.models.lm import layer_pattern
    prefix, period = layer_pattern(spec)
    if not (prefix < n_layers <= spec.n_layers
            and (n_layers - prefix) % period == 0):
        raise ValueError(
            f"{spec.name}: --layers {n_layers} must be {prefix} prefix "
            f"layer(s) plus whole periods of {period}, at most "
            f"{spec.n_layers}")
    cut = dataclasses.replace(spec, n_layers=n_layers)
    assert layer_pattern(cut) == (prefix, period), spec.name
    return cut
