"""Logical-axis -> mesh-axis sharding rules (GSPMD side of the house).

The same logical names the STAGE core reasons about ("vocab", "heads",
"ffn", "experts", ...) are mapped here onto physical mesh axes, so the
analytical plan and the compiled program shard identically:

* model-parallel logical axes -> the ``model`` mesh axis (Megatron TP),
* batch -> ``("pod", "data")`` (DP across pods and within),
* ``act_seq`` -> ``model`` when sequence-parallelism is on,
* FSDP variant: weight ``embed`` dims additionally sharded over data.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import AxisRules, Param, paxes


def logical_rules(*, sp: bool = True, fsdp: bool = False,
                  shard_kv_heads: bool = True,
                  data_axes: tuple = ("pod", "data"),
                  model_axis: str = "model",
                  extra: dict | None = None) -> dict[str, Any]:
    rules: dict[str, Any] = {
        "vocab": model_axis,
        "heads": model_axis,
        "kv_heads": model_axis if shard_kv_heads else None,
        "q_grp": None if shard_kv_heads else model_axis,
        "ffn": model_axis,
        "experts": model_axis,
        "embed": data_axes if fsdp else None,
        "lora": None,
        "head_dim": None,
        "state": None,
        "router": None,
        "conv": None,
        "layers": None,
        "act_batch": data_axes,
        "act_seq": model_axis if sp else None,
        "act_kv": None,
        "act_cap": data_axes,
    }
    rules.update(extra or {})
    return rules


def axis_rules(mesh: Mesh, **kw) -> AxisRules:
    return AxisRules(logical_rules(**kw))


def data_axes_of(mesh: Mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def shard_plan(spec, model: int) -> tuple[bool, bool]:
    """``(shard_kv_heads, fsdp)`` for ``spec`` on a ``model``-way model
    axis.  KV heads shard when they divide it (never for MLA).  FSDP
    (ZeRO-3) weights over data when attention is unshardable over model
    (neither kv heads nor query groups divide it: qwen3 / minitron /
    internvl at 16) or the model is MoE (expert weights would otherwise
    replicate across the data axes)."""
    kv_ok = spec.n_kv_heads % model == 0 and spec.block != "mla"
    grp_ok = max(1, spec.n_heads // max(1, spec.n_kv_heads)) % model == 0
    fsdp = (spec.moe is not None) or \
        not (kv_ok or grp_ok or spec.block in ("mla", "rwkv6"))
    return kv_ok, fsdp


def arch_rules(spec, mesh: Mesh, *, sp: bool = True,
               overrides: Optional[dict] = None) -> dict:
    """Per-arch logical->mesh rules with divisibility-driven choices."""
    kv_ok, fsdp = shard_plan(spec, mesh.shape["model"])
    return logical_rules(sp=sp, fsdp=fsdp, shard_kv_heads=kv_ok,
                         data_axes=data_axes_of(mesh), extra=overrides)


def _divisible(shape, axes_entry, mesh: Mesh, dim: int) -> bool:
    if axes_entry is None:
        return True
    names = axes_entry if isinstance(axes_entry, (tuple, list)) else (axes_entry,)
    deg = int(np.prod([mesh.shape[n] for n in names]))
    return shape[dim] % deg == 0


def param_pspec(p: Param, rules: dict, mesh: Mesh) -> P:
    """PartitionSpec for one param; skips non-divisible dims (e.g. MQA
    kv_heads=1 cannot shard over model — exactly the STG role rule)."""
    entries = []
    used: set = set()
    for dim, name in enumerate(p.axes):
        e = rules.get(name)
        if e is not None:
            names = tuple(e) if isinstance(e, (tuple, list)) else (e,)
            names = tuple(n for n in names if n not in used)
            e = names if names else None
        if e is None or not _divisible(p.shape, e, mesh, dim):
            entries.append(None)
            continue
        used.update(e if isinstance(e, tuple) else (e,))
        entries.append(e if isinstance(e, tuple) and len(e) > 1
                       else (e[0] if isinstance(e, tuple) else e))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_shardings(params, rules: dict, mesh: Mesh):
    """NamedSharding tree matching the Param tree."""
    return jax.tree.map(
        lambda p: NamedSharding(mesh, param_pspec(p, rules, mesh)), params,
        is_leaf=lambda x: isinstance(x, Param))


def batch_pspec(data_axes: tuple = ("pod", "data")) -> P:
    return P(data_axes)


def cache_shardings(cache, mesh: Mesh, *, model_axis: str = "model",
                    data_axes: tuple = ("pod", "data")):
    """Decode caches: batch over data axes, heads/kv dims over model."""
    def spec(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return NamedSharding(mesh, P())
        entries: list = [None] * x.ndim
        # leading 'layers' stack dim possible; batch dim is the first dim
        # whose size matches nothing special — use heuristic: shard dim 0
        # over data if divisible, plus the kv-head dim over model if any.
        deg = int(np.prod([mesh.shape[n] for n in data_axes]))
        start = 0
        if x.ndim >= 3 and x.shape[0] != 0 and x.shape[0] % deg != 0 \
                and x.shape[1] % deg == 0:
            start = 1                       # stacked [n_rep, B, ...]
        if x.shape[start] % deg == 0:
            entries[start] = data_axes
        return NamedSharding(mesh, P(*entries))
    return jax.tree.map(spec, cache)
