"""Generic decoder LM covering all 10 assigned architectures.

The layer stack is grouped into a repeating *period* (gemma2: 2 =
local+global; jamba: 8 = 7×mamba+1×attn with MoE every 2nd; others: 1)
and executed with ``jax.lax.scan`` over period groups — params for each
slot are stacked ``[n_rep, ...]`` so the HLO stays compact for the
512-device dry-run and remat applies per group.

API:
  init_params(spec, rt, key)             -> Param tree
  forward(params, spec, rt, rules, ...)  -> logits  (train / prefill)
  loss_fn(params, batch, ...)            -> scalar
  init_cache(spec, rt, batch, kv_len)    -> decode cache
  decode_step(params, cache, tokens,...) -> (logits, cache[, routed])
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from .common import AxisRules, Initializer, Param, RuntimeCfg, dt, pvalue

# ---------------------------------------------------------------------------
# Layer pattern
# ---------------------------------------------------------------------------


def _slot_kind(spec, layer: int) -> dict:
    """Describe layer ``layer``: mixer kind, window, ffn kind."""
    mixer = "attn"
    if spec.block == "rwkv6":
        mixer = "rwkv"
    elif spec.block == "mamba" and spec.attn_every <= 1:
        mixer = "mamba"
    elif spec.attn_every > 1:
        mixer = "attn" if layer % spec.attn_every == spec.attn_offset else "mamba"
    window = spec.window if spec._is_local_layer(layer) else None
    if mixer != "attn":
        window = None
    if spec._is_moe_layer(layer):
        ffn = "moe"
    elif mixer == "rwkv":
        ffn = None                       # channel-mix lives inside the block
    elif spec.block == "mamba" and spec.attn_every <= 1:
        ffn = None                       # pure-mamba: no separate FFN
    else:
        ffn = "ffn"
    return {"mixer": mixer, "window": window, "ffn": ffn}


def layer_pattern(spec) -> tuple[int, int]:
    """(n_prefix_unstacked, period).  Pattern repeats every ``period``
    layers after the prefix."""
    prefix = 1 if (spec.moe and spec.moe.first_dense) else 0
    n = spec.n_layers - prefix
    period = 1
    if spec.attn_every > 1:
        period = np.lcm(period, spec.attn_every)
    if spec.moe and spec.moe.every > 1:
        period = np.lcm(period, spec.moe.every)
    if spec.window_pattern == "alternate":
        period = np.lcm(period, 2)
    period = int(period)
    if n % period != 0:
        period = 1 if n == 0 else math.gcd(period, n)
    # verify the pattern truly repeats
    for l in range(prefix, spec.n_layers):
        base = prefix + (l - prefix) % period
        if _slot_kind(spec, l) != _slot_kind(spec, base):
            return (spec.n_layers, 1)    # fully unstacked fallback
    return (prefix, period)


def _init_slot(ini: Initializer, spec, kind: dict, prefix: str) -> dict:
    p: dict = {}
    if kind["mixer"] == "attn":
        if spec.block == "mla":
            p["attn"] = L.init_mla(ini, spec, prefix + "a_")
        else:
            p["attn"] = L.init_gqa(ini, spec, prefix + "a_")
    elif kind["mixer"] == "mamba":
        p["mamba"] = L.init_mamba(ini, spec, prefix + "m_")
    else:
        p["rwkv"] = L.init_rwkv6(ini, spec, prefix + "r_")
    if kind["ffn"] == "moe":
        p["moe"] = L.init_moe(ini, spec, prefix + "f_")
    elif kind["ffn"] == "ffn":
        p["ffn"] = L.init_ffn(ini, spec, prefix=prefix + "f_")
    return p


def init_params(spec, rt: RuntimeCfg, key) -> dict:
    ini = Initializer(key, rt.param_dtype)
    H, V = spec.d_model, spec.vocab
    params: dict = {
        "embed": ini("embed", (V, H), (L.VOCAB, L.EMB), scale=1.0),
        "ln_f": ini("ln_f", (H,), (L.EMB,)),
        "lm_head": ini("lm_head", (H, V), (L.EMB, L.VOCAB)),
    }
    if spec.encoder_layers:
        enc_kind = {"mixer": "attn", "window": None, "ffn": "ffn"}
        reps = [_init_slot(ini, spec, enc_kind, f"enc{i}_")
                for i in range(spec.encoder_layers)]
        params["encoder"] = _stack(reps)
        params["ln_enc"] = ini("ln_enc", (H,), (L.EMB,))
        # decoder cross-attention (one per decoder layer; period must be 1)
        params["cross"] = _stack([L.init_gqa(ini, spec, f"x{i}_")
                                  for i in range(spec.n_layers)])
    prefix_n, period = layer_pattern(spec)
    params["prefix"] = [
        _init_slot(ini, spec, _slot_kind(spec, l), f"pl{l}_")
        for l in range(prefix_n)]
    n_rep = (spec.n_layers - prefix_n) // period if period else 0
    params["slots"] = []
    for s in range(period):
        kind = _slot_kind(spec, prefix_n + s)
        reps = [_init_slot(ini, spec, kind, f"l{r}s{s}_") for r in range(n_rep)]
        params["slots"].append(_stack(reps))
    return params


def _stack(reps: list) -> Any:
    if not reps:
        return {}
    def stack_leaf(*leaves):
        vals = jnp.stack([l.value for l in leaves])
        return Param(vals, ("layers",) + leaves[0].axes)
    return jax.tree.map(stack_leaf, *reps,
                        is_leaf=lambda x: isinstance(x, Param))


def _index(tree, i):
    return jax.tree.map(lambda p: Param(p.value[i], p.axes[1:]), tree,
                        is_leaf=lambda x: isinstance(x, Param))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _apply_slot(p: dict, x, spec, rt, rules, kind: dict, *,
                positions=None, cache=None, pos=None, cross_kv=None,
                cross_p=None, layer=None):
    """One layer: (x, its new cache or None, the tokens routed to each
    held expert [held] for an MoE layer, else None).  Decoding, ``cache``
    is the layer's cache and ``pos`` the position of x's first token.
    With ``layer`` the cache, and an MoE layer's expert weights in ``p``,
    are the stacks of the slot's layers and this is layer ``layer`` of
    them (``layers.write_cache``, ``layers.moe_ffn``)."""
    c = cache or {}
    new_cache: dict = {}
    routed = None
    if kind["mixer"] == "attn":
        attn = L.mla_attention if spec.block == "mla" else functools.partial(
            L.gqa_attention, window=kind["window"])
        x, new_cache["attn"] = attn(p["attn"], x, spec, rt, rules,
                                    positions=positions, cache=c.get("attn"),
                                    pos=pos, layer=layer)
    elif kind["mixer"] == "mamba":
        x, new_cache["mamba"] = L.mamba_layer(p["mamba"], x, spec, rt, rules,
                                              cache=c.get("mamba"), layer=layer)
    else:
        x, new_cache["rwkv"] = L.rwkv6_layer(p["rwkv"], x, spec, rt, rules,
                                             cache=c.get("rwkv"), layer=layer)
    if cross_p is not None:
        x, new_cache["cross"] = L.gqa_attention(
            cross_p, x, spec, rt, rules, cross_kv=cross_kv,
            cache=c.get("cross"), layer=layer)
    if kind["ffn"] == "moe":
        x, routed = L.moe_ffn(p["moe"], x, spec, rt, rules, with_counts=True,
                              layer=layer)
    elif kind["ffn"] == "ffn":
        x = L.ffn(p["ffn"], x, spec, rt, rules)
    return x, (new_cache if cache is not None else None), routed


def _remat(fn, rt: RuntimeCfg):
    if rt.remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    return fn


def _run_encoder(params, frames, spec, rt, rules):
    x = frames.astype(dt(rt.compute_dtype))
    enc_kind = {"mixer": "attn", "window": None, "ffn": "ffn"}

    def enc_block(xc, pc):
        h, _ = L.gqa_attention(pc["attn"], xc, spec, rt, rules, causal=False)
        h = L.ffn(pc["ffn"], h, spec, rt, rules)
        return h, None

    if spec.encoder_layers:
        x, _ = jax.lax.scan(_remat(enc_block, rt), x, params["encoder"])
        x = L.rms_norm(params["ln_enc"], x)
    return x


def forward(params: dict, tokens, spec, rt: RuntimeCfg,
            rules: Optional[AxisRules] = None, *, frames=None,
            vision=None, positions=None) -> jax.Array:
    """Training / prefill forward -> logits [B, S(+Sv), V]."""
    with jax.named_scope("embed"):
        x = params["embed"].value.astype(dt(rt.compute_dtype))[tokens]
        x = L.constrain(x, rules, (L.BATCH, L.SEQ, L.EMB))
    if vision is not None:
        x = jnp.concatenate([vision.astype(x.dtype), x], axis=1)
    cross_kv = None
    if spec.encoder_layers:
        cross_kv = _run_encoder(params, frames, spec, rt, rules)

    prefix_n, period = layer_pattern(spec)
    layer_idx = 0
    for p in params["prefix"]:
        kind = _slot_kind(spec, layer_idx)

        def prefix_block(xc, pc, kind=kind):
            return _apply_slot(pc, xc, spec, rt, rules, kind,
                               positions=positions)[0]
        x = _remat(prefix_block, rt)(x, p)
        layer_idx += 1

    if params["slots"] and period:
        kinds = [_slot_kind(spec, prefix_n + s) for s in range(period)]

        def group(xc, slot_params):
            h = xc
            for s in range(period):
                h = _apply_slot(slot_params[s], h, spec, rt, rules, kinds[s],
                                positions=positions,
                                cross_kv=cross_kv,
                                cross_p=slot_params[period] if spec.encoder_layers else None)[0]
            return h, None

        scanned = list(params["slots"])
        if spec.encoder_layers:
            scanned = scanned + [params["cross"]]
        x, _ = jax.lax.scan(_remat(group, rt), x, tuple(scanned))

    with jax.named_scope("lm_head"):
        x = L.rms_norm(params["ln_f"], x)
        x = L.constrain(x, rules, (L.BATCH, L.SEQ, L.EMB))
        logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"].value.astype(
            dt(rt.compute_dtype)))
        logits = L.constrain(logits, rules, (L.BATCH, L.SEQ, L.VOCAB))
        if spec.final_softcap:
            logits = L._softcap(logits.astype(jnp.float32), spec.final_softcap)
    return logits


def loss_fn(params: dict, batch: dict, spec, rt: RuntimeCfg,
            rules: Optional[AxisRules] = None) -> jax.Array:
    logits = forward(params, batch["tokens"], spec, rt, rules,
                     frames=batch.get("frames"), vision=batch.get("vision"))
    with jax.named_scope("loss"):
        return _cross_entropy(logits, batch["labels"])


def _cross_entropy(logits, labels) -> jax.Array:
    """Mean token cross-entropy of ``logits`` against ``labels``."""
    if logits.shape[1] != labels.shape[1]:       # VLM: vision positions unlabeled
        logits = logits[:, -labels.shape[1]:]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------


def _slot_cache(spec, rt, kind: dict, batch: int, kv_len: int) -> dict:
    if kind["mixer"] == "attn":
        c = {"attn": L.init_mla_cache(spec, rt, batch, kv_len)
             if spec.block == "mla"
             else L.init_gqa_cache(spec, rt, batch, kv_len, kind["window"])}
    elif kind["mixer"] == "mamba":
        c = {"mamba": L.init_mamba_cache(spec, rt, batch)}
    else:
        c = {"rwkv": L.init_rwkv6_cache(spec, rt, batch)}
    if spec.encoder_layers:
        c["cross"] = L.init_gqa_cache(spec, rt, batch, spec.enc_seq)
    return c


def init_cache(spec, rt: RuntimeCfg, batch: int, kv_len: int) -> dict:
    """The decode cache: ``pos``, the next position to write (one int32
    for every layer and row); each prefix layer's cache; and each
    scanned slot's caches stacked over its layers."""
    prefix_n, period = layer_pattern(spec)
    n_rep = (spec.n_layers - prefix_n) // period if period else 0
    cache: dict = {
        "pos": jnp.zeros((), jnp.int32),
        "prefix": [_slot_cache(spec, rt, _slot_kind(spec, l), batch, kv_len)
                   for l in range(prefix_n)],
        "slots": [],
    }
    for s in range(period):
        kind = _slot_kind(spec, prefix_n + s)
        reps = [_slot_cache(spec, rt, kind, batch, kv_len) for _ in range(n_rep)]
        cache["slots"].append(jax.tree.map(lambda *ls: jnp.stack(ls), *reps)
                              if reps else {})
    return cache


def decode_step(params: dict, cache: dict, tokens, spec, rt: RuntimeCfg,
                rules: Optional[AxisRules] = None, *,
                routed: bool = False) -> tuple:
    """One decode step: tokens [B, S] -> (logits [B,S,V], new cache), the
    tokens written from the cache's ``pos``, which comes back advanced by
    S.  With ``routed`` also the tokens of all B rows routed to each held
    expert of each MoE layer, in layer order: [MoE layers, held] int32
    (None for a model without MoE layers).

    Each scanned slot's whole cache stack is carried through the layer
    scan, and each layer writes its own part of it (its new positions,
    or its whole entry for a ring window, mamba or rwkv state); with the
    cache donated, as ``Engine`` does, the whole cache is updated in
    place."""
    with jax.named_scope("embed"):
        x = params["embed"].value.astype(dt(rt.compute_dtype))[tokens]
    pos = cache["pos"]
    prefix_n, period = layer_pattern(spec)
    new_cache = {"pos": pos + tokens.shape[1], "prefix": [],
                 "slots": cache["slots"]}
    counts = []            # (layer index, [held]) of each MoE layer
    for li, (p, c) in enumerate(zip(params["prefix"], cache["prefix"])):
        x, nc, r = _apply_slot(p, x, spec, rt, rules, _slot_kind(spec, li),
                               cache=c, pos=pos)
        new_cache["prefix"].append(nc)
        if r is not None:
            counts.append((li, r))

    n_rep = (spec.n_layers - prefix_n) // period
    kinds = [_slot_kind(spec, prefix_n + s) for s in range(period)]
    ps, experts = list(params["slots"]), [None] * period
    for s in range(period):
        if kinds[s]["ffn"] == "moe" and n_rep:
            # the scan reads the experts from the whole stack: no layer's
            # experts are sliced out of it each step
            moe = dict(ps[s]["moe"])
            experts[s] = {k: moe.pop(k) for k in L.EXPERT_WEIGHTS}
            ps[s] = {**ps[s], "moe": moe}

    def group(carry, xs):
        """Layers ``prefix_n + i * period + s`` for each slot ``s``."""
        (h, caches, i), pcs = carry, xs[0]
        cross_p = xs[1] if spec.encoder_layers else None
        caches, rs = list(caches), []
        for s in range(period):
            pc = pcs[s]
            if experts[s] is not None:
                pc = {**pc, "moe": {**pc["moe"], **experts[s]}}
            h, caches[s], r = _apply_slot(
                pc, h, spec, rt, rules, kinds[s], cache=caches[s], pos=pos,
                cross_p=cross_p, layer=i)
            rs.append(r)
        return (h, caches, i + 1), rs

    if n_rep:
        scanned = (ps, params["cross"]) if spec.encoder_layers else (ps,)
        (x, new_cache["slots"], _), rs = jax.lax.scan(
            group, (x, cache["slots"], jnp.int32(0)), scanned)
        for s, r in enumerate(rs):
            if r is not None:
                counts += [(prefix_n + s + i * period, r[i])
                           for i in range(n_rep)]

    with jax.named_scope("lm_head"):
        x = L.rms_norm(params["ln_f"], x)
        logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"].value.astype(
            dt(rt.compute_dtype)))
        if spec.final_softcap:
            logits = L._softcap(logits.astype(jnp.float32), spec.final_softcap)
    if not routed:
        return logits, new_cache
    counts = jnp.stack([r for _, r in sorted(counts, key=lambda c: c[0])]) \
        if counts else None
    return logits, new_cache, counts
