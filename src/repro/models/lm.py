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
                positions=None, cache=None, cross_kv=None, cross_p=None,
                cross_cache=None, moe_layer=None, layer=None):
    """One layer: (x, its new cache or None, the tokens routed to each
    held expert [held] for an MoE layer, else None).  ``moe_layer``: the
    expert weights in ``p`` are the stack of the slot's layers and this
    is layer ``moe_layer`` of it (``layers.moe_ffn``); ``layer``: so is
    the attention cache (``layers.write_cache``)."""
    new_cache: dict = {}
    routed = None
    if kind["mixer"] == "attn":
        if spec.block == "mla":
            x, c = L.mla_attention(p["attn"], x, spec, rt, rules,
                                   positions=positions, layer=layer,
                                   cache=None if cache is None else cache.get("attn"))
        else:
            x, c = L.gqa_attention(p["attn"], x, spec, rt, rules,
                                   positions=positions, window=kind["window"],
                                   layer=layer,
                                   cache=None if cache is None else cache.get("attn"))
        if c is not None:
            new_cache["attn"] = c
    elif kind["mixer"] == "mamba":
        x, c = L.mamba_layer(p["mamba"], x, spec, rt, rules,
                             cache=None if cache is None else cache.get("mamba"))
        if c is not None:
            new_cache["mamba"] = c
    else:
        x, c = L.rwkv6_layer(p["rwkv"], x, spec, rt, rules,
                             cache=None if cache is None else cache.get("rwkv"))
        if c is not None:
            new_cache["rwkv"] = c
    if cross_p is not None:
        x, cc = L.gqa_attention(cross_p, x, spec, rt, rules,
                                cross_kv=cross_kv, cache=cross_cache)
        if cache is not None and cc is not None:
            new_cache["cross"] = cc
    if kind["ffn"] == "moe":
        x, routed = L.moe_ffn(p["moe"], x, spec, rt, rules, with_counts=True,
                              layer=moe_layer)
    elif kind["ffn"] == "ffn":
        x = L.ffn(p["ffn"], x, spec, rt, rules)
    return x, (new_cache or None), routed


def _remat(fn, rt: RuntimeCfg):
    if rt.remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    if rt.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
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
        return _cross_entropy(logits, batch["labels"], rt)


def _cross_entropy(logits, labels, rt: RuntimeCfg) -> jax.Array:
    """Mean token cross-entropy of ``logits`` against ``labels``."""
    if logits.shape[1] != labels.shape[1]:       # VLM: vision positions unlabeled
        logits = logits[:, -labels.shape[1]:]
    s = labels.shape[1]
    if rt.loss_chunk and s % rt.loss_chunk == 0 and s > rt.loss_chunk:
        # scan the CE over sequence chunks: the [B, chunk, V] fp32
        # working set replaces the full [B, S, V] materialization
        nc = s // rt.loss_chunk
        lc = logits.reshape(logits.shape[0], nc, rt.loss_chunk, -1)             .transpose(1, 0, 2, 3)
        yc = labels.reshape(labels.shape[0], nc, rt.loss_chunk)             .transpose(1, 0, 2)

        def body(acc, inp):
            lg, yy = inp
            lgf = lg.astype(jnp.float32)
            lse = jax.nn.logsumexp(lgf, axis=-1)
            gold = jnp.take_along_axis(lgf, yy[..., None], axis=-1)[..., 0]
            return acc + jnp.sum(lse - gold), None

        tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (lc, yc))
        return tot / (labels.shape[0] * s)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------


def _slot_cache(spec, rt, kind: dict, batch: int, kv_len: int) -> dict:
    cdt = dt(rt.compute_dtype)
    c: dict = {}
    if kind["mixer"] == "attn":
        if spec.block == "mla":                  # time major: [T, B, ...]
            m = spec.mla
            c["attn"] = {"ckv": jnp.zeros((kv_len, batch, m.kv_lora), cdt),
                         "kr": jnp.zeros((kv_len, batch, m.rope_dim), cdt),
                         "pos": jnp.zeros((), jnp.int32)}
        else:
            nkv, dh = max(1, spec.n_kv_heads), spec.head_dim
            klen = min(kv_len, spec.window) if kind["window"] else kv_len
            c["attn"] = {"k": jnp.zeros((batch, klen, nkv, dh), cdt),
                         "v": jnp.zeros((batch, klen, nkv, dh), cdt),
                         "pos": jnp.zeros((), jnp.int32)}
    elif kind["mixer"] == "mamba":
        ss = spec.ssm
        din = ss.expand * spec.d_model
        c["mamba"] = {"conv": jnp.zeros((batch, 3, din), cdt),
                      "ssm": jnp.zeros((batch, din, ss.d_state), jnp.float32)}
    else:
        nh, dh = spec.n_heads, spec.head_dim
        c["rwkv"] = {"wkv": jnp.zeros((batch, nh, dh, dh), jnp.float32),
                     "shift_tm": jnp.zeros((batch, spec.d_model), cdt),
                     "shift_cm": jnp.zeros((batch, spec.d_model), cdt)}
    if spec.encoder_layers:
        nkv, dh = max(1, spec.n_kv_heads), spec.head_dim
        c["cross"] = {"k": jnp.zeros((batch, spec.enc_seq, nkv, dh), cdt),
                      "v": jnp.zeros((batch, spec.enc_seq, nkv, dh), cdt)}
    return c


def init_cache(spec, rt: RuntimeCfg, batch: int, kv_len: int) -> dict:
    prefix_n, period = layer_pattern(spec)
    n_rep = (spec.n_layers - prefix_n) // period if period else 0
    cache: dict = {
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


def _in_place(kind: dict) -> bool:
    """Whether a slot's layers write their attention cache by position
    (full attention, GQA or MLA), so that ``decode_step`` can carry the
    slot's cache stack through the layer scan.  Sliding-window ring
    caches, mamba and rwkv states are rewritten whole."""
    return kind["mixer"] == "attn" and kind["window"] is None


def decode_step(params: dict, cache: dict, tokens, spec, rt: RuntimeCfg,
                rules: Optional[AxisRules] = None, *,
                routed: bool = False) -> tuple:
    """One decode step: tokens [B, 1] -> (logits [B,1,V], new cache).
    With ``routed`` also the tokens of all B rows routed to each held
    expert of each MoE layer, in layer order: [MoE layers, held] int32
    (None for a model without MoE layers).

    A scanned slot whose layers write their attention cache by position
    carries that cache's stack through the scan, and each layer writes
    its new positions into it; with the cache donated, as ``Engine``
    does, the whole cache is then updated in place.  Other caches go
    through the scan as its inputs and outputs."""
    with jax.named_scope("embed"):
        x = params["embed"].value.astype(dt(rt.compute_dtype))[tokens]
    prefix_n, period = layer_pattern(spec)
    new_cache = {"prefix": [], "slots": []}
    counts = []            # (layer index, [held]) of each MoE layer
    li = 0
    for p, c in zip(params["prefix"], cache["prefix"]):
        kind = _slot_kind(spec, li)
        x, nc, r = _apply_slot(p, x, spec, rt, rules, kind, cache=c)
        new_cache["prefix"].append(nc)
        if r is not None:
            counts.append((li, r))
        li += 1

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
    stacks = [c.get("attn") if _in_place(k) else None
              for c, k in zip(cache["slots"], kinds)]
    rest = [{k: v for k, v in c.items() if st is None or k != "attn"}
            for c, st in zip(cache["slots"], stacks)]

    def group(carry, xs):
        """Layers ``prefix_n + i * period + s`` for each slot ``s``."""
        (h, stacks), (pcs, ccs, i) = carry, xs[:3]
        cross_p = xs[3] if spec.encoder_layers else None
        stacks, ncs, rs = list(stacks), [], []
        for s in range(period):
            pc, cc = pcs[s], ccs[s]
            if experts[s] is not None:
                pc = {**pc, "moe": {**pc["moe"], **experts[s]}}
            if stacks[s] is not None:
                cc = {**cc, "attn": stacks[s]}
            h, nc, r = _apply_slot(
                pc, h, spec, rt, rules, kinds[s], cache=cc, cross_p=cross_p,
                cross_cache=cc.get("cross"),
                moe_layer=None if experts[s] is None else i,
                layer=None if stacks[s] is None else i)
            if stacks[s] is not None:
                nc = dict(nc)
                stacks[s] = nc.pop("attn")
            ncs.append(nc)
            rs.append(r)
        return (h, stacks), (ncs, rs)

    if n_rep:
        scanned = (ps, rest, jnp.arange(n_rep)) + \
            ((params["cross"],) if spec.encoder_layers else ())
        (x, stacks), (ncs, rs) = jax.lax.scan(group, (x, stacks), scanned)
        new_cache["slots"] = [nc if st is None else {"attn": st, **nc}
                              for nc, st in zip(ncs, stacks)]
        for s, r in enumerate(rs):
            if r is not None:
                counts += [(prefix_n + s + i * period, r[i])
                           for i in range(n_rep)]
    else:
        new_cache["slots"] = cache["slots"]

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
