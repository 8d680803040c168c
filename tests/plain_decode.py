"""A plain decode step, and a comparison of ``repro.serve.Engine`` with it.

``plain_decode_step`` runs the layers one after another in Python, each
with its own cache and all at the cache's one position, the way the
model reads on paper: no layer scan, no cache stack written in place, no
donation.  ``engine_matches_plain`` steps an ``Engine`` over a few
requests, its greedy step (``greedy_step``) run around a jitted
``make_serve_step`` that donates its cache and writes each layer's part
of the stack, then feeds the plain step the same token batches from a
fresh cache, and compares the logits of every step and the caches at the
end.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L, lm
from repro.models.common import dt
from repro.serve import Engine, Request, greedy_step, make_serve_step


def plain_decode_step(params, cache, tokens, spec, rt):
    """tokens [B, 1] -> (logits [B, 1, V], new cache), layer by layer."""
    x = params["embed"].value.astype(dt(rt.compute_dtype))[tokens]
    prefix_n, period = lm.layer_pattern(spec)
    pos = cache["pos"]
    new = {"pos": pos + tokens.shape[1], "prefix": [],
           "slots": [[] for _ in range(period)]}
    for layer in range(spec.n_layers):
        if layer < prefix_n:
            p, c = params["prefix"][layer], cache["prefix"][layer]
        else:
            s, r = (layer - prefix_n) % period, (layer - prefix_n) // period
            p = lm._index(params["slots"][s], r)
            c = jax.tree.map(lambda a: a[r], cache["slots"][s])
        cross = (lm._index(params["cross"], layer) if spec.encoder_layers
                 else None)
        x, nc, _ = lm._apply_slot(p, x, spec, rt, None,
                                  lm._slot_kind(spec, layer), cache=c,
                                  pos=pos, cross_p=cross)
        if layer < prefix_n:
            new["prefix"].append(nc)
        else:
            new["slots"][s].append(nc)
    new["slots"] = [jax.tree.map(lambda *a: jnp.stack(a), *reps)
                    for reps in new["slots"]]
    x = L.rms_norm(params["ln_f"], x)
    logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"].value.astype(
        dt(rt.compute_dtype)))
    if spec.final_softcap:
        logits = L._softcap(logits.astype(jnp.float32), spec.final_softcap)
    return logits, new


def engine_matches_plain(spec, rt, params, prompts, max_new: int,
                         kv_len: int, rel: float):
    """Serve ``prompts`` through an Engine, check that it consumed the
    cache it was given, and compare it with the plain step fed the same
    token batches: each step's logits and every cache leaf, within
    ``rel`` of the largest magnitude.  Returns (the engine's cache, the
    plain cache, the number of steps)."""
    eng = Engine(spec, rt, params, batch_slots=len(prompts), kv_len=kv_len)
    first = jax.tree.leaves(eng.cache)
    step = jax.jit(make_serve_step(spec, rt, routed=eng.routed),
                   donate_argnums=(1,))
    fed, seen = [], []

    def recording(params, cache, tokens):
        fed.append(np.asarray(tokens))
        out = step(params, cache, tokens)
        seen.append(np.asarray(out[0]))
        return out
    eng.step_fn = greedy_step(recording)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=np.asarray(p, np.int32),
                           max_new=max_new))
    eng.run(max_steps=64)
    assert all(a.is_deleted() for a in first), "the cache was not donated"

    plain = jax.jit(functools.partial(plain_decode_step, spec=spec, rt=rt))
    cache = lm.init_cache(spec, rt, len(prompts), kv_len)
    for tok, got in zip(fed, seen):
        want, cache = plain(params, cache, jnp.asarray(tok))
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=0, atol=rel * np.abs(want).max())
    assert jax.tree.structure(eng.cache) == jax.tree.structure(cache)
    for (path, a), b in zip(jax.tree.leaves_with_path(eng.cache),
                            jax.tree.leaves(cache)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=rel * max(np.abs(b).max(), 1e-30),
                                   err_msg=jax.tree_util.keystr(path))
    return eng.cache, cache, len(fed)
