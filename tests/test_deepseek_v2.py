"""DeepSeek-V2 on the serve path against its plain reference, at a small
size on seeded random weights (CPU).

- Prefill by decode through ``repro.serve.Engine`` gives the logits of
  the reference's full forward (``perfbench/refs/deepseek_v2.py``).
- The eight group shares' routed parts, with the shared experts counted
  once, add up to the uncut reference layer.
- Group-limited routing on a hand example; yarn RoPE's ramp and scale.
- Planted faults (gates renormalized; no group limit) fail the first
  comparison, and the decode step forms no per-head copy of the cache.
- The engine's donated decode, which writes each layer's new position
  into the time-major latent cache in place, gives the logits and the
  cache of a plain per-layer decode (``plain_decode.py``).
"""
import dataclasses
import importlib
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "perfbench"
sys.path[:0] = [str(BENCH), str(BENCH / "refs")]

from repro.configs import get  # noqa: E402
from repro.core import MLASpec, ModelSpec, MoESpec  # noqa: E402
from repro.models import RuntimeCfg, init_params, layers as L, lm  # noqa: E402
from repro.models.common import Param  # noqa: E402
from repro.serve import (Engine, Request, greedy_step,  # noqa: E402
                         make_serve_step)

from plain_decode import engine_matches_plain  # noqa: E402

ref = importlib.import_module("deepseek_v2")
weights = importlib.import_module("weights")

# the published routing and yarn at small widths: 32 experts in 8 groups
# of 4, this share holds group 0 as the reference does
SPEC = ModelSpec(
    name="dsv2-tiny", n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=96, vocab=256, d_head=16, block="mla",
    mla=MLASpec(kv_lora=40, q_lora=48, rope_dim=8, nope_dim=16, v_dim=24,
                rope_factor=40.0, mscale_all_dim=0.707),
    moe=MoESpec(n_experts=32, top_k=6, n_shared=2, d_expert=32,
                first_dense=True, n_group=8, topk_group=3, norm_topk=False,
                routed_scale=16.0, n_held=4, held_group=0))
F32 = RuntimeCfg(attention_impl="naive", param_dtype="float32",
                 compute_dtype="float32")
STD = {"w_egate": 1 / 8, "w_eup": 1 / 8, "w_edown": 1 / math.sqrt(32)}
SEED = 2**31 + 77
# Both sides compute in float32; they differ only in the order of sums
# (latent against expanded attention, ragged against dense experts), a
# few ulps of the logits.  Rounding any product to bfloat16 (2^-9) moves
# them by ~1e-2 of their scale, a changed route by more.
TOL = 1e-4


def _weights(spec, seed=SEED):
    abstract = jax.eval_shape(lambda k: init_params(spec, F32, k),
                              jax.random.PRNGKey(0))
    params, lay = weights.make_tree(abstract, seed, STD)
    flat = dict(zip([p for p, _, _ in lay],
                    weights.make_flat(lay, seed, STD)))
    return params, flat


def _served_logits(spec, params, prompts, max_new):
    """Engine.run over one wave; each request's logits at every position
    it fed (prompt then outputs), from the step's own output."""
    eng = Engine(spec, F32, params, batch_slots=len(prompts), kv_len=64)
    step = jax.jit(make_serve_step(spec, F32, routed=True),
                   donate_argnums=(1,))
    seen = []

    def recording(*a):
        out = step(*a)
        seen.append(np.asarray(out[0][:, 0]))
        return out
    eng.step_fn = greedy_step(recording)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=64)
    got = np.stack(seen, 1)                           # [B, steps, V]
    seqs = [np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
            for r in reqs]
    return got, seqs


def _decode_vs_reference(spec) -> float:
    """Widest gap between the engine's logits and the reference's over
    every fed position, over the reference's largest logit."""
    params, flat = _weights(spec)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, spec.vocab, size=n).astype(np.int32)
               for n in (5, 9, 3, 12)]
    got, seqs = _served_logits(spec, params, prompts, max_new=6)
    L_ = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), L_), np.int32)
    for j, s in enumerate(seqs):
        toks[j, :len(s)] = s
    want = ref.forward(flat, jnp.asarray(toks), "f32")
    err = max(float(np.abs(got[j, :len(s)] - want[j, :len(s)]).max())
              for j, s in enumerate(seqs))
    return err / float(np.abs(want).max())


FAULTS = {
    "sound": {},
    "gates_renormalized": {"norm_topk": True},
    "no_group_limit": {"topk_group": 0},
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_prefill_by_decode_matches_reference(fault):
    spec = dataclasses.replace(
        SPEC, moe=dataclasses.replace(SPEC.moe, **FAULTS[fault]))
    err = _decode_vs_reference(spec)
    if fault == "sound":
        assert err <= TOL, err
    else:
        assert err > 100 * TOL, err


def test_group_shares_add_up_to_the_uncut_layer():
    """Each share's moe_ffn less x gives its routed part plus the shared
    experts; the eight parts, the shared experts once, equal the
    reference layer over all 32 experts."""
    params, flat = _weights(SPEC)
    i = 0                                            # first MoE layer
    full = {k: flat[f"slots/0/moe/{k}"][i] for k in (
        "w_router", "shared/w_gate", "shared/w_up", "shared/w_down", "ln")}
    rng = np.random.default_rng(1)
    experts = {k: jnp.asarray(rng.normal(0, STD[k], s), jnp.float32)
               for k, s in (("w_egate", (32, 64, 32)), ("w_eup", (32, 64, 32)),
                            ("w_edown", (32, 32, 64)))}
    x = jnp.asarray(rng.normal(0, 1, (3, 7, 64)), jnp.float32)
    p0 = jax.tree.map(lambda q: Param(q.value[i], q.axes[1:]),
                      params["slots"][0]["moe"],
                      is_leaf=lambda q: isinstance(q, Param))
    total = 0.0
    for g in range(8):
        spec = dataclasses.replace(
            SPEC, moe=dataclasses.replace(SPEC.moe, held_group=g))
        pg = dict(p0)
        for k, w in experts.items():
            pg[k] = Param(w[4 * g:4 * g + 4], p0[k].axes)
        total = total + (L.moe_ffn(pg, x, spec, F32, None) - x)
    h = ref.rms(x, full["ln"]).reshape(-1, 64)
    shared = ref.swiglu(h, full["shared/w_gate"], full["shared/w_up"],
                        full["shared/w_down"], "f32")
    gates, idx = ref.route(h, full["w_router"])
    uncut = ref.routed_part(h, gates, idx, experts["w_egate"],
                            experts["w_eup"], experts["w_edown"], 0, "f32")
    got = np.asarray(total).reshape(-1, 64) - 7 * np.asarray(shared)
    want = np.asarray(uncut + shared)
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


def test_group_limited_routing_hand_example():
    """8 groups of 2; group bests 5 (g0), 4.95 (g3), 4.9 (g1), 4.8 (g2):
    groups 0, 3 and 1 stay, so expert 4 (4.8, group 2) loses its place
    in the top 4 to expert 1 (1.0, group 0)."""
    logits = np.full(16, -3.0, np.float32)
    logits[[0, 1, 6, 2, 4, 5]] = [5.0, 1.0, 4.95, 4.9, 4.8, 4.79]
    mo = MoESpec(n_experts=16, top_k=4, n_group=8, topk_group=3,
                 norm_topk=False, routed_scale=16.0)
    gates, idx = L.route(jnp.asarray(logits)[None], jnp.eye(16), mo)
    probs = np.exp(logits) / np.exp(logits).sum()
    assert np.asarray(idx[0]).tolist() == [0, 6, 2, 1]
    np.testing.assert_allclose(np.asarray(gates[0]), 16 * probs[[0, 6, 2, 1]],
                               rtol=1e-6)
    # without the group limit expert 4 is in; renormalized gates sum to 1
    _, idx = L.route(jnp.asarray(logits)[None], jnp.eye(16),
                     dataclasses.replace(mo, topk_group=0))
    assert np.asarray(idx[0]).tolist() == [0, 6, 2, 4]
    g, _ = L.route(jnp.asarray(logits)[None], jnp.eye(16),
                   dataclasses.replace(mo, norm_topk=True))
    assert float(g.sum()) == pytest.approx(1.0, rel=1e-6)


def test_yarn_ramp_and_softmax_scale():
    m = get("deepseek-v2-236b").spec.mla
    inv_freq = L.mla_rope(m)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = (base - inv_freq) / (base - base / 40)    # 0: original, 1: /40
    assert np.all(np.abs(ramp[:11]) < 1e-6)
    assert np.all(np.abs(ramp[23:] - 1) < 1e-6)
    np.testing.assert_allclose(ramp[10:24], np.arange(14) / 13, atol=1e-5)
    assert ref.yarn_mscale(40, 0.707) / ref.yarn_mscale(40, 0.707) == 1.0
    assert L.mla_softmax_scale(m) == pytest.approx(0.114721, abs=1e-6)
    np.testing.assert_allclose(inv_freq, ref.yarn_inv_freq(64), rtol=1e-6)
    assert ref.softmax_scale(192) == pytest.approx(L.mla_softmax_scale(m))


def test_decode_forms_no_per_head_cache():
    """The decode step's program holds nothing of [B, T, heads, d] for
    the cache's T positions: attention runs in the latent space."""
    B, T = 3, 56
    params = jax.eval_shape(lambda k: init_params(SPEC, F32, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(SPEC, F32, B, T))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, c, t: lm.decode_step(
        p, c, t, SPEC, F32, routed=True))(params, cache, tok)
    shapes = set()

    def walk(x):
        if hasattr(x, "eqns"):
            for eqn in x.eqns:
                shapes.update(tuple(getattr(v.aval, "shape", ()))
                              for v in eqn.outvars)
                for v in eqn.params.values():
                    walk(v)
        elif hasattr(x, "jaxpr"):
            walk(x.jaxpr)
        elif isinstance(x, (tuple, list)):
            for v in x:
                walk(v)
    walk(jaxpr.jaxpr)
    m, N = SPEC.mla, SPEC.n_heads
    per_head = {(B, T, N, d) for d in (m.nope_dim, m.v_dim,
                                       m.nope_dim + m.rope_dim)}
    assert not shapes & per_head, shapes & per_head
    assert (B, 1, T, N) in shapes                    # latent scores


def test_engine_decode_matches_plain_decode():
    """Through the Engine the latent cache is donated and each layer
    writes its new position into it in place, the scanned layers into
    the stack they carry.  Each step's logits and the final cache equal
    those of a plain decode that runs the layers one by one, and read
    by position through the [B, T, R] view of the time-major cache,
    every row holds exactly the positions the steps wrote; the cache's
    one position reads the number of steps."""
    params, _ = _weights(SPEC)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, SPEC.vocab, size=n) for n in (4, 7, 2)]
    got, _, steps = engine_matches_plain(SPEC, F32, params, prompts,
                                         max_new=5, kv_len=24, rel=1e-5)
    assert steps == 11
    m = SPEC.mla
    for c, layers in ((got["prefix"][0]["attn"], 1),
                      (got["slots"][0]["attn"], SPEC.n_layers - 1)):
        ckv, kr = (np.asarray(c[k], np.float32).reshape(
            layers, 24, 3, -1).swapaxes(1, 2) for k in ("ckv", "kr"))
        assert ckv.shape[-1] == m.kv_lora and kr.shape[-1] == m.rope_dim
        for a in (ckv, kr):                       # [layers, B, T, R]
            assert (np.abs(a[:, :, :steps]).max(-1) > 0).all()
            assert not a[:, :, steps:].any()
    assert int(got["pos"]) == steps
