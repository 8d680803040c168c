"""Per-architecture smoke tests: reduced same-family configs run one
forward/train step on CPU; shapes + finiteness asserted (assignment
requirement (f))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get
from repro.models import (RuntimeCfg, decode_step, init_cache, init_params,
                          lm, loss_fn)
from repro.serve import Engine, Request

from plain_decode import engine_matches_plain

RT = RuntimeCfg(attention_impl="chunked", attn_chunk=64)
RT_F32 = RuntimeCfg(attention_impl="chunked", attn_chunk=64,
                    param_dtype="float32", compute_dtype="float32")


def _batch(spec, B=2, S=32):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, spec.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if spec.encoder_layers:
        batch["frames"] = jnp.ones((B, spec.enc_seq, spec.d_model),
                                   jnp.bfloat16)
    if spec.vision_seq:
        batch["vision"] = jnp.ones((B, spec.vision_seq, spec.d_model),
                                   jnp.bfloat16)
    return batch


@pytest.mark.parametrize("name", ARCHS)
def test_smoke_train_step(name):
    arch = get(name)
    spec = arch.smoke
    params = init_params(spec, RT, jax.random.PRNGKey(0))
    batch = _batch(spec)

    def step(p, b):
        l, g = jax.value_and_grad(lambda pp: loss_fn(pp, b, spec, RT))(p)
        return l, g

    loss, grads = jax.jit(step)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{name}: loss={loss}"
    leaves = jax.tree.leaves(grads)
    assert leaves and all(bool(jnp.isfinite(g).all()) for g in leaves), \
        f"{name}: non-finite grads"


@pytest.mark.parametrize("name", ARCHS)
def test_smoke_decode_step(name):
    arch = get(name)
    spec = arch.smoke
    params = init_params(spec, RT, jax.random.PRNGKey(0))
    cache = init_cache(spec, RT, 2, 64)
    tok = jnp.zeros((2, 1), jnp.int32)
    logits, cache2 = jax.jit(
        lambda p, c, t: decode_step(p, c, t, spec, RT))(params, cache, tok)
    assert logits.shape == (2, 1, spec.vocab)
    assert bool(jnp.isfinite(logits).all()), name
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("name", ARCHS)
def test_engine_decode_matches_plain_decode(name):
    """Decode steps through the Engine, which donates its cache and
    carries each scanned slot's cache stack through the layer scan (full
    attention writes its new positions into the stack, ring windows,
    mamba and rwkv states rewrite their layer's entry, cross-attention
    reads its own), give each step's logits and the final caches of a
    plain decode that runs the layers one by one on caches of their
    own."""
    spec = get(name).smoke
    params = init_params(spec, RT_F32, jax.random.PRNGKey(0))
    _, _, steps = engine_matches_plain(
        spec, RT_F32, params, [[3, 1, 4], [1, 5, 9, 2, 6]], max_new=3,
        kv_len=64, rel=1e-5)
    assert steps == 7


@pytest.mark.parametrize("name", ARCHS)
def test_cache_holds_one_position(name):
    """The decode cache holds one position, at its top, for every layer
    and row; after k Engine steps it reads k."""
    spec = get(name).smoke
    cache = init_cache(spec, RT, 2, 64)
    named = [p for p, _ in jax.tree.leaves_with_path(cache)
             if any(getattr(k, "key", None) == "pos" for k in p)]
    assert named == [(jax.tree_util.DictKey("pos"),)]
    assert cache["pos"].shape == () and cache["pos"].dtype == jnp.int32
    eng = Engine(spec, RT, init_params(spec, RT, jax.random.PRNGKey(0)),
                 batch_slots=2, kv_len=64)
    for i, n in enumerate((2, 4)):
        eng.submit(Request(rid=i, prompt=np.arange(1, n + 1, dtype=np.int32),
                           max_new=2))
    eng.run(max_steps=64)
    assert eng.n_steps == 5
    assert int(eng.cache["pos"]) == eng.n_steps


def test_decode_matches_forward_on_a_periodic_stack():
    """gemma2 alternates local and global layers (a period of 2): prefill
    by decode, one token a step through the cache, gives the full
    forward's logits, so the decode runs the layers in their order."""
    spec = get("gemma2-27b").smoke
    assert lm.layer_pattern(spec)[1] == 2
    params = init_params(spec, RT_F32, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                spec.vocab)
    want = lm.forward(params, tokens, spec, RT_F32)
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, spec, RT_F32))
    cache, got = init_cache(spec, RT_F32, 2, 32), []
    for t in range(tokens.shape[1]):
        logits, cache = step(params, cache, tokens[:, t:t + 1])
        got.append(logits)
    got = jnp.concatenate(got, axis=1)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())


def test_full_configs_match_assignment():
    """The full-size SPEC fields must equal the assigned table exactly."""
    expect = {
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "gemma2-27b": (46, 4608, 32, 16, 36864, 256000),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
        "deepseek-moe-16b": (28, 2048, 16, 16, None, 102400),
        "deepseek-v2-236b": (60, 5120, 128, 128, None, 102400),
        "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
    }
    for name, (L, H, NH, NKV, DFF, V) in expect.items():
        s = get(name).spec
        assert s.n_layers == L and s.d_model == H, name
        assert s.n_heads == NH and s.n_kv_heads == NKV, name
        assert s.vocab == V, name
        if DFF is not None:
            assert s.d_ff == DFF, name
    # MoE widths per assignment
    assert get("deepseek-moe-16b").spec.moe.d_expert == 1408
    assert get("deepseek-moe-16b").spec.moe.n_experts == 64
    assert get("deepseek-moe-16b").spec.moe.top_k == 6
    assert get("deepseek-v2-236b").spec.moe.d_expert == 1536
    assert get("deepseek-v2-236b").spec.moe.n_experts == 160
    assert get("deepseek-v2-236b").spec.mla.kv_lora == 512
    assert get("jamba-v0.1-52b").spec.moe.n_experts == 16
    assert get("jamba-v0.1-52b").spec.moe.top_k == 2


def test_long_500k_applicability():
    runs = {a for a in ARCHS if "long_500k" not in get(a).skip}
    assert runs == {"rwkv6-7b", "jamba-v0.1-52b", "gemma2-27b"}
