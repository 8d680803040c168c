"""Per-kernel allclose sweeps vs. the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rwkv6_scan import wkv6_bhsd


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("b,h,sq,sk,d", [
    (1, 1, 128, 128, 64),
    (2, 3, 256, 256, 64),
    (1, 2, 64, 384, 128),       # cross-ish: kv longer than q
    (2, 2, 96, 160, 80),        # non-128-multiple dims (padding path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_vs_ref(b, h, sq, sk, d, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (b, h, sq, d), dtype)
    k = _rand(ks[1], (b, h, sk, d), dtype)
    v = _rand(ks[2], (b, h, sk, d), dtype)
    out = flash_attention_bhsd(q, k, v, causal=causal, interpret=True,
                               block_q=64, block_k=128)
    want = ref.ref_attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window,softcap", [(32, None), (None, 20.0),
                                            (64, 30.0)])
def test_flash_window_softcap(window, softcap):
    b, h, s, d = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (_rand(ks[i], (b, h, s, d), jnp.float32) for i in range(3))
    out = flash_attention_bhsd(q, k, v, causal=True, window=window,
                               softcap=softcap, interpret=True)
    want = ref.ref_attention(q, k, v, causal=True, window=window,
                             softcap=softcap)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


def test_flash_q_offset_decode():
    """Single-token decode against a longer KV context."""
    b, h, sk, d = 2, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (b, h, 1, d), jnp.float32)
    k = _rand(ks[1], (b, h, sk, d), jnp.float32)
    v = _rand(ks[2], (b, h, sk, d), jnp.float32)
    out = flash_attention_bhsd(q, k, v, causal=True, q_offset=sk - 1,
                               interpret=True)
    want = ref.ref_attention(q, k, v, causal=True, q_offset=sk - 1)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,h,s,d,chunk", [
    (1, 1, 64, 32, 32),
    (2, 2, 128, 64, 32),
    (1, 3, 96, 48, 32),          # d needs padding to 128
])
def test_wkv6_vs_ref(b, h, s, d, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    r = _rand(ks[0], (b, h, s, d), jnp.float32)
    k = _rand(ks[1], (b, h, s, d), jnp.float32)
    v = _rand(ks[2], (b, h, s, d), jnp.float32)
    dec = jax.random.uniform(ks[3], (b, h, s, d), minval=-2.0, maxval=0.5)
    w = jnp.exp(-jnp.exp(dec))
    u = _rand(ks[4], (h, d), jnp.float32) * 0.5
    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    out, st = wkv6_bhsd(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    want_o, want_s = ref.ref_wkv(r, k, v, w, u, s0)
    np.testing.assert_allclose(out, want_o, atol=1e-3, rtol=1e-2)
    np.testing.assert_allclose(st, want_s, atol=1e-3, rtol=1e-2)


def test_wkv6_state_carry():
    """Two half-length calls with carried state == one full call."""
    b, h, s, d = 1, 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    r, k, v = (_rand(ks[i], (b, h, s, d), jnp.float32) for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.uniform(ks[3], (b, h, s, d),
                                            minval=-2.0, maxval=0.0)))
    u = _rand(ks[4], (h, d), jnp.float32) * 0.5
    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    full, st_full = wkv6_bhsd(r, k, v, w, u, s0, chunk=32, interpret=True)
    h1, st1 = wkv6_bhsd(r[:, :, :32], k[:, :, :32], v[:, :, :32],
                        w[:, :, :32], u, s0, chunk=32, interpret=True)
    h2, st2 = wkv6_bhsd(r[:, :, 32:], k[:, :, 32:], v[:, :, 32:],
                        w[:, :, 32:], u, st1, chunk=32, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([h1, h2], axis=2), full,
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(st2, st_full, atol=1e-4, rtol=1e-3)


@given(st.integers(1, 3), st.integers(1, 3),
       st.sampled_from([32, 64]), st.sampled_from([16, 32]),
       st.integers(0, 2**31 - 1))
@settings(max_examples=12, deadline=None)
def test_wkv6_property(b, h, s, d, seed):
    """Hypothesis: kernel == sequential oracle across random small shapes."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    r, k, v = (_rand(ks[i], (b, h, s, d), jnp.float32) for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.uniform(ks[3], (b, h, s, d),
                                            minval=-1.5, maxval=0.5)))
    u = _rand(ks[4], (h, d), jnp.float32) * 0.3
    s0 = _rand(ks[4], (b, h, d, d), jnp.float32) * 0.1
    out, st_ = wkv6_bhsd(r, k, v, w, u, s0, chunk=min(32, s), interpret=True)
    want_o, want_s = ref.ref_wkv(r, k, v, w, u, s0)
    np.testing.assert_allclose(out, want_o, atol=2e-3, rtol=2e-2)
    np.testing.assert_allclose(st_, want_s, atol=2e-3, rtol=2e-2)


def test_model_layout_wrappers():
    """ops.flash_attention / ops.wkv6 adapt model layouts correctly."""
    B, S, N, G, D = 2, 64, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _rand(ks[0], (B, S, N, G, D), jnp.float32)
    k = _rand(ks[1], (B, S, N, D), jnp.float32)
    v = _rand(ks[2], (B, S, N, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    qh = q.transpose(0, 2, 3, 1, 4).reshape(B, N * G, S, D)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    want = ref.ref_attention(qh, kh, vh, causal=True) \
        .reshape(B, N, G, S, D).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_ssm_chunked_vs_ref():
    from repro.models.layers import _ssm_scan
    b, s, d_, p_ = 2, 128, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    dA = jnp.exp(-jax.random.uniform(ks[0], (b, s, d_, p_), minval=0.0,
                                     maxval=2.0))
    dBx = jax.random.normal(ks[1], (b, s, d_, p_))
    h0 = jnp.zeros((b, d_, p_))
    hs, hl = _ssm_scan(dA, dBx, h0, chunk=32)
    want_hs, want_hl = ref.ref_ssm(dA, dBx, h0)
    np.testing.assert_allclose(hs, want_hs, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(hl, want_hl, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("b,e,t", [
    (1, 1, 1),
    (4, 7, 33),             # all dims below one tile (padding path)
    (128, 128, 128),        # exactly one tile
    (130, 257, 140),        # multi-tile with ragged remainders
])
def test_cost_reduce_vs_ref(b, e, t):
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(ks[0], (b, t), jnp.float32)
    w = jax.random.normal(ks[1], (e, t), jnp.float32)
    out = ops.cost_reduce(x, w, interpret=True)
    want = x @ w.T
    assert out.shape == (b, e)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)


def test_cost_reduce_auto_path_f64():
    """Off-TPU the auto path is the jnp contraction in the input dtype —
    float64 under x64, double-precision-close to the numpy product
    (1e-14 would fail by ~7 digits if the reduction ran in float32)."""
    rng = np.random.default_rng(11)
    with jax.enable_x64(True):
        x = jnp.asarray(rng.standard_normal((5, 37)))
        w = jnp.asarray(rng.standard_normal((9, 37)))
        assert x.dtype == jnp.float64
        out = ops.cost_reduce(x, w)
    assert out.dtype == jnp.float64
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x) @ np.asarray(w).T,
                               rtol=1e-14, atol=1e-14)


def test_cost_reduce_counts_semantics():
    """Integer selection rows act as exact gather-sums (the batched
    backend's byte-access reductions): 0/1/k weights stay exact."""
    x = jnp.arange(1, 13, dtype=jnp.float32).reshape(2, 6)
    w = jnp.asarray([[1, 0, 1, 0, 0, 0],
                     [0, 2, 0, 0, 0, 3]], jnp.float32)
    out = ops.cost_reduce(x, w, interpret=True)
    want = np.asarray([[1 + 3, 2 * 2 + 3 * 6],
                       [7 + 9, 2 * 8 + 3 * 12]], np.float32)
    np.testing.assert_array_equal(np.asarray(out), want)
