"""Plain RWKV-6 (Finch) language model: forward and loss in float32.

Follows arXiv:2404.05892's time mix and channel mix, in the form the
runtime states (departures from the paper, shared with the runtime: RMS
norms for layer norms, static token-shift mixes ``mu_*`` instead of the
data-dependent LoRA lerp, the decay ``w = exp(-exp(x W1 W2))`` with no
learned base, and a per-head RMS norm for the group norm).  The WKV is
the plain per-token recurrence

    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t),   S_t = diag(w_t) S_{t-1} + k_t^T v_t

scanned token by token (rematerialised per block of 64 tokens so that
the backward pass fits), not the runtime's chunked factorisation.
Weights are a dict ``path -> array`` in the benchmark's layout; layers
are stacked on the first dim of every ``slots/0/rwkv/*`` leaf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from numerics import HI, bf16_round, cross_entropy, mm, rms

BLOCK = 64


def _shift(x):
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _wkv(r, k, v, w, u):
    """r/k/v/w [B,S,N,D] float32 -> o [B,S,N,D]."""
    b, s, n, d = r.shape
    blk = BLOCK if s % BLOCK == 0 else s

    def tok(S, xs):
        rt, kt, vt, wt = xs
        kv = jnp.einsum("bnd,bne->bnde", kt, vt, precision=HI)
        o = jnp.einsum("bnd,bnde->bne", rt, S + u[None, :, :, None] * kv,
                       precision=HI)
        return wt[..., None] * S + kv, o

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(tok, S, xs, unroll=8)

    xs = [t.reshape(b, s // blk, blk, n, d).transpose(1, 2, 0, 3, 4)
          for t in (r, k, v, w)]
    S0 = jnp.zeros((b, n, d, d), jnp.float32)
    _, o = jax.lax.scan(block, S0, tuple(xs))          # [nb, blk, B, N, D]
    return o.transpose(2, 0, 1, 3, 4).reshape(b, s, n, d)


def layer(p: dict, x, mode: str):
    h = rms(x, p["ln"])
    sh = _shift(h)

    def mix(nm):
        return h + (sh - h) * p[f"mu_{nm}"].astype(jnp.float32)

    r = mm("bsh,hnd->bsnd", mix("r"), p["w_r"], mode)
    k = mm("bsh,hnd->bsnd", mix("k"), p["w_k"], mode)
    v = mm("bsh,hnd->bsnd", mix("v"), p["w_v"], mode)
    g = mm("bsh,hnd->bsnd", mix("g"), p["w_g"], mode)
    dec = mm("bsr,rnd->bsnd", mm("bsh,hr->bsr", mix("w"), p["w_dec1"], mode),
             p["w_dec2"], mode)
    w = jnp.exp(-jnp.exp(dec))
    o = _wkv(r, k, v, w, p["u"].astype(jnp.float32))
    if mode == "bf16":               # where the runtime rounds it
        o = bf16_round(o)
    o = rms(o, p["gn"]) * jax.nn.silu(g)
    x = x + mm("bsnd,ndh->bsh", o, p["w_tmo"], mode)
    hc = rms(x, p["ln_cm"])
    shc = _shift(hc)
    mk = hc + (shc - hc) * p["mu_ck"].astype(jnp.float32)
    mr = hc + (shc - hc) * p["mu_cr"].astype(jnp.float32)
    kk = jnp.square(jax.nn.relu(mm("bsh,hf->bsf", mk, p["w_ck"], mode)))
    rr = jax.nn.sigmoid(mm("bsh,hg->bsg", mr, p["w_cr"], mode))
    return x + mm("bsf,fh->bsh", kk, p["w_cv"], mode) * rr


def loss(params: dict, tokens, labels, mode: str = "f32"):
    x = params["embed"].astype(jnp.float32)[tokens]
    pre = "slots/0/rwkv/"
    stack = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
    n = next(iter(stack.values())).shape[0]
    for i in range(n):
        x = jax.checkpoint(layer, static_argnums=2)(
            {k: v[i] for k, v in stack.items()}, x, mode)
    x = rms(x, params["ln_f"])
    return cross_entropy(mm("bsh,hv->bsv", x, params["lm_head"], mode),
                         labels)
