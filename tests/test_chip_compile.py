"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

The TPU compiler refuses what interpret mode accepts: block shapes off
the (8, 128) tiling, more VMEM than a kernel may use, a program that
does not fit the chip's HBM.  Each test lowers one kernel or step of the
main path at real widths for one chip of a described ``v5e:2x2`` and
compiles it.  A compile that passes is not a chip run: nothing executes
and no time is measured.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a module that touched it at
collection would break the other test workers.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import cut_depth, get
from repro.kernels.cost_reduce import cost_reduce_bet
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rwkv6_scan import wkv6_bhsd
from repro.launch.train import runtime_cfg
from repro.models import RuntimeCfg, init_params, lm
from repro.serve.engine import make_serve_step
from repro.train import OptCfg, init_opt_state, make_train_step

HBM = 16 * 2**30                         # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _bytes(tree) -> int:
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def _entry_copies(compiled) -> list:
    """The shapes (dims in order) of the copies in the entry computation,
    outside every loop: relayouts of whole arguments land there."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    return [tuple(int(d) for d in m.split(","))
            for line in entry.splitlines()
            if re.search(r" copy(-start)?\(", line)
            for m in re.findall(r"\[([\d,]+)\]", line.split("=", 1)[1]
                                .split(" copy")[0])]


def _serve_step(spec, rt, **kw):
    """The serve step jitted as ``Engine`` jits it: the cache donated."""
    return jax.jit(make_serve_step(spec, rt, **kw), donate_argnums=(1,))


def test_cost_reduce_compiles(one_chip):
    # a sweep-sized busy-group contraction: [B, K] x [G, K]
    x = jax.ShapeDtypeStruct((2048, 512), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, 512), jnp.float32, sharding=one_chip)
    compiled = jax.jit(cost_reduce_bet).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_attention_compiles(one_chip, window):
    q = jax.ShapeDtypeStruct((1, 8, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention_bhsd(
        q, k, v, causal=True, window=window))
    compiled = fn.lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv6_compiles_at_rwkv6_7b_widths(one_chip):
    spec = get("rwkv6-7b").spec
    h, d = spec.n_heads, spec.head_dim                  # 64 x 64
    bhsd = jax.ShapeDtypeStruct((1, h, 4096, d), jnp.float32,
                                sharding=one_chip)
    u = jax.ShapeDtypeStruct((h, d), jnp.float32, sharding=one_chip)
    s0 = jax.ShapeDtypeStruct((1, h, d, d), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda r, k, v, w, u, s0: wkv6_bhsd(r, k, v, w, u, s0,
                                                     chunk=32))
    compiled = fn.lower(bhsd, bhsd, bhsd, bhsd, u, s0).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_34b_serve_step_fits_one_chip(one_chip):
    """8 of 88 layers at published widths, 8 slots x 4096 kv positions."""
    spec = cut_depth(get("granite-34b").spec, 8)
    rt = RuntimeCfg(attention_impl="naive")
    params = _on(one_chip, jax.eval_shape(
        lambda: init_params(spec, rt, jax.random.PRNGKey(0))))
    cache = _on(one_chip, jax.eval_shape(
        lambda: lm.init_cache(spec, rt, 8, 4096)))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    compiled = _serve_step(spec, rt).lower(params, cache, tok).compile()
    # the k/v stack is updated in place: all of the cache is aliased
    assert compiled.memory_analysis().alias_size_in_bytes >= _bytes(cache)
    assert _hbm_bytes(compiled) <= HBM


def test_deepseek_v2_serve_step_fits_one_chip(one_chip):
    """The benchmark's cut: the dense layer and 4 MoE layers at published
    widths, each holding 20 of the 160 experts; 128 slots x 1536 latent
    positions.  Attention reads the latent cache and forms no per-head
    key or value of it; the held experts go through the ragged dot.  The
    step takes the donated cache and writes each layer's new position
    into it in place: all of it is aliased, and no copy of the stacked
    latent cache is made around the layer scan."""
    spec = cut_depth(get("deepseek-v2-236b").spec, 5)
    spec = dataclasses.replace(spec, moe=dataclasses.replace(spec.moe,
                                                             n_held=20))
    rt = RuntimeCfg(attention_impl="naive")
    params = _on(one_chip, jax.eval_shape(
        lambda: init_params(spec, rt, jax.random.PRNGKey(0))))
    cache = _on(one_chip, jax.eval_shape(
        lambda: lm.init_cache(spec, rt, 128, 1536)))
    tok = jax.ShapeDtypeStruct((128, 1), jnp.int32, sharding=one_chip)
    compiled = _serve_step(spec, rt, routed=True).lower(
        params, cache, tok).compile()
    text = compiled.as_text().replace(" ", "")
    # per head, in either order of the rows and the (time-major) positions
    assert not [d for d in (128, 192, 256)
                for shape in (f"[128,1536,128,{d}]", f"[1536,128,128,{d}]")
                if shape in text]
    assert "ragged-dot" in text
    assert compiled.memory_analysis().alias_size_in_bytes >= _bytes(cache)
    stacked = sorted(cache["slots"][0]["attn"]["ckv"].shape)
    assert not [c for c in _entry_copies(compiled) if sorted(c) == stacked]
    assert _hbm_bytes(compiled) <= HBM


def test_rwkv6_7b_train_step_fits_one_chip(one_chip):
    """1 of 32 layers at published widths, batch 1 x seq 4096, with the
    params and optimizer state donated as the train launcher does."""
    spec = cut_depth(get("rwkv6-7b").spec, 1)
    rt = runtime_cfg(4096)
    params = _on(one_chip, jax.eval_shape(
        lambda: init_params(spec, rt, jax.random.PRNGKey(0))))
    opt = _on(one_chip, jax.eval_shape(init_opt_state, params))
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    step = jax.jit(make_train_step(spec, rt, OptCfg()),
                   donate_argnums=(0, 1))
    compiled = step.lower(params, opt, {"tokens": tokens,
                                        "labels": tokens}).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= mem.argument_size_in_bytes // 2
    assert _hbm_bytes(compiled) <= HBM
