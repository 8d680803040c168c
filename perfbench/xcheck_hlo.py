#!/usr/bin/env python3
"""Cross-check of ``flops.py`` against the program's compiled HLO, as
``repro.launch.hlo_analysis`` counts it (dots at 2·M·N·K, loop trip
counts applied).  Compiles on the CPU (shapes only, nothing runs):

    JAX_PLATFORMS=cpu python3 perfbench/xcheck_hlo.py

- granite-34b decode step, 8 layers, 64 rows, 8192-position cache: the
  program attends over the whole cache, so it is compared with
  ``flops.decode_step`` at 8192 filled positions;
- rwkv6-7b train step, 1 layer, seq 4096: compared with 6N per token;
  the HLO also holds the recomputed forward (full remat), the WKV chunk
  products and the optimizer, which 6N leaves out by definition.
"""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def spec_of(name: str, layers: int):
    from repro.core import ModelSpec
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return cfg["spec"], ModelSpec(**{**cfg["spec"], "n_layers": layers})


def hlo_flops(lowered) -> float:
    from repro.launch.hlo_analysis import analyze_hlo
    return analyze_hlo(lowered.compile().as_text())["flops"]


def decode() -> dict:
    import jax
    import jax.numpy as jnp

    import flops
    from repro.models import RuntimeCfg, init_params, lm
    from repro.serve.engine import make_serve_step
    sd, spec = spec_of("granite-34b", 8)
    rt = RuntimeCfg(attention_impl="naive")
    params = jax.eval_shape(lambda k: init_params(spec, rt, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(spec, rt, 64, 8192))
    toks = jax.ShapeDtypeStruct((64, 1), jnp.int32)
    got = hlo_flops(jax.jit(make_serve_step(spec, rt)).lower(
        params, cache, toks))
    want = flops.decode_step(sd, 8, 64, 8192)["flops"]
    return {"hlo_flops": got, "flops_py": want, "ratio": got / want}


def train() -> dict:
    import jax
    import jax.numpy as jnp

    import flops
    from repro.launch.train import runtime_cfg
    from repro.models import init_params
    from repro.train import OptCfg, init_opt_state, make_train_step
    sd, spec = spec_of("rwkv6-7b", 1)
    rt = runtime_cfg(4096)
    params = jax.eval_shape(lambda k: init_params(spec, rt, k),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(init_opt_state, params)
    batch = {k: jax.ShapeDtypeStruct((1, 4096), jnp.int32)
             for k in ("tokens", "labels")}
    got = hlo_flops(jax.jit(make_train_step(spec, rt, OptCfg())).lower(
        params, opt, batch))
    want = flops.train_flops_per_token(sd, 1) * 4096
    return {"hlo_flops": got, "six_n_flops": want, "ratio": got / want}


if __name__ == "__main__":
    print(json.dumps({"granite-34b decode": decode(),
                      "rwkv6-7b train": train()}, indent=1))
