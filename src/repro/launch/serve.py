"""Production serving driver (batched continuous decoding).

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --smoke
    PYTHONPATH=src python -m repro.launch.serve --arch granite-34b \\
        --layers 8 --slots 8 --kv-len 4096 --requests 8 --max-new 16

``--layers N`` keeps the published widths and cuts the depth to N
layers (whole periods of the layer pattern only).  Exits non-zero when
fewer requests finish than were submitted.
"""
import argparse

import jax
import numpy as np

from repro.configs import cut_depth, get as get_arch
from repro.launch.compile_cache import use_compile_cache
from repro.launch.preflight import announce, preflight
from repro.models import RuntimeCfg, init_params
from repro.serve import Engine, Request


def main(argv=None):
    """Serves the requests; returns ``(engine, finished requests)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--kv-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args(argv)
    use_compile_cache()

    arch = get_arch(args.arch)
    spec = arch.smoke if args.smoke else arch.spec
    if args.layers is not None:
        try:
            spec = cut_depth(spec, args.layers)
        except ValueError as e:
            ap.error(str(e))
    rt = RuntimeCfg(attention_impl="naive")
    announce("serve", preflight(spec, mode="decode", batch=args.slots,
                                seq=1, kv_len=args.kv_len,
                                dp=jax.device_count(),
                                ep=spec.moe is not None))
    # jitted: built in place on the device, never a doubled layer stack
    params = jax.jit(lambda k: init_params(spec, rt, k))(
        jax.random.PRNGKey(0))
    engine = Engine(spec, rt, params, batch_slots=args.slots,
                    kv_len=args.kv_len)
    rng = np.random.RandomState(0)
    for rid in range(args.requests):
        engine.submit(Request(rid=rid,
                              prompt=rng.randint(1, spec.vocab,
                                                 size=rng.randint(3, 9)),
                              max_new=args.max_new))
    done = engine.run(max_steps=400)
    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: -> {r.out}")
    print(f"served {len(done)}/{args.requests}")
    if len(done) < args.requests:
        raise SystemExit(f"served only {len(done)} of {args.requests} "
                         f"requests")
    return engine, done


if __name__ == "__main__":
    main()
