"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch granite-34b --smoke
    PYTHONPATH=src python -m repro.launch.train --arch rwkv6-7b --layers 1 \\
        --seq 4096 --batch 1 --steps 5

``--smoke`` runs the reduced config; ``--layers N`` keeps the published
widths and cuts the depth to N layers (whole periods of the layer
pattern only); with neither, the full config runs.  Params and optimizer
state are built in place on a (data, model) mesh of the local devices
under the same logical sharding rules the dry-run lowers (FSDP/ZeRO-1),
and the step donates them.  Checkpoints are written to, and resumed
from, ``--ckpt-dir`` only.  Wires together: config registry, data
pipeline, sharded train_step, checkpoint manager, straggler watchdog.

Each step is a ``train.step`` span (``repro.obs``) with the children
``train.data`` (batch and ``device_put``), ``train.dispatch`` and
``train.sync`` (the host's read of the loss); its duration feeds the
log line and the watchdog, and under a JAX profiler the spans name the
device's idle gaps.
"""
import argparse

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import CheckpointManager
from repro.configs import cut_depth, get as get_arch
from repro.data import DataCfg, TokenPipeline
from repro.ft import StragglerWatchdog
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.preflight import announce, preflight
from repro.models import RuntimeCfg, init_params
from repro.models.common import AxisRules
from repro.obs import span, timed
from repro.parallel.sharding import arch_rules, data_axes_of, param_shardings
from repro.train import OptCfg, init_opt_state, make_train_step
from repro.train.optimizer import opt_state_shardings


def runtime_cfg(seq: int) -> RuntimeCfg:
    """The launcher's runtime: full remat, as the dry-run lowers it (the
    layer-stack residuals of a full-width model do not fit one chip's
    HBM next to its train state otherwise)."""
    return RuntimeCfg(attention_impl="chunked", attn_chunk=max(64, seq),
                      remat="full")


def main(argv=None) -> dict:
    """Runs the loop; returns ``{"losses", "params", "opt", "mesh"}``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    arch = get_arch(args.arch)
    spec = arch.smoke if args.smoke else arch.spec
    if args.layers is not None:
        try:
            spec = cut_depth(spec, args.layers)
        except ValueError as e:
            ap.error(str(e))
    rt = runtime_cfg(args.seq)
    mesh = make_local_mesh()
    dp, tp = mesh.shape["data"], mesh.shape["model"]
    if args.batch % dp:
        ap.error(f"--batch {args.batch} must divide over {dp} data shards")
    print(f"training {spec.name}: {spec.params()/1e6:.1f}M params, "
          f"mesh data={dp} model={tp}")
    announce("train", preflight(spec, mode="train", batch=args.batch,
                                seq=args.seq, dp=dp, tp=tp,
                                ep=spec.moe is not None))

    rules_d = arch_rules(spec, mesh, sp=rt.sp)
    rules = AxisRules(rules_d)
    rules.mesh = mesh            # enables the shard_map EP path in MoE
    da = data_axes_of(mesh)
    b_shard = NamedSharding(mesh, P(da))
    pipe = TokenPipeline(DataCfg(global_batch=args.batch, seq_len=args.seq,
                                 vocab=spec.vocab, seed=0,
                                 num_hosts=jax.process_count(),
                                 host_id=jax.process_index()))
    watchdog = StragglerWatchdog(n_hosts=max(1, jax.process_count()))

    with jax.set_mesh(mesh):
        key = jax.random.PRNGKey(0)
        init = lambda k: init_params(spec, rt, k)          # noqa: E731
        p_shard = param_shardings(jax.eval_shape(init, key), rules_d, mesh)
        # built in place, sharded: never a host copy or a doubled stack
        params = jax.jit(init, out_shardings=p_shard)(key)
        o_shard = opt_state_shardings(params, rules_d, mesh, zero1=rt.zero1,
                                      data_axes=da)
        opt = jax.jit(init_opt_state, out_shardings=o_shard)(params)
        mgr, start = None, 0
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir, keep=2, every=10)
            state, start = mgr.resume({"params": params, "opt": opt},
                                      shardings={"params": p_shard,
                                                 "opt": o_shard})
            if state:
                params, opt = state["params"], state["opt"]
                print(f"resumed at step {start} from {args.ckpt_dir}")
        step_fn = jax.jit(
            make_train_step(spec, rt, OptCfg(lr=1e-3, warmup=5), rules),
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, NamedSharding(mesh, P())),
            donate_argnums=(0, 1))

        losses = []
        for step in range(start, args.steps):
            with timed("train.step", step=step) as t:
                with span("train.data"):
                    batch = {k: jax.device_put(v, b_shard)
                             for k, v in pipe.batch(step).items()}
                with span("train.dispatch"):
                    params, opt, m = step_fn(params, opt, batch)
                with span("train.sync"):
                    loss = float(m["loss"])
            d = watchdog.observe(t.dur)
            print(f"step {step:4d} loss {loss:.4f} ({t.dur:.2f}s) "
                  f"[{d.kind}]", flush=True)
            losses.append(loss)
            if mgr is not None:
                mgr.maybe_save(step + 1, {"params": params, "opt": opt},
                               host_id=jax.process_index())
    print("done")
    return {"losses": losses, "params": params, "opt": opt, "mesh": mesh}


if __name__ == "__main__":
    main()
