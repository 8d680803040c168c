import os
# a host-side lowering tool: 512 placeholder CPU devices, never the chip
os.environ["XLA_FLAGS"] = (os.environ.get("_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production mesh, record memory/cost/collective analysis.

Must be run as a module: ``PYTHONPATH=src python -m repro.launch.dryrun
--all`` (single-pod 16x16 baseline + 2x16x16 multi-pod pass), or
``--arch granite-34b --shape train_4k [--multipod]`` for one cell.
Results append to a JSONL (default ``dryrun_results.jsonl``); completed
cells are skipped on re-run, so the sweep is resumable.
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, Arch, get as get_arch, ARCHS
from repro.launch.compile_cache import use_compile_cache
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.preflight import preflight
from repro.models import lm
from repro.models.common import AxisRules, RuntimeCfg
from repro.parallel.sharding import arch_rules, data_axes_of, param_shardings
from repro.train.optimizer import (OptCfg, init_opt_state,
                                   opt_state_shardings)
from repro.train.train_step import make_train_step

# v5e roofline constants (assignment)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def abstract_params(arch: Arch, rt: RuntimeCfg):
    return jax.eval_shape(
        lambda: lm.init_params(arch.spec, rt, jax.random.PRNGKey(0)))


def batch_specs(arch: Arch, shape, mesh) -> tuple[dict, dict]:
    """(ShapeDtypeStructs, NamedShardings) for the data batch."""
    spec = arch.spec
    da = data_axes_of(mesh)
    b, s = shape.global_batch, shape.seq_len
    sds, shd = {}, {}
    text_s = s - spec.vision_seq if spec.vision_seq else s
    sds["tokens"] = jax.ShapeDtypeStruct((b, text_s), jnp.int32)
    shd["tokens"] = NamedSharding(mesh, P(da))
    sds["labels"] = jax.ShapeDtypeStruct((b, text_s), jnp.int32)
    shd["labels"] = NamedSharding(mesh, P(da))
    if spec.encoder_layers:
        sds["frames"] = jax.ShapeDtypeStruct((b, spec.enc_seq, spec.d_model),
                                             jnp.bfloat16)
        shd["frames"] = NamedSharding(mesh, P(da))
    if spec.vision_seq:
        sds["vision"] = jax.ShapeDtypeStruct((b, spec.vision_seq, spec.d_model),
                                             jnp.bfloat16)
        shd["vision"] = NamedSharding(mesh, P(da))
    return sds, shd


def input_specs(arch: Arch, shape_name: str, *, multi_pod: bool = False):
    """Public helper: ShapeDtypeStruct stand-ins for every model input."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    return batch_specs(arch, SHAPES[shape_name], mesh)[0]


def _cache_abstract(arch: Arch, rt, batch: int, kv_len: int):
    return jax.eval_shape(lambda: lm.init_cache(arch.spec, rt, batch, kv_len))


def _cache_shardings(cache_abs, mesh, *, batch: int = 0,
                     seq_axis: Optional[str] = None, buggy: bool = False):
    """Decode-cache shardings.  ``buggy=True`` reproduces the naive
    'first divisible dim' heuristic (which lands on the layer-stack dim
    and forces per-layer gathers) — kept as the recorded baseline of
    §Perf iteration 1 on minitron-8b/decode_32k."""
    da = data_axes_of(mesh)
    deg = int(np.prod([mesh.shape[a] for a in da]))

    def one(x):
        entries: list = [None] * len(x.shape)
        if buggy:
            for d, sz in enumerate(x.shape):
                if sz % deg == 0 and sz > 1:
                    entries[d] = da
                    break
            return NamedSharding(mesh, P(*entries))
        # shard the batch dim (identified by size), never the layer stack
        bdim = next((d for d, sz in enumerate(x.shape)
                     if sz == batch and sz % deg == 0), None)
        if bdim is not None:
            entries[bdim] = da
        if seq_axis is not None and len(x.shape) >= 3:
            # optionally shard the kv-seq dim (largest remaining) over model
            cand = [(sz, d) for d, sz in enumerate(x.shape)
                    if entries[d] is None and sz % mesh.shape[seq_axis] == 0
                    and sz > 1]
            if cand:
                sz, d = max(cand)
                if sz >= 4 * mesh.shape[seq_axis]:
                    entries[d] = seq_axis
        return NamedSharding(mesh, P(*entries))
    return jax.tree.map(one, cache_abs)


def lower_cell(arch: Arch, shape_name: str, *, multi_pod: bool = False,
               rt: Optional[RuntimeCfg] = None,
               rule_overrides: Optional[dict] = None,
               donate: bool = True):
    """Lower + compile one (arch x shape x mesh) cell; returns
    (lowered, compiled, mesh, meta)."""
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rt = rt or RuntimeCfg(remat="full")
    rules_d = arch_rules(arch.spec, mesh, overrides=rule_overrides, sp=rt.sp)
    rules = AxisRules(rules_d)
    rules.mesh = mesh            # enables the shard_map EP path in MoE
    spec = arch.spec

    with jax.set_mesh(mesh):
        params_abs = abstract_params(arch, rt)
        p_shard = param_shardings(params_abs, rules_d, mesh)
        meta = {"fsdp": any(v == data_axes_of(mesh)
                            for v in [rules_d.get("embed")]),
                "rules": {k: str(v) for k, v in rules_d.items()}}
        if shape.kind == "train":
            opt_abs = jax.eval_shape(lambda: init_opt_state(params_abs))
            o_shard = opt_state_shardings(params_abs, rules_d, mesh,
                                          zero1=rt.zero1,
                                          data_axes=data_axes_of(mesh))
            bsds, bshard = batch_specs(arch, shape, mesh)
            step = make_train_step(spec, rt, OptCfg(), rules)
            fn = jax.jit(step,
                         in_shardings=(p_shard, o_shard, bshard),
                         donate_argnums=(0, 1) if donate else ())
            lowered = fn.lower(params_abs, opt_abs, bsds)
        elif shape.kind == "prefill":
            bsds, bshard = batch_specs(arch, shape, mesh)
            bsds.pop("labels")
            bshard.pop("labels")

            def prefill(params, batch):
                return lm.forward(params, batch["tokens"], spec, rt, rules,
                                  frames=batch.get("frames"),
                                  vision=batch.get("vision"))
            fn = jax.jit(prefill, in_shardings=(p_shard, bshard))
            lowered = fn.lower(params_abs, bsds)
        else:                                        # decode
            b = shape.global_batch
            cache_abs = _cache_abstract(arch, rt, b, shape.seq_len)
            c_shard = _cache_shardings(
                cache_abs, mesh, batch=b,
                seq_axis=(rule_overrides or {}).get("_cache_seq_axis"),
                buggy=(rule_overrides or {}).get("_buggy_cache", True))
            tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
            deg = int(np.prod([mesh.shape[a] for a in data_axes_of(mesh)]))
            t_shard = NamedSharding(
                mesh, P(data_axes_of(mesh)) if b % deg == 0 else P())

            def serve_step(params, cache, tokens):
                return lm.decode_step(params, cache, tokens, spec, rt, rules)
            fn = jax.jit(serve_step,
                         in_shardings=(p_shard, c_shard, t_shard),
                         donate_argnums=(1,) if donate else ())
            lowered = fn.lower(params_abs, cache_abs, tok)
        compiled = lowered.compile()
    return lowered, compiled, mesh, meta


def analyze(arch: Arch, shape_name: str, compiled, mesh, *,
            wall_s: float) -> dict:
    ca = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    # trip-count-aware HLO walk (XLA's cost_analysis counts while bodies
    # once and reports no collective volume — see hlo_analysis docstring)
    walk = analyze_hlo(hlo)
    coll = walk["collectives"]
    chips = int(np.prod(list(mesh.shape.values())))
    flops = float(walk["flops"])
    bytes_acc = float(walk["bytes"])
    coll_total = float(walk["collective_bytes"])
    spec = arch.spec
    shp = SHAPES[shape_name]
    tokens = shp.global_batch * (shp.seq_len if shp.kind != "decode" else 1)
    n_active = spec.active_params()
    model_flops = (6.0 if shp.kind == "train" else 2.0) * n_active * tokens
    rec = {
        "arch": arch.name, "shape": shape_name,
        "mesh": "x".join(str(v) for v in mesh.shape.values()),
        "chips": chips,
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_acc,
        "xla_flops_once": float(ca.get("flops", 0.0)),
        "xla_bytes_once": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes_per_dev": coll_total,
        "collectives": coll,
        "t_compute_s": flops / PEAK_FLOPS,
        "t_memory_s": bytes_acc / HBM_BW,
        "t_collective_s": coll_total / LINK_BW,
        "model_flops_total": model_flops,
        "useful_flops_ratio": model_flops / (flops * chips) if flops else 0.0,
        "peak_memory_per_dev_gb": None,
        "compile_wall_s": round(wall_s, 2),
    }
    try:
        rec["peak_memory_per_dev_gb"] = round(
            mem.temp_size_in_bytes / 2**30 +
            mem.argument_size_in_bytes / 2**30 +
            mem.output_size_in_bytes / 2**30, 3)
        rec["temp_gb"] = round(mem.temp_size_in_bytes / 2**30, 3)
        rec["args_gb"] = round(mem.argument_size_in_bytes / 2**30, 3)
    except Exception:
        rec["memory_analysis"] = str(mem)[:2000]
    dom = max(("t_compute_s", "t_memory_s", "t_collective_s"),
              key=lambda k: rec[k])
    rec["dominant"] = dom.replace("t_", "").replace("_s", "")
    return rec


def stage_predict(arch: Arch, shape_name: str, *, multi_pod: bool = False,
                  fsdp: bool = False, zero1: bool = True) -> dict:
    """Symbolic STAGE estimate for one dry-run cell (Scenario pipeline):
    predicted step time / peak memory on the production mesh, recorded
    next to the XLA-measured numbers for fidelity tracking.  Mirrors the
    runtime strategy: experts shard over the model ("tp") axis like the
    shard_map EP path, and optimizer state follows ``rt.zero1``."""
    shp = SHAPES[shape_name]
    return preflight(arch.spec, mode=shp.kind, batch=shp.global_batch,
                     seq=shp.seq_len, dp=32 if multi_pod else 16, tp=16,
                     sp=True, fsdp=fsdp, zero1=zero1,
                     ep="tp" if arch.spec.moe is not None else False)


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             out_path: str, rt: Optional[RuntimeCfg] = None,
             label: str = "") -> dict:
    arch = get_arch(arch_name)
    if shape_name in arch.skip:
        rec = {"arch": arch_name, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "SKIP", "reason": arch.skip[shape_name]}
    else:
        t0 = time.time()
        try:
            lowered, compiled, mesh, meta = lower_cell(
                arch, shape_name, multi_pod=multi_pod, rt=rt)
            rec = analyze(arch, shape_name, compiled, mesh,
                          wall_s=time.time() - t0)
            rec["status"] = "OK"
            try:
                rec["stage_predict"] = stage_predict(
                    arch, shape_name, multi_pod=multi_pod,
                    fsdp=bool(meta.get("fsdp")),
                    zero1=(rt or RuntimeCfg(remat="full")).zero1)
            except Exception as e:  # noqa: BLE001 — advisory only
                rec["stage_predict"] = {"error": f"{type(e).__name__}: {e}"}
            del lowered, compiled
        except Exception as e:  # noqa: BLE001 — record and continue sweep
            rec = {"arch": arch_name, "shape": shape_name,
                   "mesh": "2x16x16" if multi_pod else "16x16",
                   "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:],
                   "compile_wall_s": round(time.time() - t0, 2)}
    if label:
        rec["label"] = label
    with open(out_path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return rec


def done_cells(out_path: str) -> set:
    done = set()
    if os.path.exists(out_path):
        with open(out_path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if r.get("status") in ("OK", "SKIP") and not r.get("label"):
                    done.add((r["arch"], r["shape"], r["mesh"]))
    return done


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    args = ap.parse_args()
    use_compile_cache()

    if args.all:
        done = done_cells(args.out)
        cells = [(a, s, mp) for a in ARCHS for s in SHAPES
                 for mp in (False, True)]
        for a, s, mp in cells:
            mesh_tag = "2x16x16" if mp else "16x16"
            if (a, s, mesh_tag) in done:
                continue
            t0 = time.time()
            rec = run_cell(a, s, multi_pod=mp, out_path=args.out)
            print(f"[{time.strftime('%H:%M:%S')}] {a} {s} {mesh_tag}: "
                  f"{rec['status']} ({time.time()-t0:.1f}s)", flush=True)
        return
    rec = run_cell(args.arch, args.shape, multi_pod=args.multipod,
                   out_path=args.out)
    print(json.dumps(rec, indent=2))


if __name__ == "__main__":
    main()
