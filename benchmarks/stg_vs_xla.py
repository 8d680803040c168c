"""§Fidelity: STAGE symbolic predictions vs the XLA-compiled artifact.

The paper validates tensor-level accuracy against H100 traces; our
ground truth is the SPMD-partitioned, compiled XLA program (what a pod
would execute).  For every dry-run cell we compare:

* per-device FLOPs: STG (fwd+bwd+opt, + full-remat fwd recompute) vs the
  trip-count-aware HLO walk,
* per-device collective bytes by kind.

Requires ``dryrun_results.jsonl`` (run ``python -m repro.launch.dryrun
--all`` first); cells missing from it are skipped.
"""
import json
import os
import time

from repro import Scenario
from repro.configs import SHAPES, get
from repro.parallel.sharding import shard_plan

COLL_MAP = {"all-gather": "AllGather", "all-reduce": "AllReduce",
            "reduce-scatter": "ReduceScatter", "all-to-all": "AllToAll"}


def _scenario(arch, mesh_tag: str) -> Scenario:
    multi = mesh_tag.startswith("2x")
    spec = arch.spec
    _, fsdp = shard_plan(spec, 16)        # the production mesh's model axis
    # MoE archs route experts over the tensor axis here, mirroring the
    # runtime's shard_map EP path on the production mesh's model axis
    return Scenario(spec).parallel(dp=32 if multi else 16, tp=16, sp=True,
                                   ep="tp" if spec.moe else False,
                                   fsdp=fsdp, zero1=True)


def predict(arch_name: str, shape_name: str, mesh_tag: str) -> dict:
    arch = get(arch_name)
    shp = SHAPES[shape_name]
    sc = _scenario(arch, mesh_tag)
    if shp.kind == "train":
        sc = sc.train(batch=shp.global_batch, seq=shp.seq_len)
    elif shp.kind == "decode":
        sc = sc.decode(batch=shp.global_batch, kv_len=shp.seq_len)
    else:
        sc = sc.prefill(batch=shp.global_batch, seq=shp.seq_len)
    w = sc.trace().workload
    flops = w.total_flops()
    if shp.kind == "train":
        # the runtime rematerializes the forward during backward
        fwd = sum(n.flops * n.repeat for n in w.stage_nodes(0)
                  if n.phase == "fwd" and n.category != "Comm")
        flops += fwd
    vols = w.comm_volume()
    return {"flops": flops, "colls": vols}


def run(report, results_path: str = "dryrun_results.jsonl"):
    if not os.path.exists(results_path):
        report("stg_vs_xla/SKIPPED", 0.0, f"missing {results_path}")
        return []
    recs = {}
    fixed = {}
    for line in open(results_path):
        r = json.loads(line)
        if r.get("status") != "OK":
            continue
        if not r.get("label"):
            recs[(r["arch"], r["shape"], r["mesh"])] = r
        elif "no-qblock" in str(r.get("label")) and r["shape"] == "prefill_32k":
            fixed[(r["arch"], r["shape"])] = r
    rows = []
    for (a, s, m), r in sorted(recs.items()):
        if m != "16x16":
            continue
        # prefer the q-block-fixed runtime where measured (§Perf p1-p3):
        # fidelity should be judged against the non-defective program
        if (a, s) in fixed:
            r = {**fixed[(a, s)], "chips": r["chips"]}
        t0 = time.time()
        try:
            pred = predict(a, s, m)
        except Exception as e:   # noqa: BLE001
            report(f"stg_vs_xla/{a}/{s}", 0.0, f"predict failed: {e}")
            continue
        # both sides are per-device quantities (STG instantiates one
        # representative rank; the SPMD HLO walk sees per-device shapes)
        xla_flops = r["hlo_flops_per_dev"]
        ratio = pred["flops"] / xla_flops if xla_flops else 0.0
        coll_pred = sum(pred["colls"].get(v, 0.0) for v in COLL_MAP.values())
        coll_x = sum(v for k, v in r.get("collectives", {}).items()
                     if k in COLL_MAP)
        cratio = coll_pred / coll_x if coll_x else None
        rows.append({"arch": a, "shape": s,
                     "fixed_runtime": (a, s) in fixed,
                     "stg_flops": pred["flops"], "xla_flops": xla_flops,
                     "flops_ratio": round(ratio, 3),
                     "coll_ratio": round(cratio, 3) if cratio else None})
        report(f"stg_vs_xla/{a}/{s}", (time.time() - t0) * 1e6,
               f"flops_ratio={ratio:.2f} coll_ratio={cratio}")
    if rows:
        med = sorted(r["flops_ratio"] for r in rows)[len(rows) // 2]
        report("stg_vs_xla/median", 0.0, f"median flops ratio {med:.2f}")
    return rows
