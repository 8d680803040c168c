import os
# a host-side lowering tool: 512 placeholder CPU devices, never the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""§Perf hillclimb driver: re-lower the three chosen cells under each
candidate change and append labeled records to dryrun_results.jsonl.

Cells (chosen per the assignment rubric):
  * granite-34b/train_4k       — dense train, highest-leverage memory term
  * deepseek-v2-236b/train_4k  — EP/MoE+MLA: most representative of the
                                 paper's technique (Table IV AllToAll)
  * minitron-8b/decode_32k     — worst cell (192GB/dev at baseline)

Run: PYTHONPATH=src python -m benchmarks.perf_iterations
"""
import dataclasses
import json
import time

from repro.launch import dryrun
from repro.models.common import RuntimeCfg

BASE = RuntimeCfg(remat="full")

VARIANTS = [
    # --- granite-34b train_4k -------------------------------------------
    ("granite-34b", "train_4k", "g1-remat-dots",
     dataclasses.replace(BASE, remat="dots"), None),
    ("granite-34b", "train_4k", "g2-dots+loss-chunk512",
     dataclasses.replace(BASE, remat="dots", loss_chunk=512), None),
    ("granite-34b", "train_4k", "g3-full+loss-chunk512",
     dataclasses.replace(BASE, remat="full", loss_chunk=512), None),
    ("granite-34b", "train_4k", "g4-dots+losschunk+attnchunk512",
     dataclasses.replace(BASE, remat="dots", loss_chunk=512, attn_chunk=512),
     None),
    # --- deepseek-v2-236b train_4k --------------------------------------
    ("deepseek-v2-236b", "train_4k", "d1-capacity1.0",
     dataclasses.replace(BASE, moe_capacity=1.0), None),
    ("deepseek-v2-236b", "train_4k", "d2-dots+capacity1.0",
     dataclasses.replace(BASE, remat="dots", moe_capacity=1.0), None),
    ("deepseek-v2-236b", "train_4k", "d3-d2+loss-chunk512",
     dataclasses.replace(BASE, remat="dots", moe_capacity=1.0,
                         loss_chunk=512), None),
    ("granite-34b", "train_4k", "g5-no-seq-parallel",
     dataclasses.replace(BASE, sp=False), None),
    ("granite-34b", "train_4k", "g6-no-remat",
     dataclasses.replace(BASE, remat="none"), None),
    ("granite-34b", "train_4k", "g7-nosp+accum4",
     dataclasses.replace(BASE, sp=False, grad_accum=4), None),
    ("granite-34b", "train_4k", "g8-nosp+accum8",
     dataclasses.replace(BASE, sp=False, grad_accum=8), None),
    ("deepseek-v2-236b", "train_4k", "d4-nosp+accum4",
     dataclasses.replace(BASE, sp=False, grad_accum=4, moe_capacity=1.0),
     None),
    # --- prefill cells: the q-block lax.map finding ----------------------
    ("granite-34b", "prefill_32k", "p1-no-qblock-map",
     dataclasses.replace(BASE, attn_q_block=False), None),
    ("qwen3-14b", "prefill_32k", "p2-no-qblock-map",
     dataclasses.replace(BASE, attn_q_block=False), None),
    ("deepseek-v2-236b", "prefill_32k", "p3-no-qblock-map",
     dataclasses.replace(BASE, attn_q_block=False), None),
    ("granite-34b", "train_4k", "g9-no-qblock-map",
     dataclasses.replace(BASE, sp=False, grad_accum=8, attn_q_block=False),
     None),
    # --- minitron-8b decode_32k ------------------------------------------
    ("minitron-8b", "decode_32k", "m1-cache-batch-shard",
     BASE, {"_buggy_cache": False}),
    ("minitron-8b", "decode_32k", "m2-m1+cache-seq-over-model",
     BASE, {"_buggy_cache": False, "_cache_seq_axis": "model"}),
]


def main():
    out = "dryrun_results.jsonl"
    done = set()
    if os.path.exists(out):
        for line in open(out):
            r = json.loads(line)
            if r.get("label"):
                done.add(r["label"])
    for arch, shape, label, rt, overrides in VARIANTS:
        if label in done:
            print(f"skip {label} (done)")
            continue
        t0 = time.time()
        try:
            a = dryrun.get_arch(arch)
            lowered, compiled, mesh, meta = dryrun.lower_cell(
                a, shape, rt=rt, rule_overrides=overrides)
            rec = dryrun.analyze(a, shape, compiled, mesh,
                                 wall_s=time.time() - t0)
            rec["status"] = "OK"
            del lowered, compiled
        except Exception as e:  # noqa: BLE001
            import traceback
            rec = {"arch": arch, "shape": shape, "mesh": "16x16",
                   "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-1500:]}
        rec["label"] = label
        with open(out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        keys = ("t_compute_s", "t_memory_s", "t_collective_s",
                "peak_memory_per_dev_gb")
        print(f"{label}: {rec['status']} "
              + " ".join(f"{k}={rec.get(k)}" for k in keys)
              + f" ({time.time()-t0:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
