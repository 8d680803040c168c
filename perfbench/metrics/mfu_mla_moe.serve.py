"""Model FLOP/s utilization of the traced window's serving on a model
with latent attention and routed experts: per decode step, 2 FLOPs per
always-on weight for every row a live slot fed, 2 per held expert's
weight for every token of the step routed to a held expert (the
engine's ``moe.expert_tokens`` samples, one per step of the window,
times held experts and MoE layers; ``moe.routed_tokens`` holds the same
over the whole run, set-up included), and the latent attention's FLOPs
over the filled positions of the live rows (``flops_mla_moe``); over the
window and the chip's bf16 peak.  Nothing to read where the program
keeps no such samples."""
import flops_mla_moe as fm
from metrics import common


def read(r):
    from repro.obs import metrics
    spec, layers = common.runtime_spec(r)
    w = r.window
    n = sum(w["steps_per_wave"])
    newest = getattr(metrics.histogram("moe.expert_tokens"), "newest", None)
    xs = newest(n) if newest is not None else []
    if not n or len(xs) < n:
        return None
    routed = iter(fm.window_routed(spec, layers, xs))
    rows = iter(w["live_rows"])
    total = 0.0
    for steps in w["steps_per_wave"]:
        for k in range(steps):
            total += fm.decode_step(spec, layers, next(rows), k + 1,
                                    next(routed))["flops"]
    return common.peak_share(total, w["seconds"], r)
