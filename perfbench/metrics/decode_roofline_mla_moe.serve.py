"""Share of its roofline that the decode step of a model with latent
attention and routed experts reaches: the least time the chip could
take for what the window's decode steps need (per step the larger of
its FLOPs over the bf16 peak and its bytes over the HBM bandwidth:
every weight once, the live rows' filled latent cache, the FLOPs of
``mfu_mla_moe.serve``; see ``flops_mla_moe.decode_step``) over the device
time of the ``serve_step`` program in the trace.  Nothing to read where
the trace holds no such program or the program keeps no
``moe.expert_tokens`` samples."""
import flops_mla_moe as fm
from metrics import common

PROGRAM = "serve_step"


def read(r):
    from repro.obs import metrics
    mods = [m for n, m in r.trace["modules"].items() if PROGRAM in n]
    spec, layers = common.runtime_spec(r)
    w = r.window
    n = sum(w["steps_per_wave"])
    newest = getattr(metrics.histogram("moe.expert_tokens"), "newest", None)
    xs = newest(n) if newest is not None else []
    if not mods or not n or len(xs) < n:
        return None
    seconds = sum(m["seconds"] for m in mods)
    routed = iter(fm.window_routed(spec, layers, xs))
    rows = iter(w["live_rows"])
    t = 0.0
    for steps in w["steps_per_wave"]:
        for k in range(steps):
            d = fm.decode_step(spec, layers, next(rows), k + 1, next(routed))
            t += common.flops.roofline_seconds(d["flops"], d["bytes"],
                                               r.peak())[0]
    print(f"perfbench: decode_roofline_mla_moe.serve "
          f"{sum(m['count'] for m in mods)} {PROGRAM} executions for {n} "
          f"steps, {seconds} device s, roofline {t} s", flush=True)
    return 100.0 * t / seconds
