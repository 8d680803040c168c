"""Share of its roofline that the decode step reaches: the least time
the chip could take for what the window's decode steps need (the larger
of their FLOPs over the bf16 peak and their bytes over the HBM
bandwidth: every matrix weight once per step and the filled K/V of the
live rows; see ``flops.decode_step``) over the device time of the
``serve_step`` program in the trace.  Nothing to read where the trace
holds no such program."""
from metrics import common

PROGRAM = "serve_step"


def read(r):
    mods = [m for n, m in r.trace["modules"].items() if PROGRAM in n]
    if not mods:
        return None
    seconds = sum(m["seconds"] for m in mods)
    spec, layers = common.runtime_spec(r)
    w = r.window
    need_f = need_b = 0.0
    rows = iter(w["live_rows"])
    for n in w["steps_per_wave"]:
        for k in range(n):
            d = common.flops.decode_step(spec, layers, next(rows), k + 1)
            need_f += d["flops"]
            need_b += d["bytes"]
    t, bound = common.flops.roofline_seconds(need_f, need_b, r.peak())
    print(f"perfbench: decode_roofline.serve {bound}-bound; "
          f"{sum(m['count'] for m in mods)} {PROGRAM} executions for "
          f"{sum(w['steps_per_wave'])} steps, {seconds} device s", flush=True)
    return 100.0 * t / seconds
