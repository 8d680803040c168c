"""Model FLOP/s utilization of the traced window's serving: 2 FLOPs per
matrix parameter (layers and head, not the embedding) for every token a
live slot fed through the model, prompt or output, over the window and
the chip's bf16 peak."""
from metrics import common


def read(r):
    spec, layers = common.runtime_spec(r)
    w = r.window
    total = 2.0 * common.flops.matmul_params(spec, layers) * sum(w["live_rows"])
    return common.peak_share(total, w["seconds"], r)
