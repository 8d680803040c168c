"""Model FLOP/s utilization of the traced window's training: 6 FLOPs
per matrix parameter a token multiplies through (layers and head, not
the embedding; recomputation not counted) times tokens per second, over
the chip's bf16 peak."""
from metrics import common


def read(r):
    spec, layers = common.runtime_spec(r)
    w = r.window
    total = common.flops.train_flops_per_token(spec, layers) * w["tokens"]
    return common.peak_share(total, w["seconds"], r)
