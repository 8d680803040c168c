"""Tokens a held expert takes per MoE layer per decode step, averaged
over the window's steps: the engine's ``moe.expert_tokens`` histogram
(``repro.obs``; one sample per step, the mean over held experts and MoE
layers of the tokens of all rows routed there), over its newest samples,
one per step of the window (set-up's warm-up steps come before them).
Nothing to read where the program keeps no such samples."""
import statistics


def read(r):
    from repro.obs import metrics
    n = sum(r.window["steps_per_wave"])
    newest = getattr(metrics.histogram("moe.expert_tokens"), "newest", None)
    xs = newest(n) if newest is not None else []
    if not n or len(xs) < n:
        return None
    return statistics.fmean(xs)
