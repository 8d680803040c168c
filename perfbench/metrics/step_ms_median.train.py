"""Median host-clock time of one train step in the window, in ms: the
steady part of `train_tokens_per_s`, which also counts the rare steps
that stall for a second or more."""


def read(r):
    return r.window["step_ms_median"]
