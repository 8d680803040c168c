"""Percent of the traced window with no operation on the device."""
from metrics import common


def read(r):
    return common.idle_share(r)
