"""Device trace: time inside collective operations over the device's busy time."""

from perfbench.metrics import _common


def read(ctx):
    return _common.collective_pct(ctx)
