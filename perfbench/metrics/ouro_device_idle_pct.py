"""1 minus the union of device operation intervals over the traced slice."""

from perfbench.metrics import _common


def read(ctx):
    return _common.device_idle_pct(ctx)
