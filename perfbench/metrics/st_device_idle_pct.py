"""1 minus the union of device operation intervals over the traced slice.
With 12 of the model's 52 layers on the chip the host's turn is a larger
share of a step than in a deployment (PERF.md section 4)."""

from perfbench.metrics import _common


def read(ctx):
    return _common.device_idle_pct(ctx)
