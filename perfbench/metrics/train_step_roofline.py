"""The least time the chip could take for the whole steps inside the traced
slice (the benchmark's own operation and byte counts, the peaks table) over
the device's busy time in the trace."""

from perfbench.metrics import _common


def read(ctx):
    return _common.step_roofline_pct(ctx)
