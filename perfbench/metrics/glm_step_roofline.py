"""The least time the chip could take for the whole steps inside the traced
slice (the counts of ``perfbench/costs/glm-4.7-flash.py``, which the
configuration names: a decode step's routed experts at the EXPECTED number
touched under balanced routing; and the shared peaks) over the device's busy
time in the trace."""

from perfbench.metrics import _common


def read(ctx):
    return _common.step_roofline_pct(ctx)
