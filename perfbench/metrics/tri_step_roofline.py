"""The least time the chip could take for the whole steps inside the traced
slice (the counts of ``perfbench/costs/trinity-mini.py``, which the
configuration names: a sliding layer reads ``min(context, window)`` rows and
scores the band, a full layer the whole context; a decode step's routed
experts at the EXPECTED number touched under balanced routing; and the
shared peaks) over the device's busy time in the trace.  The PR that added
the configuration wrote no kernel, so this is its share."""

from perfbench.metrics import _common


def read(ctx):
    return _common.step_roofline_pct(ctx)
