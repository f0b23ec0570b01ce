"""The least time the chip could take for the whole steps inside the traced
slice (the counts of ``perfbench/costs/smallthinker-21b-a3b.py``, which the
configuration names: a sliding layer reads ``min(context, 4,096)`` rows and
scores the band, a full layer the whole context; a decode step's routed
experts at the EXPECTED number touched under balanced routing, 50.7 of 64 at
16 lanes; of the last layer's prefill its rows only; and the shared peaks)
over the device's busy time in the trace.  The PR that added the
configuration wrote no kernel, so this is its share."""

from perfbench.metrics import _common


def read(ctx):
    return _common.step_roofline_pct(ctx)
