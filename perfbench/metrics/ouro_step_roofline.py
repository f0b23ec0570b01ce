"""The least time the chip could take for the whole steps inside the traced
slice (the counts of ``perfbench/costs/ouro-2.6b.py``, which the
configuration names: every block weight once a LOOP STEP, every held
token's 192 rows once; and the shared peaks) over the device's busy time in
the trace."""

from perfbench.metrics import _common


def read(ctx):
    return _common.step_roofline_pct(ctx)
