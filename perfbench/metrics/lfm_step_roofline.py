"""The least time the chip could take for the whole steps inside the traced
slice (the counts of ``perfbench/costs/lfm2-24b-a2b.py``, which the
configuration names: a convolution layer reads its weights and two rows a
lane and writes one, an attention layer each lane's HELD keys and values
once; a decode step's routed experts at the EXPECTED number touched under
balanced routing at the live lanes; a prefill all experts once and the lower
triangle in two layers; and the shared peaks) over the device's busy time in
the trace.  The PR that added the configuration wrote no kernel (it widened
the paged-attention kernel to heads that divide 128), so this is its
share."""

from perfbench.metrics import _common


def read(ctx):
    return _common.step_roofline_pct(ctx)
