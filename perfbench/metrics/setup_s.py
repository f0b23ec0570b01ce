"""Process start to the start of the window: loading, weights, compilation or
cache loads, warm-up, the first steps the check reads, the lead-in of the
load."""

from perfbench.metrics import _common


def read(ctx):
    return _common.setup_s(ctx)
