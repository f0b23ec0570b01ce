"""All the time between first and last token of every request due in the
window, over all their token gaps (weighted by tokens)."""

from perfbench.metrics import _common


def read(ctx):
    return _common.tpot_mean_ms(ctx)
