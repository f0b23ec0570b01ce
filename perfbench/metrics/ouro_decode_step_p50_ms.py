"""Median time of the resident decode step over the window's turns, host
clock around the call that ends in the fetch of its tokens: 192 applications
of a block a token."""

from perfbench import stats
from perfbench.metrics import _common


def read(ctx):
    values = _common.decode_step_ms(ctx)
    return stats.quantile(values, 0.50) if values else None
