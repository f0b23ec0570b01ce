"""Median time from the client's send to the start of the engine's admission."""

from perfbench import stats
from perfbench.metrics import _common


def read(ctx):
    values = _common.queue_wait_ms(ctx)
    return stats.quantile(values, 0.50) if values else None
