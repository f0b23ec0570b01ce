"""How late the load generator sent: sent-time minus due-time, 90th percentile."""

from perfbench import stats
from perfbench.metrics import _common


def read(ctx):
    values = _common.gen_late_ms(ctx)
    return stats.quantile(values, 0.90) if values else None
