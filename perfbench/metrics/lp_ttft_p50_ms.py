"""Median time from the client's send to the first token, over requests sent in
the window."""

from perfbench import stats
from perfbench.metrics import _common


def read(ctx):
    values = [(r["engine"]["t_first"] - r["sent"]) * 1e3
              for r in _common.window_requests(ctx)
              if r["engine"] and r["engine"]["t_first"] is not None]
    return stats.quantile(values, 0.50) if values else None
