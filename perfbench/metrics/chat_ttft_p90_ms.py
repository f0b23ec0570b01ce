"""Per-layer since PR 24 (its spread between same-code runs was 7%, too wide
for an end-to-end bound of at most 10%; PERF.md): 90th percentile, over
every request due in the window, of the time from when
it was due to its first token; a failed or refused request counts as the
worst."""

from perfbench import stats
from perfbench.metrics import _common


def read(ctx):
    values = _common.ttft_ms(ctx)
    return stats.quantile(values, 0.90) if values else None
