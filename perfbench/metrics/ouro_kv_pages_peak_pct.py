"""Peak pages in use over the pool's pages (the allocator's own high-water
mark); a page holds 16 tokens' rows of every (loop step, layer): 24 MiB."""

from perfbench.metrics import _common


def read(ctx):
    return _common.kv_pages_peak_pct(ctx)
