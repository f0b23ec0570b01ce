"""Peak pages in use over the pool's pages (the allocator's own high-water
mark); the pool holds the full-attention layers' keys and values only."""

from perfbench.metrics import _common


def read(ctx):
    return _common.kv_pages_peak_pct(ctx)
