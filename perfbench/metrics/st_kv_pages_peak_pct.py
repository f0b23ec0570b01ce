"""Peak pages in use over the FULL layers' pool's pages (the allocator's
first count and its own high-water mark); a page holds 16 tokens' keys and
values, 4 heads of 128, in each of the three full layers.  The window
layers' pool is counted apart: ``st_window_pages_peak_pct``."""

from perfbench.metrics import _common


def read(ctx):
    return _common.kv_pages_peak_pct(ctx)
