"""Peak pages in use over the pool's pages (the allocator's high-water
mark); a page holds 16 tokens' keys and values, 8 heads of 64, in each of
the two attention layers.  The convolution layers hold no page: their rows
are ``lfm_state_peak_mib``."""

from perfbench.metrics import _common


def read(ctx):
    return _common.kv_pages_peak_pct(ctx)
