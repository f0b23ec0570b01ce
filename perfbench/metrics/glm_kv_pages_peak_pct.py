"""Peak pages in use over the pool's pages (the allocator's own high-water
mark); a page holds 16 tokens' latent rows, 576 entries a token a layer."""

from perfbench.metrics import _common


def read(ctx):
    return _common.kv_pages_peak_pct(ctx)
