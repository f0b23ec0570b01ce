"""memory_stats() peak_bytes_in_use on the chip, read before the reference
runs: weights, the full layer's pool, the four window layers' rings, and what
the largest prefill held."""

from perfbench.metrics import _common


def read(ctx):
    return _common.hbm_peak_gib(ctx)
