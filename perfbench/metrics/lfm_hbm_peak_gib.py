"""memory_stats() peak_bytes_in_use on the chip, read before the reference
runs: weights, the two attention layers' pools, the seven convolution
layers' tails, and what the largest prefill held."""

from perfbench.metrics import _common


def read(ctx):
    return _common.hbm_peak_gib(ctx)
