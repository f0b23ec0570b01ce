"""memory_stats() peak_bytes_in_use on the chip, read before the reference
runs: 4.97 GiB of weights and 9.0 GiB of pool."""

from perfbench.metrics import _common


def read(ctx):
    return _common.hbm_peak_gib(ctx)
