"""memory_stats() peak_bytes_in_use on the chip, read before the reference
runs: 11.12 GB of weights, the three full layers' pool, the nine window
layers' sixteen rings, and what the 12,288-token prefill held.  The cell
closest to the chip's 16 GB."""

from perfbench.metrics import _common


def read(ctx):
    return _common.hbm_peak_gib(ctx)
