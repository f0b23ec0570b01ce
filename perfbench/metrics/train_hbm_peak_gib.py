"""The training step's memory on the fullest chip: the compiled step's
footprint by ``memory_analysis()`` or the allocator's peak (read before the
reference runs), whichever is larger."""

from perfbench.metrics import _common


def read(ctx):
    return _common.train_hbm_peak_gib(ctx)
