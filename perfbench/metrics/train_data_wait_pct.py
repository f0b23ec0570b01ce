"""train_step records of the window: data-wait over data-wait plus compute."""

from perfbench.metrics import _common


def read(ctx):
    return _common.data_wait_pct(ctx)
