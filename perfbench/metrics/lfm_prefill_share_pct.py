"""Whole-prompt prefill's share of the engine's time in the window."""

from perfbench.metrics import _common


def read(ctx):
    return _common.prefill_share_pct(ctx)
