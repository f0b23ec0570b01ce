"""Whole-prompt prefill's share of the engine's time in the window: the
queue mixes prompts of 512 with prompts of 12,288."""

from perfbench.metrics import _common


def read(ctx):
    return _common.prefill_share_pct(ctx)
