"""Prompt tokens prefilled plus tokens generated by every turn of the engine
loop that ended in the window, over the time from the first of those turns'
start to the last one's end."""

from perfbench.metrics import _common


def read(ctx):
    return _common.tokens_per_s(ctx)
