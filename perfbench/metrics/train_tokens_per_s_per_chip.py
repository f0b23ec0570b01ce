"""Every token of every optimizer step that ended in the window, over the time
from the start of the first of those steps to the end of the last (each
closed by a fetch), over the chips."""

from perfbench.metrics import _common


def read(ctx):
    rate = _common.tokens_per_s(ctx)
    return None if rate is None else rate / ctx["chips"]
