"""Device idle time while the engine thread was in ``serve.turn`` outside
``serve.step`` (schedule, admission's host side, complete, the harness's own
wrapper), as a share of the traced slice (``perfbench/spans.py``)."""

from perfbench import spans


def read(ctx):
    return spans.idle_pct(ctx, "loop")
