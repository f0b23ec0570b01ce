"""Device idle time while the engine thread was in ``serve.step.retire`` (the
per-slot loop, spans, gauges and the step record), as a share of the traced
slice (``perfbench/spans.py``)."""

from perfbench import spans


def read(ctx):
    return spans.idle_pct(ctx, "retire")
