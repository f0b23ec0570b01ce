"""Device idle time while the engine thread was in ``serve.step.fetch`` (launch
latency, holes inside the running program, the copy back), as a share of the
traced slice (``perfbench/spans.py``)."""

from perfbench import spans


def read(ctx):
    return spans.idle_pct(ctx, "fetch")
