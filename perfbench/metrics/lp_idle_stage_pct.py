"""Device idle time while the engine thread was in ``serve.step.stage`` (host
arrays, uploads, the dispatch), as a share of the traced slice
(``perfbench/spans.py``)."""

from perfbench import spans


def read(ctx):
    return spans.idle_pct(ctx, "stage")
