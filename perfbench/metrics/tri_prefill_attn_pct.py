"""The share of the prefill programs' device time in ``attn.scores`` (the
flash kernel over a band of 2,048 at 4,096 to 32,768 tokens: the cut's one
full layer is its last, whose scores a prefill never needs), in the traced
slice (``perfbench/regions.py``).  A program that places no region gives
nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.pct_of_programs(ctx, regions.PREFILL, ("attn.scores",))
