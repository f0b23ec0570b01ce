"""The share of the prefill programs' device time in ``attn.scores`` (the
flash kernel, or the dense fallback of the 1,600- and 2,400-token buckets),
in the traced slice (``perfbench/regions.py``).  A program that places no region gives nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.pct_of_programs(ctx, regions.PREFILL, ("attn.scores",))
