"""The share of the prefill programs' device time in
``linear_attention.scan`` (the chunked gated delta rule of the twelve linear
layers), in the traced slice (``perfbench/regions.py``).  A program that places no region gives nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.pct_of_programs(ctx, regions.PREFILL,
                                   ("linear_attention.scan",))
