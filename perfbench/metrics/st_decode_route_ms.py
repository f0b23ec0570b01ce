"""Device self time a decode step spends in ``moe.route`` alone: the
router's product, top 6 of 64, the softmax over the six and the route's
plan (the sort by expert, the counts, the permutation back), which this
configuration lays down BEFORE each layer's attention; milliseconds an
execution of the decode-step program in the traced slice
(``perfbench/regions.py``).  A PART of ``st_decode_experts_ms``: what the
early route costs, and beside ``st_decode_attn_ms`` whether it is small
enough to run in the attention kernel's shadow.  A program that places no
region gives nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.ms_per_execution(ctx, regions.DECODE, ("moe.route",))
