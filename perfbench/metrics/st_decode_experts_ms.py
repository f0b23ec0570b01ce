"""Device self time a decode step spends on the routed experts: the regions
``moe.route`` (the router's product over the block's normed input, top 6 of
64, the softmax over the six, and here the sort by expert, the counts and
the permutation back, all ahead of the attention: ``st_decode_route_ms``)
and ``moe.experts`` (the take, three grouped products over the stacked
kernels, the weighted sum) of the twelve layers, in milliseconds an
execution of the decode-step program in the traced slice
(``perfbench/regions.py``).  A PART of ``st_decode_matmul_ms``, not a fourth
term beside the three that add up.  A program that places no region gives
nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.ms_per_execution(ctx, regions.DECODE,
                                    ("moe.route", "moe.experts"))
