"""Device self time a decode step spends on the routed experts: the regions
``moe.route`` (scores, top 4 of 64) and ``moe.experts`` (the sort, three
grouped products over the stacked kernels, the rows put back) of the eight
sparse layers, in milliseconds an execution of the decode-step program in the
traced slice (``perfbench/regions.py``).  A PART of ``lfm_decode_matmul_ms``,
not a fourth term beside the three that add up.  A program that places no
region gives nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.ms_per_execution(ctx, regions.DECODE,
                                    ("moe.route", "moe.experts"))
