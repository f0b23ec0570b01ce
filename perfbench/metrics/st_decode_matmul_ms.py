"""Device self time a decode step spends in every region that is not
attention's (``st_decode_attn_ms``): the projections, the route and the
experts, the head, and the small ones, by far the read of the weights;
milliseconds an execution of the decode-step program in the traced slice
(``perfbench/regions.py``).  A program that places no region gives nothing
to read."""

from perfbench import regions


def read(ctx):
    return regions.ms_per_execution(ctx, regions.DECODE, None)
