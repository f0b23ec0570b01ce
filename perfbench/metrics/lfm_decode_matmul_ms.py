"""Device self time a decode step spends in every region that is not
attention's (``lfm_decode_attn_ms``): the experts (``lfm_decode_experts_ms``,
a PART of this), the convolution layers' gates and taps
(``lfm_decode_conv_ms``, a PART of this), the projections, the dense MLP, the
head, and the small ones, by far the read of the weights; milliseconds an
execution of the decode-step program in the traced slice
(``perfbench/regions.py``).  A program that places no region gives nothing to
read."""

from perfbench import regions


def read(ctx):
    return regions.ms_per_execution(ctx, regions.DECODE, None)
