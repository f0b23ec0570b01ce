"""Device self time a decode step spends on the seven convolution layers'
gates and taps: the region ``short_conv.step`` (``B * X``, three taps over the
lane's tail and the new row, ``C *``; the projections are ``attn.qkv`` /
``attn.out``'s and the tail's shift ``cache.write``'s), in milliseconds an
execution of the decode-step program in the traced slice
(``perfbench/regions.py``).  A PART of ``lfm_decode_matmul_ms``, not a fourth
term beside the three that add up.  A program that places no such region (the
parent of the PR that added it) gives nothing to read."""

from perfbench import regions


REGION = "short_conv.step"


def read(ctx):
    got = regions._of(ctx, regions.DECODE)
    if not got or REGION not in got[2]:
        return None
    return 1e3 * got[2][REGION] / got[0]
