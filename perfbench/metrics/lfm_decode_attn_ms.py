"""Device self time a decode step spends on its caches and on attending over
them, in the traced slice (``perfbench/regions.py``): the regions
``cache.gather``, ``cache.write`` (the two attention layers' rows and the
seven convolution layers' tails) and ``attn.scores`` (the paged-attention
kernel at heads of 64) with what nests in them, in milliseconds an execution
of the decode-step program.  With ``lfm_decode_matmul_ms`` and
``lfm_decode_unnamed_ms`` it adds up to the step's device self time.  A
program that places no region gives nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.ms_per_execution(ctx, regions.DECODE, regions.ATTENTION)
