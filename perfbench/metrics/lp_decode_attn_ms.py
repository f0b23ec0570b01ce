"""Device self time a decode step spends on its cache and on attending over
it, in the traced slice (``perfbench/regions.py``): the regions
``cache.gather``, ``cache.write``, ``attn.scores``, ``mla.absorb`` and
``linear_attention.step`` with what nests in them, in milliseconds an
execution of the decode-step program.  What a step that reads held pages
only can move.  With ``lp_decode_matmul_ms`` and
``lp_decode_unnamed_ms`` it adds up to the step's device self time.
A program that places no region gives nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.ms_per_execution(ctx, regions.DECODE, regions.ATTENTION)
