"""Device self time a decode step spends on its caches and on attending over
them, in the traced slice (``perfbench/regions.py``): the regions
``cache.gather``, ``cache.write`` and ``attn.scores`` with what nests in
them (four rings of 2,064 rows a lane and one table of 33,280), in
milliseconds an execution of the decode-step program.  With
``tri_decode_matmul_ms`` and ``tri_decode_unnamed_ms`` it adds up to the
step's device self time.  A program that places no region gives nothing to
read."""

from perfbench import regions


def read(ctx):
    return regions.ms_per_execution(ctx, regions.DECODE, regions.ATTENTION)
