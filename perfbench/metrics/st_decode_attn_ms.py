"""Device self time a decode step spends on its caches and on attending over
them, in the traced slice (``perfbench/regions.py``): the regions
``cache.gather``, ``cache.write`` and ``attn.scores`` with what nests in
them (nine rings of 4,112 rows a lane and three tables of 13,312, 28 query
heads in groups of seven), in milliseconds an execution of the decode-step
program.  With ``st_decode_matmul_ms`` and ``st_decode_unnamed_ms`` it adds
up to the step's device self time.  A program that places no region gives
nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.ms_per_execution(ctx, regions.DECODE, regions.ATTENTION)
