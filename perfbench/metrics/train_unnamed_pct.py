"""The share of the training step's device time under no region of the
program's and in no collective, in the traced steps
(``perfbench/regions.py``): relayout copies, the waits on async copies, the
compiler's own fusions.  A program that places no region gives nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.pct_of_programs(ctx, None, (regions.UNNAMED,))
