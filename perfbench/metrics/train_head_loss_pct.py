"""The share of the training step's device time in ``head`` and ``loss``
(the vocabulary projection and the log-softmax over its logits, forward and
backward), in the traced steps (``perfbench/regions.py``; the step is the
program that took most of the slice's device time).  A program that places no region gives nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.pct_of_programs(ctx, None, ("head", "loss"))
