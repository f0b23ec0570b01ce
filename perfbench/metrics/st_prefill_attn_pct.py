"""The share of the prefill programs' device time in ``attn.scores`` (the
flash kernel at 28 heads of 128 over 512 to 12,288 tokens: three full
layers scored whole, without rotation, beside eight bands of 4,096; the
cut's last layer is a sliding one, whose scores a prefill never needs), in
the traced slice (``perfbench/regions.py``).  A program that places no
region gives nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.pct_of_programs(ctx, regions.PREFILL, ("attn.scores",))
