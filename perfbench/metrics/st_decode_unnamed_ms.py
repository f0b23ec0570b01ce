"""Device self time a decode step spends under NO region of the program's:
the compiler's relayout copies, hoisted work, the waits on its async
copies; milliseconds an execution of the decode-step program in the traced
slice (``perfbench/regions.py``, whose table names the five largest of them
by HLO head).  Nothing the architecture adds may land here.  A program that
places no region gives nothing to read."""

from perfbench import regions


def read(ctx):
    return regions.ms_per_execution(ctx, regions.DECODE, (regions.UNNAMED,))
