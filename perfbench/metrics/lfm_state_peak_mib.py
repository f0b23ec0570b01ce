"""The most the seated lanes held in rows of their own beside their pages at
any engine step of the traced slice, in MiB: here the convolution layers'
tails, two rows of 2,048 a lane a layer, whatever the sequence's length.

The program counts it (``serving/kv_pool.py``: resident sequences times the
bytes a slot holds over all layers that keep a row) and puts the count on
its own ``serve.step.retire`` region as the profiler event's ``state_bytes``
stat, so it is read from the trace file, beside the device's operations
(``ohlp_state_peak_mib`` reads the same stat).  A program that places no
such stat gives nothing to read."""

from perfbench.metrics import _retire_stats


def read(ctx):
    found = [s["state_bytes"]
             for s in _retire_stats.read(ctx, ("state_bytes",))]
    return max(found) / 2.0 ** 20 if found else None
