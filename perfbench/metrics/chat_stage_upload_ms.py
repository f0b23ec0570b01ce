"""The median over the traced slice's steps of the time from the start of
``serve.step.stage`` to the stamp after the seven uploads: the host arrays
and their copies to the device (``upload_us`` on the program's
``serve.step.retire`` regions).  With ``chat_stage_dispatch_ms`` it adds
up to the stage.  A program that places no such stat gives nothing to
read."""

import statistics

from perfbench.metrics import _retire_stats


def read(ctx):
    steps = _retire_stats.read(ctx, ("upload_us",))
    return statistics.median(s["upload_us"] for s in steps) / 1e3 \
        if steps else None
