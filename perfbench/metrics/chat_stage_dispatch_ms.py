"""The median over the traced slice's steps of the time from the stamp
after the seven uploads to the end of ``serve.step.stage``: the call of the
step program over the whole parameter tree until it returns
(``dispatch_us`` on the program's ``serve.step.retire`` regions).  With
``chat_stage_upload_ms`` it adds up to the stage.  A program that places no
such stat gives nothing to read."""

import statistics

from perfbench.metrics import _retire_stats


def read(ctx):
    steps = _retire_stats.read(ctx, ("dispatch_us",))
    return statistics.median(s["dispatch_us"] for s in steps) / 1e3 \
        if steps else None
