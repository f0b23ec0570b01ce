"""The most recurrent state (and convolution tails) the seated lanes held
beside their pages at any engine step of the traced slice, in MiB.

The program counts it (``serving/kv_pool.py``: resident sequences times the
bytes a slot holds over all linear-attention layers) and puts the count on
its own ``serve.step.retire`` region as the profiler event's ``state_bytes``
stat, so it is read from the trace file, beside the device's operations.  A
program that places no such stat (the parent of the PR that added it; a
model without such layers) gives nothing to read."""

import os

from perfbench import spec, xplane

REGION, STAT = "serve.step.retire", "state_bytes"


def state_bytes(path: str) -> list[int]:
    """Every ``state_bytes`` stat on the program's retire regions of one
    trace file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if xplane._is_device_plane(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == REGION:
                    out += [int(v) for k, v in ev.stats if k == STAT]
    return out


def read(ctx):
    if not ctx.get("trace"):
        return None
    path = xplane.newest_xplane(os.path.join(spec.OUT_DIR, "trace",
                                             ctx["cell"]))
    found = state_bytes(path) if path else []
    return max(found) / 2.0 ** 20 if found else None
