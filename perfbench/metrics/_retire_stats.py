"""The counters the program puts on its own ``serve.step.retire`` regions as
the profiler event's stats (``utils/profiling.annotate(name, **stats)``),
read from the traced slice's file beside the device's operations.  A program
that places none of the stats asked for (the parent of the PR that added
them; a model without such layers) gives nothing to read."""

import os

from perfbench import spec, xplane

REGION = "serve.step.retire"


def read(ctx, names: tuple) -> list[dict]:
    """One ``{name: value}`` for every retire region of the traced slice
    that carries all of ``names``."""
    if not ctx.get("trace"):
        return []
    path = xplane.newest_xplane(os.path.join(spec.OUT_DIR, "trace",
                                             ctx["cell"]))
    if not path:
        return []
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if xplane._is_device_plane(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != REGION:
                    continue
                stats = {k: int(v) for k, v in ev.stats if k in names}
                if len(stats) == len(names):
                    out.append(stats)
    return out
