"""Loop steps run a decoded token: ``loop_steps_run`` / ``loop_tokens`` summed
over the decode steps of the traced slice (the program's retire regions; the
step itself returns the count behind its tokens).  4.0 while every token
runs the whole loop; a later change that lets a lane leave early moves it."""

from perfbench.metrics import _retire_stats


def read(ctx):
    steps = _retire_stats.read(ctx, ("loop_steps_run", "loop_tokens"))
    tokens = sum(s["loop_tokens"] for s in steps)
    return sum(s["loop_steps_run"] for s in steps) / tokens if tokens \
        else None
