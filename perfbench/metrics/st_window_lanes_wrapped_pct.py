"""Of the seated lanes of a dispatched decode step, the share whose position
has passed the ring's rows, so whose ring has gone round
(``window_lanes_wrapped`` over ``lanes_live`` on the program's retire
regions, both counted by the host from the arrays it uploads), averaged over
the steps of the traced slice.  Says whether the queue really mixes lanes
inside the window with lanes past it: NOT a goal, expected near half.  A
program that places no such stat (the parent of the PR that added it; a
model without window layers) gives nothing to read."""

from perfbench.metrics import _retire_stats


def read(ctx):
    steps = _retire_stats.read(ctx, ("window_lanes_wrapped", "lanes_live"))
    shares = [s["window_lanes_wrapped"] / s["lanes_live"] for s in steps
              if s["lanes_live"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
