"""The loop step at which the exit gate expects a decoded token to leave,
the sum over the steps of t x (mass leaving at t), averaged over the tokens
of the traced slice (``exit_step_expected_milli`` / ``loop_tokens`` on the
program's retire regions; thousandths, because an event's stats are read as
whole numbers).  NOT a goal: with random weights it describes a random gate.
It says what a scheduler that let lanes leave early would have to work
with."""

from perfbench.metrics import _retire_stats


def read(ctx):
    steps = _retire_stats.read(ctx, ("exit_step_expected_milli",
                                     "loop_tokens"))
    tokens = sum(s["loop_tokens"] for s in steps)
    return sum(s["exit_step_expected_milli"] for s in steps) / 1e3 / tokens \
        if tokens else None
