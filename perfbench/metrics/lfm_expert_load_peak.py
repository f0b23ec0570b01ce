"""The most tokens one expert got in one layer in one decode step, over that
step's fair share (``routed_tokens`` / ``expert_slots``): the largest over the
steps of the traced slice.  Not a goal: it describes the imbalance the
grouped product saw.  32 full lanes give a fair share of 2 tokens an expert a
layer (64 lanes 4), and independent routing a peak of 3 to 4.5 times that
over a few hundred steps; all lanes on one expert read 16."""

from perfbench.metrics import _retire_stats


def read(ctx):
    steps = _retire_stats.read(ctx, ("expert_tokens_max", "routed_tokens",
                               "expert_slots"))
    peaks = [s["expert_tokens_max"] * s["expert_slots"] / s["routed_tokens"]
             for s in steps if s["routed_tokens"]]
    return max(peaks) if peaks else None
