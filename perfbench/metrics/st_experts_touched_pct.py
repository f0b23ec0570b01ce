"""Of the routed experts of all layers (12 x 64), the share that got at
least one token in a decode step, averaged over the steps of the traced
slice (``experts_touched`` / ``expert_slots`` on the program's retire
regions).  Not a goal: it describes the traffic the expert layer saw.  Under
balanced routing 16 full lanes touch 64 (1 - (58/64)^16) = 50.7 of 64,
79.3%; a router that clumps reads lower, and the step then needs fewer
kernels than ``st_step_roofline`` counts."""

from perfbench.metrics import _retire_stats


def read(ctx):
    steps = _retire_stats.read(ctx, ("experts_touched", "expert_slots"))
    shares = [s["experts_touched"] / s["expert_slots"] for s in steps
              if s["expert_slots"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
