"""The batch's occupancy: the seated lanes of a dispatched decode step
(``lanes_live`` on the program's retire regions: the rows whose page table
names a page, counted by the host from its own arrays) over the engine's
slots, averaged over the steps of the traced slice.  A step reads every
touched expert whole whatever the lanes, so an empty lane is bandwidth paid
for nothing.  A program that places no such stat (the parent of the PR that
added it) gives nothing to read."""

from perfbench.metrics import _retire_stats


def read(ctx):
    slots = ctx["traffic"]["engine"]["num_slots"]
    live = [s["lanes_live"] for s in _retire_stats.read(ctx, ("lanes_live",))]
    return 100.0 * sum(live) / (len(live) * slots) if live else None
