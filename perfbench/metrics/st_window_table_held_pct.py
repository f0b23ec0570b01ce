"""Of the window tables' entries (slots x ring pages) the share that names
a held page and not the sentinel's page of zeros, averaged over the decode
steps of the traced slice (``window_table_pages_held`` /
``window_table_pages`` on the program's retire regions): how much of the
rings a lane holds.  Well under 100 where lanes INSIDE the window (tables
part sentinel) sit beside lanes gone round (rings whole), which is this
cell's queue.  A program that places neither gives nothing to read."""

from perfbench.metrics import _retire_stats


def read(ctx):
    steps = _retire_stats.read(ctx, ("window_table_pages_held",
                                     "window_table_pages"))
    shares = [s["window_table_pages_held"] / s["window_table_pages"]
              for s in steps if s["window_table_pages"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
