"""Of the window tables' entries (slots x ring pages) the share that names
a held page and not the sentinel's page of zeros, averaged over the decode
steps of the traced slice (``window_table_pages_held`` /
``window_table_pages`` on the program's retire regions): how much of the
rings' gather reads rows a lane holds.  Beside it the full tables' share
(``table_pages_held`` / ``table_pages``) is a fraction of that: a lane's
table is sized for the longest context.  A program that places neither gives
nothing to read."""

from perfbench.metrics import _retire_stats


def read(ctx):
    steps = _retire_stats.read(ctx, ("window_table_pages_held",
                                     "window_table_pages"))
    shares = [s["window_table_pages_held"] / s["window_table_pages"]
              for s in steps if s["window_table_pages"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
