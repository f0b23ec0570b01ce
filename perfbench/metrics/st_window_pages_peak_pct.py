"""The window layers' pool: the most pages held at a decode step of the
traced slice over the pages it has (``window_pages_peak``, the allocator's
second high-water mark, over ``window_table_pages``, slots x ring pages,
which IS that pool: both on the program's retire regions).  Under 100 here
and not a goal: a lane inside the window holds only the pages its context
has reached, a lane past it its ring whole.  A program that places neither
gives nothing to read."""

from perfbench.metrics import _retire_stats


def read(ctx):
    steps = _retire_stats.read(ctx, ("window_pages_peak",
                                     "window_table_pages"))
    shares = [s["window_pages_peak"] / s["window_table_pages"]
              for s in steps if s["window_table_pages"]]
    return 100.0 * max(shares) if shares else None
