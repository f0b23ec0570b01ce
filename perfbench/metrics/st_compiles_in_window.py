"""Compilations and compile-cache loads inside the window; should read 0:
four prefill programs (one a prompt length) live under a cache of eight, so
none is evicted and built again while the window is open."""


def read(ctx):
    return float(ctx["counters"]["compiles_in_window"])
