"""Compilations and compile-cache loads inside the window; should read 0."""


def read(ctx):
    return float(ctx["counters"]["compiles_in_window"])
