"""Backend-compile and persistent-cache-load seconds during set-up
(jax.monitoring durations)."""


def read(ctx):
    return ctx["setup"]["compile_s"]
