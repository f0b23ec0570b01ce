"""Backend-compile and persistent-cache-load seconds during set-up
(jax.monitoring durations): one decode step whose loop over the four steps
is ONE scan around 48 layers, and a prefill program a prompt bucket."""


def read(ctx):
    return ctx["setup"]["compile_s"]
