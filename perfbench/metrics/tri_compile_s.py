"""Backend-compile and persistent-cache-load seconds during set-up
(jax.monitoring durations): the decode step, the hand-over and one prefill
program a prompt length (4,096 / 8,192 / 16,384 / 32,768 tokens)."""


def read(ctx):
    return ctx["setup"]["compile_s"]
