"""Backend-compile and persistent-cache-load seconds during set-up
(jax.monitoring durations): the decode step, the hand-over and one prefill
program a prompt length (512 / 2,048 / 6,144 / 12,288 tokens)."""


def read(ctx):
    return ctx["setup"]["compile_s"]
