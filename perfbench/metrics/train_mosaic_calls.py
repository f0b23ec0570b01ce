"""tpu_custom_call count in the compiled step's text: whether the Pallas
kernels are in the program."""


def read(ctx):
    return float(ctx["counters"]["mosaic_calls"])
