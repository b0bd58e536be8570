"""compile layer: how many times jit went to XLA during set-up, a
persistent-cache hit included."""


def read(ctx):
    return ctx["setup_compiles"]
