"""compile layer: summed ``backend_compile_duration`` events of set-up —
XLA compiling, or loading the executable from the persistent cache."""


def read(ctx):
    return ctx["setup_compile_s"]
