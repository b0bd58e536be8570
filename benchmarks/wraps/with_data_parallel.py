"""Single-process data parallelism over every chip of the host through
GSPMD: ``fluid.CompiledProgram(main).with_data_parallel(loss_name=...)``,
what Fluid users call to use a host's chips."""

# XLA spells the gradient reduction "all-reduce(" or, made asynchronous,
# "all-reduce-start("
EXPECTS_IN_HLO = ["all-reduce"]


def wrap(main, startup, loss, n_devices):
    import paddle_tpu.fluid as fluid
    return fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
