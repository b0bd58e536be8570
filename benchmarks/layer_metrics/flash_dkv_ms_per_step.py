"""kernels layer: device milliseconds a step spends in the Pallas flash
attention's dK/dV kernel (``_flash_backward``: a second pass, over the k
blocks, that rebuilds the scores again), on the first chip: the trace's Mosaic
custom calls whose instruction XLA:TPU named ``flash_dkv`` after the kernel's
``name=`` in ``ops/pallas_ops.py``."""

from harness import program_spans


def read(ctx):
    return program_spans.kernel_ms_per_step(ctx, "flash_dkv")
