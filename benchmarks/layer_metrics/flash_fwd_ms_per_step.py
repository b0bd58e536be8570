"""kernels layer: device milliseconds a step spends in the Pallas flash
attention's forward kernel (``_flash_forward``: online softmax over the K/V
blocks), on the first chip: the trace's Mosaic custom calls whose instruction
XLA:TPU named ``flash_fwd`` after the kernel's ``name=`` in
``ops/pallas_ops.py``."""

from harness import program_spans


def read(ctx):
    return program_spans.kernel_ms_per_step(ctx, "flash_fwd")
