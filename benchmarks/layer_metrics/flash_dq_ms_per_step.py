"""kernels layer: device milliseconds a step spends in the Pallas flash
attention's dQ kernel (``_flash_backward``: a pass over the q blocks that
rebuilds the scores), on the first chip: the trace's Mosaic custom calls whose
instruction XLA:TPU named ``flash_dq`` after the kernel's ``name=`` in
``ops/pallas_ops.py``."""

from harness import program_spans


def read(ctx):
    return program_spans.kernel_ms_per_step(ctx, "flash_dq")
