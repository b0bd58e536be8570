"""kernels layer: device milliseconds a step spends in the Pallas flash
attention's fused backward kernel (``_flash_backward`` where a head is one
tile: dQ, dK and dV from one pass over the scores, in place of the dQ and
dK/dV passes), on the first chip: the trace's Mosaic custom calls whose
instruction XLA:TPU named ``flash_bwd`` after the kernel's ``name=`` in
``ops/pallas_ops.py``.  0 from a program whose step runs the two passes."""

from harness import program_spans


def read(ctx):
    return program_spans.kernel_ms_per_step(ctx, "flash_bwd")
