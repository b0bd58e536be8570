"""kernels layer: device milliseconds a step spends in the Pallas flash
attention kernels of grouped-query attention (forward, dQ, dK/dV; K and V
read at their own head count), on the first chip: the Mosaic calls XLA:TPU
named ``flash_fwd`` / ``flash_dq`` / ``flash_dkv`` after the kernels'
``name=``.  (The step's other Mosaic calls, XLA's own grouped matmuls, are
not attention and are read by ``moe_experts_ms_per_step``; the sum of dK and
dV over a group, outside the kernels, is plain XLA and is not counted
here.)"""

from harness import scope_seconds


def read(ctx):
    return scope_seconds.flash_kernels_ms_per_step(ctx)
