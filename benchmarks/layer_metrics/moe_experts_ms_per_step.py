"""kernels layer: device milliseconds a step spends in the routed experts'
grouped matmuls, forward and backward, on the first chip: the operations
the ``routed_experts`` lowering traced under its ``moe_experts`` scope (the
three ``ragged_dot`` calls of the SwiGLU, their transposes, the casts of
the held experts' weights and the silu-and-multiply between them), and
the grouped-matmul kernels themselves, which XLA:TPU puts in as Mosaic
calls named ``ragged-dot*`` without an ``op_name``."""

from harness import scope_seconds


def read(ctx):
    return scope_seconds.scope_ms_per_step(ctx, "moe_experts",
                                           instructions=("ragged-dot",))
