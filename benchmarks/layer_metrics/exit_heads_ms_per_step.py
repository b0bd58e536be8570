"""lowering layer: device milliseconds a step spends in the exit heads of
the loop's passes, forward, rematerialised and backward, on the first chip:
the final norm, the LM head's matmul, the cross-entropy and the exit gate of
every pass, which the model builds under ``fluid.name_scope("exit_head")``
(the lowering turns an op's ``op_namescope`` into a scope of its
``op_name``)."""

from harness import scope_seconds


def read(ctx):
    return scope_seconds.scope_ms_per_step(ctx, "exit_head")
