"""lowering layer: device milliseconds a step spends in the rotary
embedding, forward and backward, on the first chip: the operations whose
``op_name`` holds ``fluid_rotary_embedding``, which reads the forward op,
its replay and backward inside a ``recompute`` span or a loop body, and
the grad op ``fluid_rotary_embedding_grad`` outside them.  The tables of
angles are among it; the projections before and the attention after are
ops of their own and are not counted.  Nothing from a step without the
op."""

from harness import scope_seconds


def read(ctx):
    return scope_seconds.scope_ms_per_step(ctx, "fluid_rotary_embedding")
