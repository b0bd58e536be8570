"""lowering layer: device milliseconds a step spends inside the loop over
weight-tied passes, forward and backward, on the first chip: every operation
of the ``recurrent`` / ``recurrent_grad`` lowering's scan bodies, which sit
under the scope ``ut_loop`` (the backward's through ``jvp(ut_loop)`` and
``transpose(jvp(ut_loop))``).  What the step spends outside it is the
embedding, the few operations of the exit loss and the optimizer."""

from harness import scope_seconds


def read(ctx):
    return scope_seconds.scope_ms_per_step(ctx, "ut_loop")
