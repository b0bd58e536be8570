"""lowering layer: device milliseconds a step spends in the gated short
convolutions, forward and backward, on the first chip: the operations the
``gated_short_conv`` lowering traced under its ``short_conv`` scope (the
gate products, the shifted multiply-adds over the taps and, in the
backward, their transposes).  The projections before and after are ``mul``
ops of their own and are not counted."""

from harness import scope_seconds


def read(ctx):
    return scope_seconds.scope_ms_per_step(ctx, "short_conv")
