"""lowering layer: device milliseconds a step spends in the operations the
lowering put under ``role_bwd`` (every ``*_grad`` op and the loss gradient),
on the first chip, from the trace and the program's ``step_scopes()``.  The
forward remainder and the time under no role scope are logged beside it."""

from harness import program_spans


def read(ctx):
    roles = program_spans.role_seconds(ctx)
    return None if roles is None else 1e3 * roles["bwd"] / ctx["trace"].steps
