"""lowering layer: device milliseconds a step spends in the operations the
lowering put under ``role_opt`` (the optimizer's update ops and the
learning-rate schedule), on the first chip, from the trace and the program's
``step_scopes()``."""

from harness import program_spans


def read(ctx):
    roles = program_spans.role_seconds(ctx)
    return None if roles is None else 1e3 * roles["opt"] / ctx["trace"].steps
