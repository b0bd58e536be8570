"""kernels layer: device milliseconds a step spends in the Pallas flash
attention kernels (forward, dQ, dK/dV: every Mosaic custom call of the
step), from the trace.  Nothing where the step holds no such call."""

MOSAIC = "tpu_custom_call"


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.steps:
        return None
    seconds = trace.custom_call_seconds(MOSAIC)
    return 1e3 * seconds / trace.steps if seconds else None
