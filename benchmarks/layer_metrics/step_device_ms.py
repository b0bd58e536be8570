"""lowering layer: device milliseconds of one compiled step — the union of
the device-op intervals on one chip over the traced stretch, per step."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.steps:
        return None
    first = trace.devices[min(trace.devices)]
    return 1e3 * first["busy_s"] / trace.steps
