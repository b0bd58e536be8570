"""device: share of the traced stretch in which no operation ran, on the
chip that idles most."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else 100.0 * trace.idle_share()
