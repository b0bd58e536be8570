"""collectives layer: device milliseconds a step spends in all-reduce on the
first chip, from the trace: the synchronous ``all-reduce`` operations plus
the asynchronous ones from start to done (those run beside compute: time in
flight, not time exposed).  Nothing on one chip."""

MARKERS = ("all-reduce-start", "all-reduce-done")


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.steps or ctx["chips"] < 2:
        return None
    seconds = trace.op_seconds(
        lambda label, name, target: name.startswith("all-reduce")
        and not name.startswith(MARKERS))
    seconds += trace.op_seconds(
        lambda label, name, target: name.startswith("all-reduce"),
        line="async_ops")
    return 1e3 * seconds / trace.steps if seconds else None
