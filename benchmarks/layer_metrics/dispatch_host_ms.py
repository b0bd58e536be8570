"""dispatch layer: mean host milliseconds a step spends inside ``exe.run``
(it returns before the device finishes) over the untraced window, less the
wait for the loader inside it, which ``feed_wait_share`` reports."""


def read(ctx):
    spans = ctx["dispatch_s"]
    return 1e3 * (sum(spans) - ctx["wait_s"]) / len(spans)
