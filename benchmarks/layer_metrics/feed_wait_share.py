"""input layer: share of the untraced window the consumer (``Executor.run``
pulling ``loader.next_feed()``) spent blocked on the DataLoader queue, from
the program's ``data_wait_seconds_total`` counter."""


def read(ctx):
    return 100.0 * ctx["wait_s"] / ctx["window_s"]
