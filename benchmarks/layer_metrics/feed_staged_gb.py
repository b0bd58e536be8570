"""input layer: the most the program-bound loader held staged on one device
and not yet handed to a step, GB: the program's
``feed_staged_bytes{stat=peak}`` (the capacity queue, the one-batch lookahead
and the batch the worker is handing to a full queue)."""

from harness import memory_gauges


def read(ctx):
    return memory_gauges.feed_staged_gb()
