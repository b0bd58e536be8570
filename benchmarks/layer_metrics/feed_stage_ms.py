"""input layer: mean milliseconds the loader's worker thread spends staging
one batch — drawing it from the source and ``sharded_put`` — from the
program's ``fluid.feed_stage`` spans of the traced stretch (recorded in
``executor._prefetch_ahead_sync`` / ``reader.FeedRing._producer``).  It runs
beside the step: it costs throughput only once it exceeds the step."""

from harness import program_spans


def read(ctx):
    spans = program_spans.spans(ctx)
    return None if spans is None else spans.mean_ms("feed_stage")
