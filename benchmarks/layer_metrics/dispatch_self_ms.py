"""dispatch layer: the executor's own Python per step over the traced
stretch — the program's ``fluid.step`` span less its children
``fluid.feed_wait`` (the loader) and ``fluid.enqueue`` (the jitted call): plan
key, feed coercers and placement guards, state gather and write-back, the
step-event and wire-traffic bookkeeping, the loader's sharding hand-back."""

from harness import program_spans


def read(ctx):
    spans = program_spans.spans(ctx)
    if spans is None:
        return None
    own_s = spans.seconds("step") - spans.seconds("feed_wait") - \
        spans.seconds("enqueue")
    return 1e3 * own_s / len(spans.named("step"))
