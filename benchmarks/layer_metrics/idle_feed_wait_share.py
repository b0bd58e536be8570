"""input layer: share of the traced stretch in which the chip that idles most
ran nothing WHILE the consumer sat in the program's ``fluid.feed_wait`` span
(``GeneratorLoader.next_feed`` / ``FeedRing.__next__`` blocked on the queue):
the part of ``device_idle_share`` the input layer answers for."""

from harness import program_spans


def read(ctx):
    shares = program_spans.idle_by_span(ctx)
    return None if shares is None else shares[0]
