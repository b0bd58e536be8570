"""dispatch layer: share of the traced stretch in which the chip that idles
most ran nothing while the program was inside ``fluid.step``
(``Executor.run`` / ``run_window``) and outside ``fluid.feed_wait``: the part
of ``device_idle_share`` the executor's own Python and the jitted call answer
for.  What is left of ``device_idle_share`` lies outside ``fluid.step``: the
caller's."""

from harness import program_spans


def read(ctx):
    shares = program_spans.idle_by_span(ctx)
    return None if shares is None else shares[1]
