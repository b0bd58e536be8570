"""dispatch layer: mean milliseconds of the program's ``fluid.enqueue`` span
over the traced stretch — the jitted call alone (``compiled.fn(...)`` in
``Executor._dispatch``): jit's argument flattening and sharding checks over
the step's state arrays, then the runtime's enqueue; it returns before the
device finishes."""

from harness import program_spans


def read(ctx):
    spans = program_spans.spans(ctx)
    return None if spans is None else spans.mean_ms("enqueue")
