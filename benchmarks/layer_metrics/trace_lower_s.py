"""compile layer: seconds jit spent tracing the Fluid program to a jaxpr and
lowering that to MLIR inside the executor's dispatches, from the program's
``xla_compile_seconds_total{phase=trace|lower, why=dispatch}`` (fed by the
executor's ``jax.monitoring`` listener).  The window compiles nothing, so the
total is set-up's; the backend's share of set-up is ``compile_s``."""

from harness import program_spans

COUNTER = "xla_compile_seconds_total"


def read(ctx):
    parts = [program_spans.compile_counter(COUNTER, phase=phase,
                                           why="dispatch")
             for phase in ("trace", "lower")]
    return None if None in parts else sum(parts)
