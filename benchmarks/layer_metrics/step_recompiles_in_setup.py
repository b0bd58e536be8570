"""compile layer: how many times jit compiled a step AGAIN — a backend compile
inside the call of an executable that had run before, for changed argument
shardings or commitment — from the program's
``xla_backend_compiles_total{why=recompile}``.  ``exe.compile_count()`` cannot
see these; the window compiles nothing, so the total is set-up's."""

from harness import program_spans


def read(ctx):
    return program_spans.compile_counter("xla_backend_compiles_total",
                                         why="recompile")
