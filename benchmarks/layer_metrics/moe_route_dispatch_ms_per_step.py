"""lowering layer: device milliseconds a step spends around the routed
experts' matmuls, forward and backward, on the first chip: the
``routed_experts`` lowering's scopes ``moe_route`` (float32 router, top-k,
weights, load), ``moe_dispatch`` (sort by expert, gather of the rows) and
``moe_combine`` (weighted gather-sum back to the tokens)."""

from harness import scope_seconds


def read(ctx):
    return scope_seconds.scope_ms_per_step(ctx, "moe_route", "moe_dispatch",
                                           "moe_combine")
