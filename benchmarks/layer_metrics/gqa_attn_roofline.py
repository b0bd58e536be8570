"""kernels layer: the least time the chip could take for a step's
grouped-query attention — the larger of its FLOPs over the bf16 peak and
its bytes over the HBM peak, both from the configuration's
``kernel_costs(params)`` (from shapes: the causal half of the square for
every query head, K, V, dK and dV once a KEY/VALUE head) and the table in
``harness/peaks.py`` — over the named flash kernels' measured device time
(``gqa_attn_ms_per_step``)."""

from harness import scope_seconds


def read(ctx):
    peaks = ctx["peaks"]
    costs_of = getattr(ctx["builder"], "kernel_costs", None)
    measured_ms = scope_seconds.flash_kernels_ms_per_step(ctx)
    if peaks is None or costs_of is None or measured_ms is None:
        return None
    costs = costs_of(ctx["params"])
    compute_s = costs["flops"] / peaks["bf16_flops_per_s"]
    memory_s = costs["bytes"] / peaks["hbm_bytes_per_s"]
    print("gqa_attn_roofline: least time %.3f ms compute-bound, %.3f ms "
          "memory-bound (%s binds); measured %.3f ms a step" % (
              1e3 * compute_s, 1e3 * memory_s,
              "compute" if compute_s >= memory_s else "memory", measured_ms),
          flush=True)
    return 100.0 * 1e3 * max(compute_s, memory_s) / measured_ms
