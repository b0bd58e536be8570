"""kernels layer: the least time the chip could take for a step's flash
attention — the larger of its FLOPs over the bf16 peak and its bytes over
the HBM peak, both from the configuration's ``kernel_costs(params)`` (from
shapes) and the table in ``harness/peaks.py`` — over the kernels' measured
device time.  Nothing where the kernels are not on the path."""

MOSAIC = "tpu_custom_call"


def bounds(costs, peaks):
    """Seconds at the compute peak and at the memory peak."""
    return (costs["flops"] / peaks["bf16_flops_per_s"],
            costs["bytes"] / peaks["hbm_bytes_per_s"])


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    costs_of = getattr(ctx["builder"], "kernel_costs", None)
    costs = costs_of(ctx["params"]) if costs_of else None
    if trace is None or peaks is None or costs is None or not trace.steps:
        return None
    seconds = trace.custom_call_seconds(MOSAIC) / trace.steps
    if not seconds:
        return None
    compute_s, memory_s = bounds(costs, peaks)
    print("flash_attn_roofline: least time %.3f ms compute-bound, %.3f ms "
          "memory-bound (%s binds); measured %.3f ms a step" % (
              1e3 * compute_s, 1e3 * memory_s,
              "compute" if compute_s >= memory_s else "memory",
              1e3 * seconds), flush=True)
    return 100.0 * max(compute_s, memory_s) / seconds
