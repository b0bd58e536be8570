"""kernels layer: the least time the chip could take for the attention of a
step's WINDOWED layers — the larger of its FLOPs over the bf16 peak and its
bytes over the HBM peak, both from the ``"window"`` entry of the
configuration's ``kernel_costs(params)`` (from shapes: 14 * D FLOPs a query
head over the pairs the band leaves, every operand read once) and the table
in ``harness/peaks.py`` — over those layers' named flash kernels' measured
device time (``window_attn_ms_per_step``, the replayed forward among it: a
kernel that masked the tiles outside the band instead of skipping them
would read a share 2.3 times lower at 16384 tokens with a window of 4096).
Nothing where the builder counts no windowed layer or the step runs none."""

import os

from harness import spec


def _measured_ms(ctx):
    """``window_attn_ms_per_step``'s own reading (one reader, two metrics)."""
    return spec.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "window_attn_ms_per_step.py")).read(ctx)


def read(ctx):
    peaks = ctx["peaks"]
    costs_of = getattr(ctx["builder"], "kernel_costs", None)
    if peaks is None or costs_of is None:
        return None
    costs = costs_of(ctx["params"]).get("window")
    measured_ms = _measured_ms(ctx)
    if not costs or measured_ms is None:
        return None
    compute_s = costs["flops"] / peaks["bf16_flops_per_s"]
    memory_s = costs["bytes"] / peaks["hbm_bytes_per_s"]
    print("window_attn_roofline: least time %.3f ms compute-bound, %.3f ms "
          "memory-bound (%s binds); measured %.3f ms a step" % (
              1e3 * compute_s, 1e3 * memory_s,
              "compute" if compute_s >= memory_s else "memory", measured_ms),
          flush=True)
    return 100.0 * 1e3 * max(compute_s, memory_s) / measured_ms
