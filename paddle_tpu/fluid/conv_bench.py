"""ResNet conv-ceiling A/B: native lax.conv vs im2col-as-matmul vs NHWC
layout, per dominant ResNet-50 layer shape, on the attached chip.

ROADMAP S2's hypothesis is that ResNet-50's step is bound by XLA's conv
efficiency at small channel counts; this harness is its per-layer
experiment: does contracting over C*kh*kw (im2col, FLAGS_conv_im2col),
switching to channels-last (FLAGS_conv_layout=NHWC) or the Pallas
implicit-GEMM kernel lift the per-layer ceiling?

Run: python -m paddle_tpu.fluid.conv_bench [batch]
One JSON line per (layer shape x variant) with ms/step, TFLOP/s and MXU
fraction, streamed as each lands (a killed run keeps its finished
rows).  Protocol: bench.py fence (async dispatch, scalar fetch,
pre-compiled round-trip probe subtracted).
"""

import json
import sys

import numpy as np

# the ResNet-50 training conv population at 224x224 (layer, count in net):
# (C_in, H/W_in, C_out, k, stride)
RESNET50_CONVS = [
    ("stem7x7", 3, 224, 64, 7, 2),
    ("s0_1x1a", 64, 56, 64, 1, 1),
    ("s0_3x3", 64, 56, 64, 3, 1),
    ("s0_1x1b", 64, 56, 256, 1, 1),
    ("s1_3x3", 128, 28, 128, 3, 1),
    ("s1_1x1b", 128, 28, 512, 1, 1),
    ("s2_3x3", 256, 14, 256, 3, 1),
    ("s2_1x1b", 256, 14, 1024, 1, 1),
    ("s3_3x3", 512, 7, 512, 3, 1),
    ("s3_1x1b", 512, 7, 2048, 1, 1),
]


def _timed(step, steps=30, warmup=3):
    from .timing import timed_steps
    dt, _ = timed_steps(step, steps, warmup=warmup,
                        fetch=lambda out: float(np.asarray(out)))
    return dt / steps


def bench_layer(name, C, HW, O, k, stride, batch, peak_flops,
                dtype="bfloat16"):
    """ms/step for fwd conv in three lowerings (training-dominant 3x3/1x1
    shapes; backward is two more convs of the same geometry, so the fwd
    ranking carries).  ``peak_flops``: the attached chip's bf16 peak."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from .ops.nn_ops import _conv2d_im2col

    rng = np.random.RandomState(0)
    dt = jnp.dtype(dtype)
    # local_devices: under jax.distributed, devices()[0] may be a
    # REMOTE device this process cannot device_put to
    from .mesh_utils import local_devices
    dev = local_devices()[0]
    pad = (k - 1) // 2
    x = jax.device_put(rng.normal(0, 1, (batch, C, HW, HW))
                       .astype(np.float32).astype(dt), dev)
    w = jax.device_put(rng.normal(0, 0.1, (O, C, k, k))
                       .astype(np.float32).astype(dt), dev)
    Ho = (HW + 2 * pad - k) // stride + 1
    flops = 2.0 * batch * Ho * Ho * O * C * k * k

    def native(x_, w_):
        return lax.conv_general_dilated(
            x_, w_, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def nhwc(x_, w_):
        return lax.conv_general_dilated(
            x_.transpose(0, 2, 3, 1), w_.transpose(2, 3, 1, 0),
            (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def im2col(x_, w_):
        return _conv2d_im2col(x_, w_, (stride, stride), (pad, pad), (1, 1))

    variants = [("native_ms", native), ("nhwc_ms", nhwc),
                ("im2col_ms", im2col)]
    if k == 3 and stride == 1:
        # pallas implicit-GEMM (in-VMEM im2col, fused BN+relu epilogue):
        # the 3x3/s1 family only (ops/conv_pallas.py)
        from .ops.conv_pallas import conv3x3_bn_relu

        def pallas_conv(x_, w_):
            return conv3x3_bn_relu(x_.transpose(0, 2, 3, 1),
                                   w_.transpose(2, 3, 1, 0))
        variants.append(("pallas_ms", pallas_conv))

    row = {"layer": name, "shape": [batch, C, HW, O, k, stride],
           "gflop": round(flops / 1e9, 2)}
    for variant, fn in variants:
        jitted = jax.jit(lambda a, b, f=fn: jnp.sum(
            f(a, b).astype(jnp.float32)))

        def step(i):
            return jitted(x, w)
        try:
            ms = _timed(step) * 1e3
            row[variant] = round(ms, 4)
            row[variant.replace("_ms", "_mxu_frac")] = round(
                flops / (ms * 1e-3) / peak_flops, 4)
        except Exception as e:
            row[variant] = "error: %s" % e
    times = [v for kk, v in row.items()
             if kk.endswith("_ms") and isinstance(v, float)]
    if times and isinstance(row.get("native_ms"), float):
        row["best_vs_native"] = round(row["native_ms"] / min(times), 3)
    return row


def main():
    from . import costmodel
    from .mesh_utils import local_devices
    # an MXU fraction needs the attached chip's own peak: a device that is
    # not in the table (a CPU included) raises here
    peak_flops = costmodel.device_peaks(
        local_devices()[0].device_kind)["bf16_flops"]
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    rows = []
    for spec in RESNET50_CONVS:
        row = bench_layer(*spec, batch=batch, peak_flops=peak_flops)
        rows.append(row)
        print(json.dumps(row), flush=True)     # stream per row
    # FLOP-weighted aggregates, each over a CONSISTENT layer subset so
    # cross-variant comparison is apples-to-apples: all conv layers for
    # the three general lowerings, and the 3x3/s1 subset (where the
    # pallas kernel applies) for all four
    def agg_over(label, subset, variants):
        agg = {"layer": label}
        for variant in variants:
            vals = [(r["gflop"], r[variant + "_ms"]) for r in subset
                    if isinstance(r.get(variant + "_ms"), float)]
            if len(vals) == len(subset) and vals:
                tot_f = sum(f for f, _ in vals)
                tot_t = sum(t for _, t in vals)
                agg[variant + "_mxu_frac"] = round(
                    tot_f / tot_t / (peak_flops / 1e12), 4)
            else:
                # explicit marker: 'a layer errored for this variant' is
                # a different fact from 'variant not benched'
                agg[variant + "_mxu_frac"] = None
                agg[variant + "_errored_layers"] = [
                    r["layer"] for r in subset
                    if not isinstance(r.get(variant + "_ms"), float)]
        print(json.dumps(agg), flush=True)

    agg_over("AGGREGATE_all_layers", rows, ("native", "nhwc", "im2col"))
    agg_over("AGGREGATE_3x3_s1_only",
             [r for r in rows if "pallas_ms" in r],
             ("native", "nhwc", "im2col", "pallas"))


if __name__ == "__main__":
    main()
