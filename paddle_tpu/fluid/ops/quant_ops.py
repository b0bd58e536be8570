"""Fake-quantization ops for QAT (contrib/slim quantization).

Reference analogues: ``paddle/fluid/operators/fake_quantize_op.cc`` —
FakeQuantizeDequantizeAbsMax, FakeQuantizeDequantizeMovingAverageAbsMax,
FakeChannelWiseQuantizeDequantize.  Forward simulates int-b quantization
(round(x/scale * qmax) clipped, then dequantized); backward is the
straight-through estimator, expressed structurally as
``x + stop_gradient(qdq(x) - x)`` so the generic vjp replay yields the
identity gradient with no custom grad kernel (the reference's grad kernel
is also a pass-through copy).
"""

import numpy as np
import jax.numpy as jnp
from jax import lax

from ..registry import register_op


def _qdq(x, scale, bits):
    qmax = float(2 ** (bits - 1) - 1)
    return _quant(x, scale, bits) * jnp.maximum(scale, 1e-8) / qmax


def _ste(x, y):
    """y with identity gradient w.r.t. x."""
    return x + lax.stop_gradient(y - x)


@register_op("fake_quantize_dequantize_abs_max")
def _fake_qdq_abs_max(ctx, op):
    x = ctx.i("X")
    bits = ctx.attr("bit_length", 8)
    scale = jnp.max(jnp.abs(x))
    ctx.set("Out", _ste(x, _qdq(x, scale, bits)))
    ctx.set("OutScale", scale.reshape((1,)))


@register_op("fake_channel_wise_quantize_dequantize_abs_max")
def _fake_qdq_channel(ctx, op):
    x = ctx.i("X")                        # weights, channel on axis 0
    bits = ctx.attr("bit_length", 8)
    axes = tuple(range(1, x.ndim))
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    out = _ste(x, _qdq(x, scale, bits))
    ctx.set("Out", out)
    ctx.set("OutScale", scale.reshape((-1,)))


def _quant(x, scale, bits):
    """Quantize only (values in [-qmax, qmax], still float dtype) —
    the reference's ClipAndFakeQuantFunctor."""
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(scale, 1e-8)
    return jnp.clip(jnp.round(x / scale * qmax), -qmax, qmax)


@register_op("fake_quantize_abs_max")
def _fake_quantize_abs_max(ctx, op):
    """Quantize-only variant (fake_quantize_op.h FakeQuantizeAbsMaxKernel):
    Out holds the integer levels (float dtype), OutScale = max|x|."""
    x = ctx.i("X")
    bits = ctx.attr("bit_length", 8)
    scale = jnp.max(jnp.abs(x))
    ctx.set("Out", _ste(x, _quant(x, scale, bits)))
    ctx.set("OutScale", scale.reshape((1,)))


@register_op("fake_channel_wise_quantize_abs_max")
def _fake_channel_wise_quantize_abs_max(ctx, op):
    x = ctx.i("X")                        # weights, channel on axis 0
    bits = ctx.attr("bit_length", 8)
    axes = tuple(range(1, x.ndim))
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    ctx.set("Out", _ste(x, _quant(x, scale, bits)))
    ctx.set("OutScale", scale.reshape((-1,)))


@register_op("fake_quantize_range_abs_max",
             nondiff_inputs=("InScale", "Iter", "OutScales"))
def _fake_quantize_range_abs_max(ctx, op):
    """Windowed range scale (fake_quantize_op.cc FindRangeAbsMaxFunctor):
    a ring buffer of the last ``window_size`` batch abs-maxes; the working
    scale is max(last_scale, cur) and falls back to the window max when the
    evicted entry was the maximum."""
    x = ctx.i("X")
    bits = ctx.attr("bit_length", 8)
    is_test = ctx.attr("is_test", False) or ctx.state.is_test
    last = ctx.i("InScale").reshape(())
    if is_test:
        ctx.set("Out", _ste(x, _quant(x, last, bits)))
        ctx.set("OutScale", last.reshape((1,)))
        return
    window = int(ctx.attr("window_size", 10000))
    it = ctx.i_opt("Iter")
    it = jnp.zeros((), jnp.int32) if it is None \
        else it.reshape(()).astype(jnp.int32)
    arr = ctx.i_opt("OutScales")
    if arr is None:
        arr = jnp.zeros((window,), x.dtype)
    idx = jnp.mod(it, window)
    cur = lax.stop_gradient(jnp.max(jnp.abs(x)))
    removed = arr[idx]
    arr = arr.at[idx].set(cur)
    # valid prefix of the ring buffer (reference: size = min(it, window),
    # where it has already been incremented past the store)
    size = jnp.minimum(it + 1, window)
    win_max = jnp.max(jnp.where(jnp.arange(window) < size, arr, 0.0))
    scale = jnp.where(last < cur, cur,
                      jnp.where(jnp.abs(removed - last) < 1e-6, win_max, last))
    ctx.set("Out", _ste(x, _quant(x, scale, bits)))
    ctx.set("OutScale", scale.reshape((1,)))
    ctx.set("OutScales", arr)
    ctx.set("Iter", it + 1)


@register_op("fake_quantize_moving_average_abs_max",
             nondiff_inputs=("InScale", "InAccum", "InState"))
def _fake_quantize_moving_average_abs_max(ctx, op):
    """Quantize-only moving-average scale (FindMovingAverageAbsMaxFunctor):
    state = rate*state + 1; accum = rate*accum + cur; scale = accum/state."""
    x = ctx.i("X")
    bits = ctx.attr("bit_length", 8)
    rate = ctx.attr("moving_rate", 0.9)
    is_test = ctx.attr("is_test", False) or ctx.state.is_test
    in_scale = ctx.i("InScale").reshape(())
    if is_test:
        ctx.set("Out", _ste(x, _quant(x, in_scale, bits)))
        ctx.set("OutScale", in_scale.reshape((1,)))
        return
    accum = ctx.i_opt("InAccum")
    state = ctx.i_opt("InState")
    accum = jnp.zeros(()) if accum is None else accum.reshape(())
    state = jnp.zeros(()) if state is None else state.reshape(())
    cur = lax.stop_gradient(jnp.max(jnp.abs(x)))
    state = rate * state + 1.0
    accum = rate * accum + cur
    scale = accum / state
    ctx.set("OutState", state.reshape((1,)))
    ctx.set("OutAccum", accum.reshape((1,)))
    ctx.set("OutScale", scale.reshape((1,)))
    ctx.set("Out", _ste(x, _quant(x, scale, bits)))


@register_op("fake_dequantize_max_abs", nondiff_inputs=("Scale",))
def _fake_dequantize_max_abs(ctx, op):
    """Out = X * Scale / max_range (fake_dequantize_op.h)."""
    x = ctx.i("X")
    scale = ctx.i("Scale").reshape(())
    max_range = ctx.attr("max_range", 127.0)
    ctx.set("Out", x * scale / max_range)


@register_op("fake_channel_wise_dequantize_max_abs",
             nondiff_inputs=("Scales",))
def _fake_channel_wise_dequantize_max_abs(ctx, op):
    """Per-channel dequantize (fake_dequantize_op.cc ChannelDequantize):
    one scale tensor → conv weights, channel on axis 0; two → FC
    activations, per-column weight scale (axis 1) times activation scale."""
    x = ctx.i("X")
    scales = ctx.input("Scales")
    bits = ctx.attr("quant_bits", [8])
    if len(scales) == 1:
        max_range = float(2 ** (bits[0] - 1) - 1)
        s = scales[0].reshape((-1,) + (1,) * (x.ndim - 1))
        ctx.set("Out", x * s / max_range)
    else:
        max_range = float((2 ** (bits[0] - 1) - 1) * (2 ** (bits[1] - 1) - 1))
        s0 = scales[0].reshape((1, -1) + (1,) * (x.ndim - 2))
        s1 = scales[1].reshape(())
        ctx.set("Out", x * s0 * s1 / max_range)


@register_op("fake_quantize_dequantize_moving_average_abs_max",
             nondiff_inputs=("InScale",))
def _fake_qdq_moving(ctx, op):
    x = ctx.i("X")
    in_scale = ctx.i("InScale").reshape(())
    bits = ctx.attr("bit_length", 8)
    momentum = ctx.attr("moving_rate", 0.9)
    is_test = ctx.attr("is_test", False) or ctx.state.is_test
    if is_test:
        scale = in_scale
        ctx.set("OutScale", in_scale.reshape((1,)))
    else:
        cur = lax.stop_gradient(jnp.max(jnp.abs(x)))
        # seed from the first batch when the state is still zero
        scale = jnp.where(in_scale > 0,
                          momentum * in_scale + (1 - momentum) * cur, cur)
        ctx.set("OutScale", scale.reshape((1,)))
    ctx.set("Out", _ste(x, _qdq(x, scale, bits)))


@register_op("quantized_matmul", nondiff_inputs=("Y",), stop_gradient=True)
def _quantized_matmul(ctx, op):
    """True int8 execution: X is quantized on the fly with the static
    activation scale learned during QAT, the weight arrives as an int8
    tensor, and the dot runs int8 x int8 -> int32 (the v5e int8 MXU path,
    2x the bf16 rate) before one fp32 rescale.

    No reference analogue at 1.5 (its slim int8 deployment needed
    TensorRT subgraphs); this is the TPU-native equivalent of
    inference/analysis int8 engines."""
    x = ctx.i("X")
    w8 = ctx.i("Y")                       # int8 [K, N]
    x_scale = float(ctx.attr("x_scale"))
    w_scale = float(ctx.attr("w_scale"))
    # mul semantics: flatten x to 2-D at x_num_col_dims (fc passes 4-D
    # pooled activations straight in)
    ncd = int(ctx.attr("x_num_col_dims", 1))
    lead = x.shape[:ncd]
    x2 = x.reshape((int(np.prod(lead)), -1)).astype(jnp.float32)
    xq = _quant(x2, jnp.float32(x_scale), 8).astype(jnp.int8)
    acc = lax.dot_general(
        xq, w8, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * ((x_scale / 127.0) * w_scale)
    ctx.set("Out", out.reshape(lead + (w8.shape[1],)))


@register_op("quantized_conv2d", nondiff_inputs=("Filter",),
             stop_gradient=True)
def _quantized_conv2d(ctx, op):
    """int8 convolution: activation quantized with the QAT static scale,
    filter arrives int8 with PER-OUTPUT-CHANNEL scales (they factor out
    of the contraction, unlike per-input-channel), int8 x int8 -> int32
    on the MXU, one fp32 rescale per channel."""
    x = ctx.i("Input")
    w8 = ctx.i("Filter")                   # int8 [O, I/g, kh, kw]
    x_scale = float(ctx.attr("x_scale"))
    w_scale = jnp.asarray(ctx.attr("w_scale"), jnp.float32)  # [O]
    strides = tuple(ctx.attr("strides", [1, 1]))
    pads = tuple(ctx.attr("paddings", [0, 0]))
    dilations = tuple(ctx.attr("dilations", [1, 1]))
    groups = int(ctx.attr("groups", 1) or 1)
    xq = _quant(x.astype(jnp.float32), jnp.float32(x_scale),
                8).astype(jnp.int8)
    acc = lax.conv_general_dilated(
        xq, w8, strides, [(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * (x_scale / 127.0) \
        * w_scale[None, :, None, None]
    ctx.set("Output", out)
