"""NN op lowerings: conv / pool / norms / dropout / classification losses.

Reference analogues: ``operators/conv_op.*`` (+cuDNN variants — here the MXU
path is one ``lax.conv_general_dilated``), ``operators/pool_op``,
``operators/batch_norm_op``, ``operators/layer_norm_op``,
``operators/dropout_op``, ``operators/softmax_with_cross_entropy_op``,
``operators/cross_entropy_op``, ``operators/metrics/accuracy_op``.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..registry import register_op
from ..flags import matmul_precision
from ..lowering import amp_operands


def _prec(x):
    # Backend-default precision: one bf16 MXU pass for fp32 operands — the
    # TPU-native choice.  FLAGS_matmul_precision=float32 opts into exact
    # fp32 (multi-pass, slow on MXU); see flags.py.
    return matmul_precision() if x.dtype == jnp.float32 else None


@register_op("conv2d")
def _conv2d(ctx, op):
    x = ctx.i("Input")          # NCHW
    w = ctx.i("Filter")         # OIHW (out, in/groups, kh, kw)
    strides = tuple(ctx.attr("strides", [1, 1]))
    pads = tuple(ctx.attr("paddings", [0, 0]))
    dilations = tuple(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1) or 1
    x, w, acc = amp_operands(ctx.state, x, w.astype(x.dtype))
    # XLA's NCHW convolution is the one lowering: channels-last, im2col
    # and a Pallas implicit-GEMM forward lost their chip run (PERF.md §6,
    # PR 30)
    out = lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
        precision=_prec(x))
    # AMP: conv runs fully in bf16 (the MXU accumulates fp32 internally and
    # rounds once at output); cast back so activations stay fp32.  Unlike
    # matmul, lax.conv's transpose rule rejects mixed-dtype operands, so
    # preferred_element_type can't express this here.
    if acc is not None:
        out = out.astype(acc)
    ctx.set("Output", out)


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx, op):
    # Same as conv2d with groups == in_channels (reference registers it as a
    # distinct op with a dedicated CUDA kernel; XLA needs no special case).
    _conv2d(ctx, op)


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx, op):
    x = ctx.i("Input")          # NCHW
    w = ctx.i("Filter")         # (in, out/groups, kh, kw)
    strides = tuple(ctx.attr("strides", [1, 1]))
    pads = tuple(ctx.attr("paddings", [0, 0]))
    dilations = tuple(ctx.attr("dilations", [1, 1]))
    groups = ctx.attr("groups", 1) or 1
    cin, cog, kh, kw = w.shape
    if groups == 1:
        wt = jnp.flip(w, axis=(-2, -1)).swapaxes(0, 1)          # OIHW
    else:
        # group i maps input slice i (cin/g ch) to output slice i (cog ch):
        # build the equivalent grouped-forward OIHW kernel
        # (out_total, in/g, kh, kw) for feature_group_count=groups
        wt = jnp.flip(w, axis=(-2, -1)) \
            .reshape(groups, cin // groups, cog, kh, kw) \
            .swapaxes(1, 2) \
            .reshape(groups * cog, cin // groups, kh, kw)
    wt = wt.astype(x.dtype)
    x, wt, acc = amp_operands(ctx.state, x, wt)
    pad_h = dilations[0] * (kh - 1) - pads[0]
    pad_w = dilations[1] * (kw - 1) - pads[1]
    out = lax.conv_general_dilated(
        x, wt, window_strides=(1, 1),
        padding=[(pad_h, pad_h), (pad_w, pad_w)],
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
        precision=_prec(x))
    if acc is not None:
        out = out.astype(acc)
    ctx.set("Output", out)


# depthwise transpose conv (conv_transpose_op.cc registers it as a distinct
# type with groups == in_channels); the grouped lowering above covers it
register_op("depthwise_conv2d_transpose")(_conv2d_transpose)


@register_op("pool2d")
def _pool2d(ctx, op):
    x = ctx.i("X")              # NCHW
    ptype = ctx.attr("pooling_type", "max")
    ksize = tuple(ctx.attr("ksize", [2, 2]))
    strides = tuple(ctx.attr("strides", [1, 1]))
    pads = tuple(ctx.attr("paddings", [0, 0]))
    if ctx.attr("global_pooling", False):
        ksize = (x.shape[2], x.shape[3])
        strides = (1, 1)
        pads = (0, 0)
    if ctx.attr("ceil_mode", False):
        extra_h = -(x.shape[2] + 2 * pads[0] - ksize[0]) % strides[0]
        extra_w = -(x.shape[3] + 2 * pads[1] - ksize[1]) % strides[1]
    else:
        extra_h = extra_w = 0
    window = (1, 1) + ksize
    wstrides = (1, 1) + strides
    padding = ((0, 0), (0, 0),
               (pads[0], pads[0] + extra_h), (pads[1], pads[1] + extra_w))
    if ptype == "max":
        init = -np.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            np.iinfo(np.dtype(x.dtype)).min
        out = lax.reduce_window(x, x.dtype.type(init), lax.max,
                                window, wstrides, padding)
    else:
        ssum = lax.reduce_window(x, x.dtype.type(0), lax.add,
                                 window, wstrides, padding)
        if ctx.attr("exclusive", True) and (pads[0] or pads[1] or extra_h or
                                            extra_w):
            ones = jnp.ones(x.shape, x.dtype)
            counts = lax.reduce_window(ones, x.dtype.type(0), lax.add,
                                       window, wstrides, padding)
            out = ssum / counts
        else:
            out = ssum / np.prod(ksize).astype(np.float32)
    ctx.set("Out", out)


@register_op("batch_norm", nondiff_inputs=("Mean", "Variance"))
def _batch_norm(ctx, op):
    """BN with in-place running-stat update (operators/batch_norm_op.cc):
    MeanOut/VarianceOut share the Mean/Variance variables."""
    x = ctx.i("X")
    scale = ctx.i("Scale")
    bias = ctx.i("Bias")
    mean = ctx.i("Mean")
    var = ctx.i("Variance")
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    is_test = ctx.attr("is_test", False) or ctx.state.is_test
    use_global = ctx.attr("use_global_stats", False) or is_test
    if ctx.attr("data_layout", "NCHW") == "NCHW" and x.ndim == 4:
        axes = (0, 2, 3)
        bshape = (1, -1, 1, 1)
    else:
        axes = tuple(range(x.ndim - 1))
        bshape = (1,) * (x.ndim - 1) + (-1,)

    cdt = jnp.float32
    if use_global:
        use_mean, use_var = mean.astype(cdt), var.astype(cdt)
        ctx.set("MeanOut", mean)
        ctx.set("VarianceOut", var)
    else:
        # single-pass statistics: E[x] and E[x^2] reduce in the SAME read
        # of x (XLA fuses both into one loop), where jnp.var's two-pass
        # mean((x-mean)^2) costs an extra full pass over the activation.
        # Accumulation is fp32 (cancellation-safe the same way cuDNN/TPU
        # fused BN does it); clamp for safety.
        xm = x.astype(cdt)
        use_mean = jnp.mean(xm, axis=axes)
        use_var = jnp.maximum(
            jnp.mean(jnp.square(xm), axis=axes) - jnp.square(use_mean), 0.0)
        use_mean_s = lax.stop_gradient(use_mean)
        use_var_s = lax.stop_gradient(use_var)
        ctx.set("MeanOut", (mean.astype(cdt) * momentum
                            + use_mean_s * (1 - momentum)).astype(mean.dtype))
        ctx.set("VarianceOut", (var.astype(cdt) * momentum
                                + use_var_s * (1 - momentum)).astype(var.dtype))
    inv = lax.rsqrt(use_var + eps)
    # fold the normalize into one per-channel affine (y = x*a + b): fewer
    # broadcast ops in the fusion than center-scale-shift, same math
    a = scale.astype(cdt) * inv
    b = bias.astype(cdt) - use_mean * a
    y = x.astype(cdt) * a.reshape(bshape) + b.reshape(bshape)
    ctx.set("Y", y.astype(x.dtype))
    ctx.set("SavedMean", use_mean)
    ctx.set("SavedVariance", inv)


@register_op("layer_norm")
def _layer_norm(ctx, op):
    x = ctx.i("X")
    scale = ctx.i_opt("Scale")
    bias = ctx.i_opt("Bias")
    eps = ctx.attr("epsilon", 1e-5)
    bna = ctx.attr("begin_norm_axis", 1)
    axes = tuple(range(bna, x.ndim))
    cdt = jnp.float32
    xm = x.astype(cdt)
    mean = jnp.mean(xm, axis=axes, keepdims=True)
    var = jnp.var(xm, axis=axes, keepdims=True)
    y = (xm - mean) * lax.rsqrt(var + eps)
    norm_shape = x.shape[bna:]
    if scale is not None:
        y = y * scale.astype(cdt).reshape(norm_shape)
    if bias is not None:
        y = y + bias.astype(cdt).reshape(norm_shape)
    ctx.set("Y", y.astype(x.dtype))
    ctx.set("Mean", mean.reshape(x.shape[:bna]))
    ctx.set("Variance", var.reshape(x.shape[:bna]))


@register_op("dropout")
def _dropout(ctx, op):
    x = ctx.i("X")
    p = ctx.attr("dropout_prob", 0.5)
    is_test = ctx.attr("is_test", False) or ctx.state.is_test
    impl = ctx.attr("dropout_implementation", "downgrade_in_infer")
    if is_test:
        if impl == "upscale_in_train":
            out = x
        else:
            out = x * jnp.asarray(1.0 - p, x.dtype)
        ctx.set("Out", out)
        ctx.set("Mask", jnp.ones_like(x, dtype=jnp.uint8))
        return
    if ctx.attr("fix_seed", False):
        key = jax.random.PRNGKey(ctx.attr("seed", 0))
    else:
        key = ctx.rng()
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / jnp.asarray(max(1.0 - p, 1e-8), x.dtype),
                        jnp.zeros_like(x))
    else:
        out = jnp.where(keep, x, jnp.zeros_like(x))
    ctx.set("Out", out)
    ctx.set("Mask", keep.astype(jnp.uint8))


@register_op("softmax_with_cross_entropy", nondiff_inputs=("Label",))
def _softmax_with_cross_entropy(ctx, op):
    logits = ctx.i("Logits")
    label = ctx.i("Label")
    soft_label = ctx.attr("soft_label", False)
    ignore_index = ctx.attr("ignore_index", -100)
    cdt = jnp.float32
    lm = logits.astype(cdt)
    log_sm = jax.nn.log_softmax(lm, axis=-1)
    ctx.set("Softmax", jnp.exp(log_sm).astype(logits.dtype))
    if soft_label:
        loss = -jnp.sum(label.astype(cdt) * log_sm, axis=-1, keepdims=True)
    else:
        lab = label
        if lab.ndim == logits.ndim and lab.shape[-1] == 1:
            lab = jnp.squeeze(lab, -1)
        lab = lab.astype(jnp.int32)
        picked = jnp.take_along_axis(log_sm, jnp.maximum(lab, 0)[..., None],
                                     axis=-1)
        loss = -picked
        if ignore_index >= 0:
            loss = jnp.where((lab == ignore_index)[..., None],
                             jnp.zeros_like(loss), loss)
    ctx.set("Loss", loss.astype(logits.dtype))


@register_op("cross_entropy", nondiff_inputs=("Label",))
def _cross_entropy(ctx, op):
    x = ctx.i("X")              # probabilities
    label = ctx.i("Label")
    if ctx.attr("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, 1e-20)),
                        axis=-1, keepdims=True)
    else:
        lab = label
        if lab.ndim == x.ndim and lab.shape[-1] == 1:
            lab = jnp.squeeze(lab, -1)
        picked = jnp.take_along_axis(x, lab.astype(jnp.int32)[..., None],
                                     axis=-1)
        loss = -jnp.log(jnp.maximum(picked, 1e-20))
    ctx.set("Y", loss)


@register_op("sigmoid_cross_entropy_with_logits", nondiff_inputs=("Label",))
def _sigmoid_ce(ctx, op):
    x = ctx.i("X")
    label = ctx.i("Label").astype(x.dtype)
    # max(x,0) - x*z + log(1 + exp(-|x|)) — numerically stable form
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore_index = ctx.attr("ignore_index", -100)
    if ignore_index != -100:
        loss = jnp.where(label == ignore_index, jnp.zeros_like(loss), loss)
    if ctx.attr("normalize", False):
        n = jnp.sum(jnp.where(label != ignore_index, 1.0, 0.0))
        loss = loss / jnp.maximum(n, 1.0)
    ctx.set("Out", loss)


@register_op("square_error_cost")
def _square_error_cost(ctx, op):
    x = ctx.i("X")
    y = ctx.i("Y")
    ctx.set("Out", jnp.square(x - y))


@register_op("huber_loss")
def _huber_loss(ctx, op):
    x = ctx.i("X")
    y = ctx.i("Y")
    delta = ctx.attr("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r,
                     delta * (ar - 0.5 * delta))
    ctx.set("Residual", r)
    ctx.set("Out", loss)


@register_op("accuracy", stop_gradient=True)
def _accuracy(ctx, op):
    indices = ctx.i("Indices")
    label = ctx.i("Label")
    if label.ndim == 1:
        label = label[:, None]
    correct = jnp.any(indices == label.astype(indices.dtype), axis=-1)
    num_correct = jnp.sum(correct.astype(jnp.float32))
    total = jnp.asarray(correct.shape[0], jnp.float32)
    ctx.set("Accuracy", (num_correct / total).reshape(()))
    ctx.set("Correct", num_correct.astype(jnp.int32).reshape((1,)))
    ctx.set("Total", jnp.asarray([correct.shape[0]], jnp.int32))


@register_op("auc", stop_gradient=True)
def _auc(ctx, op):
    """Streaming AUC (operators/metrics/auc_op): updates histogram stat
    buffers in place and emits the trapezoid AUC over thresholds."""
    preds = ctx.i("Predict")
    label = ctx.i("Label")
    stat_pos = ctx.i("StatPos")
    stat_neg = ctx.i("StatNeg")
    num_thresholds = ctx.attr("num_thresholds", 4095)
    pos_score = preds[:, 1] if preds.ndim == 2 and preds.shape[1] == 2 \
        else preds.reshape((-1,))
    lab = label.reshape((-1,)).astype(jnp.float32)
    idx = jnp.clip((pos_score * num_thresholds).astype(jnp.int32), 0,
                   num_thresholds)
    pos_upd = jnp.zeros_like(stat_pos).at[idx].add(lab.astype(stat_pos.dtype))
    neg_upd = jnp.zeros_like(stat_neg).at[idx].add(
        (1.0 - lab).astype(stat_neg.dtype))
    new_pos = stat_pos + pos_upd
    new_neg = stat_neg + neg_upd
    # cumulative from the top threshold down
    tp = jnp.cumsum(new_pos[::-1])[::-1].astype(jnp.float32)
    fp = jnp.cumsum(new_neg[::-1])[::-1].astype(jnp.float32)
    tot_pos = tp[0]
    tot_neg = fp[0]
    # trapezoid over consecutive thresholds
    auc = jnp.sum((fp[:-1] - fp[1:]) * (tp[:-1] + tp[1:]) / 2.0)
    denom = tot_pos * tot_neg
    auc = jnp.where(denom > 0, auc / jnp.maximum(denom, 1.0), 0.0)
    ctx.set("AUC", auc.astype(jnp.float32).reshape(()))
    ctx.set("StatPosOut", new_pos)
    ctx.set("StatNegOut", new_neg)


@register_op("cos_sim")
def _cos_sim(ctx, op):
    """Row-wise cosine similarity (operators/cos_sim_op.cc); Y may be a
    single row [1, D] broadcast against X [B, D]."""
    x = ctx.i("X")
    y = ctx.i("Y")
    xn = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True))
    out = jnp.sum(x * y, axis=-1, keepdims=True) / \
        jnp.maximum(xn * yn, 1e-12)
    ctx.set("Out", out)
    ctx.set("XNorm", xn)
    ctx.set("YNorm", yn)


@register_op("nce", nondiff_inputs=("Label", "SampleWeight",
                                    "CustomDistProbs"))
def _nce(ctx, op):
    """Noise-contrastive estimation (operators/nce_op.cc/.h).

    Per example: draws ``num_neg_samples`` noise classes, then
    cost = softplus(-(logit_true - log(k*q_true)))
         + sum_s softplus(logit_s - log(k*q_s))
    — algebraically identical to the reference's exp-space
    ``o/(o + k q)`` forward, computed stably in log space.  Sampling uses
    the op's deterministic PRNG key, so the vjp replay of the grad op
    redraws the identical samples (the reference re-reads them from the
    saved SampleLogits buffer instead).
    """
    x = ctx.i("Input")                    # [B, D]
    label = ctx.i("Label").reshape((-1,)).astype(jnp.int32)   # [B]
    w = ctx.i("Weight")                   # [C, D]
    bias = ctx.i_opt("Bias")              # [C] or [C,1]
    num_classes = ctx.attr("num_total_classes")
    k = max(int(ctx.attr("num_neg_samples", 10)), 1)
    sampler = ctx.attr("sampler", 0)      # 0 uniform, 1 log-uniform, 2 custom
    B = x.shape[0]

    key = ctx.rng()
    if sampler == 1:
        # log-uniform (Zipf): P(c) = log(c+2)/(c+1) / log(C+1)
        u = jax.random.uniform(key, (B, k))
        samples = jnp.clip(
            (jnp.exp(u * jnp.log(float(num_classes + 1))) - 1.0)
            .astype(jnp.int32), 0, num_classes - 1)
        def _q(c):
            c = c.astype(jnp.float32)
            return (jnp.log((c + 2.0) / (c + 1.0))
                    / jnp.log(float(num_classes + 1)))
    elif sampler == 2:
        probs = ctx.i("CustomDistProbs").reshape((-1,))
        samples = jax.random.categorical(
            key, jnp.log(jnp.maximum(probs, 1e-30))[None, :], shape=(B, k))
        samples = samples.astype(jnp.int32)
        def _q(c):
            return probs[c].astype(jnp.float32)
    else:
        samples = jax.random.randint(key, (B, k), 0, num_classes,
                                     dtype=jnp.int32)
        def _q(c):
            return jnp.full(c.shape, 1.0 / num_classes, jnp.float32)

    def _logit(cls):                      # cls [...,] int → logits
        lo = jnp.sum(jnp.take(w, cls, axis=0) *
                     x[:, None, :] if cls.ndim == 2 else
                     jnp.take(w, cls, axis=0) * x, axis=-1)
        if bias is not None:
            lo = lo + jnp.take(bias.reshape((-1,)), cls)
        return lo

    logit_true = _logit(label)            # [B]
    logit_neg = _logit(samples)           # [B, k]
    log_kq_true = jnp.log(k * _q(label))
    log_kq_neg = jnp.log(k * _q(samples))
    cost = jax.nn.softplus(-(logit_true - log_kq_true)) + \
        jnp.sum(jax.nn.softplus(logit_neg - log_kq_neg), axis=-1)
    sw = ctx.i_opt("SampleWeight")
    if sw is not None:
        cost = cost * sw.reshape((-1,))
    ctx.set("Cost", cost[:, None])
    ctx.set("SampleLogits", logit_neg)
    ctx.set("SampleLabels", samples.astype(jnp.int64))


@register_op("hierarchical_sigmoid", nondiff_inputs=("Label", "PathTable",
                                                     "PathCode"))
def _hierarchical_sigmoid(ctx, op):
    """Hierarchical sigmoid (operators/hierarchical_sigmoid_op.cc).

    Default tree: the reference's SimpleCode over a complete binary tree —
    for class l, code c = l + C; internal node at bit j is (c >> (j+1)) - 1
    and the branch bit is (c >> j) & 1, for j < floor(log2(c)) bits
    (``operators/math/matrix_bit_code.h``).  Cost per example is the sum of
    sigmoid cross-entropies along the path, vectorised over a static
    max-depth of ceil(log2(C)) with a validity mask (no per-example loops).
    A custom tree arrives as PathTable/PathCode gather tables.
    """
    x = ctx.i("X")                        # [B, D]
    label = ctx.i("Label").reshape((-1,)).astype(jnp.int32)
    w = ctx.i("W")                        # [num_nodes, D]
    bias = ctx.i_opt("Bias")
    path_table = ctx.i_opt("PathTable")   # [B, L] node ids, -1 pad
    path_code = ctx.i_opt("PathCode")     # [B, L] branch bits

    if path_table is not None:
        nodes = path_table.astype(jnp.int32)
        bits = path_code.astype(jnp.float32)
        valid = nodes >= 0
        nodes = jnp.maximum(nodes, 0)
    else:
        C = int(ctx.attr("num_classes"))
        L = max(int(C - 1).bit_length(), 1)
        c = label + C                     # [B]
        j = jnp.arange(L, dtype=jnp.int32)[None, :]
        # bits above the leading 1 are invalid: bit j is on the path iff
        # the node index (c >> (j+1)) - 1 exists, i.e. c >> (j+1) > 0
        # (integer-exact; float log2 misrounds near powers of two)
        valid = (c[:, None] >> (j + 1)) > 0   # [B, L]
        nodes = jnp.clip((c[:, None] >> (j + 1)) - 1, 0, w.shape[0] - 1)
        bits = ((c[:, None] >> j) & 1).astype(jnp.float32)

    z = jnp.sum(jnp.take(w, nodes, axis=0) * x[:, None, :], axis=-1)
    if bias is not None:
        z = z + jnp.take(bias.reshape((-1,)), nodes)
    # BCE with logits against the branch bit, clipped like the reference
    z = jnp.clip(z, -40.0, 40.0)
    ce = jax.nn.softplus(z) - bits * z
    cost = jnp.where(valid, ce, 0.0).sum(axis=-1)
    ctx.set("Out", cost[:, None])
    ctx.set("PreOut", z)


@register_op("sync_batch_norm", nondiff_inputs=("Mean", "Variance"))
def _sync_batch_norm(ctx, op):
    """Cross-replica BN (operators/sync_batch_norm_op.cu): moments are
    computed over the GLOBAL batch by psum-ing per-device sum / sum-of-
    squares / counts over the dp mesh axis.  Outside shard_map (single
    device, or the GSPMD CompiledProgram path where XLA already reduces
    over the full logical batch) it degrades to plain batch_norm.
    Gradients replay through lax.psum, which differentiates to the same
    cross-replica reduction the reference's hand-written grad kernel does.
    """
    from .collective_ops import _axis_for_ring
    x = ctx.i("X")
    scale = ctx.i("Scale")
    bias = ctx.i("Bias")
    mean = ctx.i("Mean")
    var = ctx.i("Variance")
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    is_test = ctx.attr("is_test", False) or ctx.state.is_test
    use_global = ctx.attr("use_global_stats", False) or is_test
    if ctx.attr("data_layout", "NCHW") == "NCHW" and x.ndim == 4:
        axes = (0, 2, 3)
        bshape = (1, -1, 1, 1)
    else:
        axes = tuple(range(x.ndim - 1))
        bshape = (1,) * (x.ndim - 1) + (-1,)

    cdt = jnp.float32
    if use_global:
        use_mean, use_var = mean.astype(cdt), var.astype(cdt)
        ctx.set("MeanOut", mean)
        ctx.set("VarianceOut", var)
    else:
        xm = x.astype(cdt)
        axis = _axis_for_ring(ctx)
        n_local = 1
        for a in axes:
            n_local *= x.shape[a]
        sum_x = jnp.sum(xm, axis=axes)
        sum_x2 = jnp.sum(xm * xm, axis=axes)
        n = jnp.asarray(n_local, cdt)
        if axis is not None:
            sum_x = lax.psum(sum_x, axis)
            sum_x2 = lax.psum(sum_x2, axis)
            n = lax.psum(n, axis)
        use_mean = sum_x / n
        use_var = jnp.maximum(sum_x2 / n - use_mean * use_mean, 0.0)
        use_mean_s = lax.stop_gradient(use_mean)
        use_var_s = lax.stop_gradient(use_var)
        ctx.set("MeanOut", (mean.astype(cdt) * momentum
                            + use_mean_s * (1 - momentum)).astype(mean.dtype))
        ctx.set("VarianceOut", (var.astype(cdt) * momentum
                                + use_var_s * (1 - momentum)).astype(var.dtype))
    inv = lax.rsqrt(use_var + eps)
    y = ((x.astype(cdt) - use_mean.reshape(bshape)) * inv.reshape(bshape)
         * scale.astype(cdt).reshape(bshape) + bias.astype(cdt).reshape(bshape))
    ctx.set("Y", y.astype(x.dtype))
    ctx.set("SavedMean", use_mean)
    ctx.set("SavedVariance", inv)
