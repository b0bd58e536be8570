"""ResNet (He et al. 2015, arXiv:1512.03385) training program and its
arithmetic.  ``build`` and ``make_batch`` are chip_smoke.py's
``build_resnet`` / ``resnet_batch`` (they ran on the v5e in PR 21); the
model itself is the program under test, ``paddle_tpu.models.resnet``.

``params`` is the configuration's JSON file merged with the cell's traffic
file (``batch`` comes from the traffic).
"""

import math

import numpy as np


def build(params):
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    kind, counts = models.resnet.DEPTH_CFG[params["depth"]]
    # the file states the shape; the model derives it from the depth alone
    if list(counts) != list(params["stage_blocks"]) or \
            (kind == "bottleneck") != (params["bottleneck_expansion"] == 4):
        raise ValueError("configuration %s says blocks %s, the model builds "
                         "%s %s" % (params["name"], params["stage_blocks"],
                                    kind, counts))
    size = params["image_size"]
    img = fluid.layers.data(name="img",
                            shape=[params["image_channels"], size, size],
                            dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    logits = models.resnet.resnet(img, class_dim=params["class_dim"],
                                  depth=params["depth"])
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    opt = fluid.optimizer.MomentumOptimizer(
        learning_rate=params["learning_rate"], momentum=params["momentum"],
        regularization=fluid.regularizer.L2Decay(params["l2_decay"]))
    fluid.contrib.mixed_precision.decorate(
        opt, use_pure_bf16=True).minimize(loss)
    return [img, label], loss


def make_batch(rng, params):
    """One host batch: float32 NCHW noise and uniform labels."""
    n, size = params["batch"], params["image_size"]
    return {
        "img": rng.standard_normal(
            (n, params["image_channels"], size, size), dtype=np.float32),
        "label": rng.integers(0, params["class_dim"], (n, 1),
                              dtype=np.int64),
    }


def first_loss(params):
    """Untrained model, uniform labels: cross-entropy of a uniform guess."""
    return math.log(params["class_dim"])


def expects_in_hlo(params):
    return []


def forward_macs(params):
    """Multiply-accumulates of one image's forward pass, walked from the
    shapes: every convolution counts k*k*C_in*C_out per output pixel, the
    classifier C_in*classes.  Batch norm, ReLU, pooling and the residual
    adds are not matmul work and are left out."""
    def conv(k, c_in, c_out, hw_out):
        return k * k * c_in * c_out * hw_out * hw_out

    hw = params["image_size"] // 2               # 7x7 stem, stride 2
    stem = params["stem_width"]
    macs = conv(7, params["image_channels"], stem, hw)
    hw //= 2                                     # 3x3 max pool, stride 2
    c_in = stem
    exp = params["bottleneck_expansion"]
    for stage, (count, width) in enumerate(zip(params["stage_blocks"],
                                               params["stage_widths"])):
        for block in range(count):
            stride = 2 if block == 0 and stage > 0 else 1
            hw_out = hw // stride
            if exp == 4:
                # 1x1 at the input resolution, 3x3 carrying the stride
                # (v1.5), 1x1 expanding
                macs += conv(1, c_in, width, hw)
                macs += conv(3, width, width, hw_out)
                macs += conv(1, width, width * exp, hw_out)
            else:                                # basic block (tiny sizes)
                macs += conv(3, c_in, width, hw_out)
                macs += conv(3, width, width, hw_out)
            if c_in != width * exp or stride != 1:
                macs += conv(1, c_in, width * exp, hw_out)   # projection
            c_in, hw = width * exp, hw_out
    return macs + c_in * params["class_dim"]


def flops_per_sample(params):
    """Training FLOPs of one image: 2 per multiply-accumulate, backward =
    twice the forward (one product for the input gradient, one for the
    weight gradient), so 3 x 2 x forward_macs.  Nothing is recomputed."""
    return 3 * 2 * forward_macs(params)
