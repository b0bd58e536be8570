"""Pallas implicit-GEMM conv kernel — the ROADMAP S2 conv-ceiling experiment.

Why this shape of kernel: the hypothesis (builder-measured in round 3, not
reproduced since) is that ResNet-50's step is bound by XLA's conv
efficiency at ResNet's channel counts — a native conv contracts over C
(64..512), underfilling the 128-wide MXU contraction at the early
layers, while the HBM-materialized im2col alternative
(FLAGS_conv_im2col) pays kh*kw x activation bandwidth.  This kernel does
the third thing: build the im2col patch matrix **in VMEM** per row-block
(9 slices, one concat) and run a single [bh*W, 9C] x [9C, O] MXU matmul
— full contraction depth, zero extra HBM patch traffic.  BN scale/shift
+ relu fuse into the epilogue (the conv+BN+relu triple is ResNet's
dominant fusion).

Scope: 3x3, stride 1, dilation 1, groups 1, NHWC — the layer family that
dominates ResNet FLOPs (s0..s3 3x3 layers); everything else keeps the
XLA path.  The whole padded image rides in VMEM per grid cell (ResNet's
3x3 layers are at most 58*58*64*2B ~ 430 KB, well under the ~16 MB VMEM
budget); the row-block loop slices halo windows in-kernel.  Forward
kernel; backward falls to XLA convs (inference + the forward half of
training benefit).

A/B harness: fluid/conv_bench.py variant "pallas"; integration knob
FLAGS_conv_pallas stays off until the chip proves it pays.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_ops import _pallas_call


def _conv3x3_kernel(x_ref, w_ref, scale_ref, shift_ref, o_ref, *,
                    bh, W, C, O, relu):
    """One (image, row-block) grid cell.

    x_ref: [1, H+2, W+2, C] the whole padded image (VMEM-resident)
    w_ref: [9*C, O] patch-major weight matrix
    o_ref: [1, bh, W, O] this row-block's output
    """
    i = pl.program_id(1)
    rows = x_ref[0, pl.dslice(i * bh, bh + 2), :, :]     # [bh+2, W+2, C]
    cols = []
    for dy in range(3):
        for dx in range(3):
            blk = rows[dy:dy + bh, dx:dx + W, :]         # [bh, W, C]
            cols.append(blk.reshape(bh * W, C))
    patches = jnp.concatenate(cols, axis=1)              # [bh*W, 9C]
    acc = jnp.dot(patches, w_ref[...],
                  preferred_element_type=jnp.float32)    # [bh*W, O]
    acc = acc * scale_ref[...].astype(jnp.float32) \
        + shift_ref[...].astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    o_ref[0] = acc.reshape(bh, W, O).astype(o_ref.dtype)


def conv3x3_bn_relu(x, w, scale=None, shift=None, relu=True):
    """Fused 3x3/s1/p1 conv + BN affine + relu, NHWC.

    x: [N, H, W, C]; w: [3, 3, C, O] (HWIO); scale/shift: [O] (None =
    identity — plain conv).  Returns [N, H, W, O].
    """
    N, H, W, C = x.shape
    O = w.shape[-1]
    if w.shape[:3] != (3, 3, C):
        raise ValueError("conv3x3_bn_relu needs a [3,3,C,O] kernel, got %s"
                         % (w.shape,))
    scale = jnp.ones((O,), jnp.float32) if scale is None else scale
    shift = jnp.zeros((O,), jnp.float32) if shift is None else shift
    # row-block: target ~512 patch rows per MXU call, dividing H
    bh = min(H, max(1, 512 // max(W, 1)))
    while H % bh:
        bh -= 1
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    wm = w.reshape(9 * C, O)
    kern = functools.partial(_conv3x3_kernel, bh=bh, W=W, C=C, O=O,
                             relu=relu)
    return _pallas_call(
        kern, "conv3x3_bn_relu",
        grid=(N, H // bh),
        in_specs=[
            pl.BlockSpec((1, H + 2, W + 2, C), lambda n, i: (n, 0, 0, 0)),
            pl.BlockSpec((9 * C, O), lambda n, i: (0, 0)),
            pl.BlockSpec((O,), lambda n, i: (0,)),
            pl.BlockSpec((O,), lambda n, i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, bh, W, O), lambda n, i: (n, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H, W, O), x.dtype),
    )(xp, wm, scale, shift)
