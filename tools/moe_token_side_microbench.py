"""Microbench behind PERF.md section 5: the routed experts' token
side at the three expert cells' shapes, each operation a layer and pass
timed alone on the chip:

  dispatch  ``x[token_of].astype(bfloat16)`` over the first rung's R rows
  g_rows    ``g[token_of]`` (float32), the backward's cotangent by row
  combine   the weighted float32 sum by token of the live rows of ``ys``
  dx        the unweighted sum by token of the live rows of ``dxs``

The two gathers are XLA's, as ``ops/decoder_ops.py`` runs them.  The two
sums are timed both ways: XLA's composition (``k`` gathers of ``[T, H]``,
one a choice, masked and summed in order: what the lowering ran before
the kernel) and ``pallas_ops.row_sum`` (the buffer read once, the live rows
added into their tokens' sums in VMEM), with the largest difference
between them.  The live rows are the share an even router sends the held
experts.  Each line gives ms a call and the rows a call reads; the GB/s
are the bytes it needs (the live rows, the output) over its time, against
the chip's 819.  Needs a TPU v5e:

    python tools/moe_token_side_microbench.py
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cell: tokens T, width H, top_k, experts E, held, the first rung's rows R
CELLS = {
    "smallthinker_ep8share_s16384_train": (16384, 2560, 6, 64, 8, 24576),
    "lfm2_ep4share_s8192_train": (8192, 2048, 4, 32, 8, 32768),
    "moonlight_ep8share_s4096_train": (4096, 2048, 6, 64, 8, 6144),
}
HBM_GBS = 819.0


def bench(f, *args, n=20):
    import jax
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def routing(rng, T, k, E, held):
    """``idx`` [T, k]: each assignment goes to a held expert with the
    probability an even router gives it, else to an absent one."""
    to_held = rng.random((T, k)) < held / E
    return np.where(to_held, rng.integers(0, held, (T, k)),
                    rng.integers(held, E, (T, k))).astype(np.int32)


def composed_sum(rows, slot, held, weight=None):
    """The composition ``row_sum`` replaces: a gather of ``[T, H]`` a
    choice, masked, summed over ``k`` in order in float32."""
    import jax.numpy as jnp
    total = 0
    for j in range(slot.shape[1]):
        chosen = jnp.where(held[:, j, None], rows[slot[:, j]], 0) \
            .astype(jnp.float32)
        total = total + (chosen if weight is None
                         else chosen * weight[:, j, None])
    return total


def main():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fluid.ops import decoder_ops, pallas_ops

    print(jax.devices()[0].device_kind, flush=True)
    rng = np.random.default_rng(0)
    for cell, (T, H, k, E, held, R) in CELLS.items():
        x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
        ys = jnp.asarray(rng.normal(size=(R, H)), jnp.bfloat16)
        weight = jnp.asarray(rng.random((T, k)), jnp.float32)
        idx = jnp.asarray(routing(rng, T, k, E, held))
        order, token_of, slot_of, is_held, sizes = decoder_ops._plan(
            idx, 0, held)
        n_live = sizes.sum()
        live = int(n_live)
        order, token_of = order[:R], token_of[:R]
        slot_of = jnp.minimum(slot_of, R - 1)
        w_row = decoder_ops._row_weights(weight, order, is_held)
        gathers = {
            "dispatch": (jax.jit(lambda x, t: x[t].astype(jnp.bfloat16)),
                         (x, token_of), R * H * (4 + 2)),
            "g_rows": (jax.jit(lambda g, t: g[t].astype(jnp.float32)),
                       (x, token_of), R * H * (4 + 4)),
        }
        for op, (f, args, nbytes) in gathers.items():
            t_ms = bench(f, *args)
            print("%s %d live of %d: %-8s XLA %.3f ms (%d rows, %.0f GB/s)"
                  % (cell, live, R, op, t_ms, R, nbytes / t_ms / 1e6),
                  flush=True)
        sums = {
            "combine": ((ys, slot_of, is_held, weight), w_row),
            "dx": ((ys, slot_of, is_held), None),
        }
        for op, (composed_args, w) in sums.items():
            composed = jax.jit(composed_sum)
            kernel = jax.jit(lambda r, t, n, w: pallas_ops.row_sum(
                r, t, n, T, w))
            want = composed(*composed_args)
            got = kernel(ys, token_of, n_live, w)
            scale = float(jnp.abs(want).max())
            err = float(jnp.abs(got - want).max()) / scale
            t_xla = bench(composed, *composed_args)
            t_k = bench(kernel, ys, token_of, n_live, w)
            nbytes = live * H * 2 + T * H * 4
            print("%s %d live of %d: %-8s XLA %.3f ms (%d rows, %.0f GB/s) "
                  "| row_sum %.3f ms (%.0f GB/s) | relative difference %.2g"
                  % (cell, live, R, op, t_xla, T * k,
                     nbytes / t_xla / 1e6, t_k, nbytes / t_k / 1e6, err),
                  flush=True)
    print("of %.0f GB/s" % HBM_GBS)


if __name__ == "__main__":
    main()
