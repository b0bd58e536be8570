"""Microbench behind PERF.md section 6 (PR 28): the grouped matmul of the
routed experts at the Moonlight cell's shapes, ``jax.lax.ragged_dot``
(XLA:TPU's own Mosaic kernel; what ``ops/decoder_ops.py`` runs) against a
hand-written Pallas kernel over tile-aligned groups with a whole expert
matrix a block.  Rows: a 24576-row worst-case buffer of which 8 groups are
live.  Forward only for the Pallas kernel (it has no backward: the starting
point of a ``perf_opt`` issue); forward and forward + backward for
``ragged_dot``.  Needs the chip:

    chiprun -- python tools/moe_gmm_microbench.py
"""
import time

import numpy as np
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

M, G = 24576, 8
TM = 128


def gmm_pallas(x, w, tile_expert, n_live):
    """x [M, K] rows grouped in whole TM-row tiles; w [G, K, N];
    tile_expert [M / TM] the expert of each tile; n_live [1] tiles that
    hold rows.  Tiles past n_live are not computed (their block indices
    repeat the last live tile's, so nothing is fetched for them)."""
    m, k = x.shape
    tn = n = w.shape[2]       # narrower column blocks ran 3x slower

    def kernel(te, nl, x_ref, w_ref, o_ref):
        i = pl.program_id(0)

        @pl.when(i < nl[0])
        def _():
            o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                                 preferred_element_type=jnp.float32
                                 ).astype(o_ref.dtype)

    def row(i, j, te, nl):
        return (jnp.minimum(i, nl[0] - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(m // TM, n // tn),
        in_specs=[pl.BlockSpec((TM, k), row),
                  pl.BlockSpec((1, k, tn), lambda i, j, te, nl: (
                      te[jnp.minimum(i, nl[0] - 1)], 0, j))],
        out_specs=pl.BlockSpec((TM, tn), lambda i, j, te, nl: (
            jnp.minimum(i, nl[0] - 1), j)))
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
                          name="moe_gmm")(tile_expert, n_live, x, w)


def bench(f, *args, n=20):
    out = f(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def main():
    rng = np.random.default_rng(0)
    print(jax.devices()[0].device_kind)
    for sizes in ([384] * 8, [128, 640, 256, 512, 384, 384, 256, 512],
                  [3072] * 8):
        sizes = np.asarray(sizes, np.int32)
        tiles = sizes // TM
        tile_expert = np.zeros(M // TM, np.int32)
        tile_expert[:tiles.sum()] = np.repeat(np.arange(G), tiles)
        n_live = np.asarray([tiles.sum()], np.int32)
        for k, n in ((2048, 1408), (1408, 2048)):
            x = jnp.asarray(rng.normal(size=(M, k)), jnp.bfloat16)
            w = jnp.asarray(rng.normal(size=(G, k, n)) * 0.02, jnp.bfloat16)
            gs = jnp.asarray(sizes)
            rd = jax.jit(lambda x, w, gs: jax.lax.ragged_dot(
                x, w, gs, preferred_element_type=jnp.bfloat16))
            pk = jax.jit(gmm_pallas)
            te, nl = jnp.asarray(tile_expert), jnp.asarray(n_live)
            a = rd(x, w, gs)
            try:
                b = pk(x, w, te, nl)
                live = int(sizes.sum())
                err = float(jnp.abs(a[:live].astype(jnp.float32) -
                                    b[:live].astype(jnp.float32)).max())
                t_pk = bench(pk, x, w, te, nl)
            except Exception as e:
                err, t_pk = -1, float("nan")
                print("pallas failed:", str(e)[:300])
            t_rd = bench(rd, x, w, gs)
            # backward of ragged_dot: dlhs and drhs
            gr = jax.jit(jax.grad(lambda x, w, gs: jax.lax.ragged_dot(
                x, w, gs, preferred_element_type=jnp.bfloat16)
                .astype(jnp.float32).sum(), argnums=(0, 1)))
            t_gr = bench(gr, x, w, gs)
            flops = 2 * int(sizes.sum()) * k * n
            print("rows %5d K %4d N %4d: ragged_dot %.3f ms (%.1f "
                  "TFLOP/s), pallas %.3f ms (%.1f TFLOP/s), max err %.3g; "
                  "ragged_dot fwd+bwd %.3f ms" % (
                      sizes.sum(), k, n, t_rd, flops / t_rd / 1e9, t_pk,
                      flops / t_pk / 1e9, err, t_gr), flush=True)


if __name__ == "__main__":
    main()
