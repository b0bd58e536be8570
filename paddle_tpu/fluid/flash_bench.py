"""Flash-attention tile sweep: ms a call of each kernel (``flash_fwd``,
``flash_dq``, ``flash_dkv``, and ``flash_bwd`` where the backward is one
kernel) on the attached chip, at the tile the chooser picks
(``pallas_ops._tiles``) and at every forced square and mixed tile from 128
to 512, at the shapes of the benchmark's two kernel cells:

* ``bert``: BH=384 (batch 32 x 12 heads), S=512, D=64, bf16, non-causal,
  with the padding mask as the op hands it to the kernels: ``[B, 1, S,
  S]`` through ``_kernel_bias``, so ONE ``[S, S]`` a sequence read at
  block row ``i // H``, and ``fwd`` and ``bwd`` with the row statistics as
  lane-dense rows — the unrolled form (``bert_base_s512_flash``);
* ``moonlight``: 16 heads, S=4096, 128 + 64 | 128, bf16, causal, a shared
  rotary key head — the looped form (``moonlight_ep8share_s4096_train``);
* ``smallthinker``: 28 query heads over 4 key/value heads of 128, S=16384,
  bf16, causal, both layer kinds of ``smallthinker_ep8share_s16384_train``
  one after the other: the full layer (``window`` 0 in the record) and the
  layer with a sliding window of 4096 keys, whose looped sweeps visit the
  band's tiles alone (252 of the 528 tiles of 512).

After the sweep of ``bert`` come the kernels that run in that cell's step
since PR 38, at the chooser's one tile: ``fwd`` and ``bwd`` on ``[B, S, H *
D]`` operands read in place (``layout`` ``bshd``: 384 heads in 192 grid
cells, a pair of heads of 64 a cell, ``pallas_ops._in_place``) beside the
same calls on ``[BH, S, D]`` (``bhsd``), in float32 as the step hands them
over (``fused_attention`` is on no AMP list) and in bfloat16.

``dropout`` (PR 40) times ``flash_fwd`` and ``flash_bwd`` in place at the
two dropout cells' attention shapes, 12 heads of 64, float32 with the
padding mask: (B=16, S=512) of ``bert_base_s512_dropout`` and (B=128,
S=128) of ``bert_base_s128_dropout``, at rate 0 and at rate 0.1 with the
keep mask drawn in the kernel from the core's generator (``bits`` ``core``)
or from the counter hash the interpreter draws (``hash``,
``pallas_ops._CHIP_BITS``).

Run: python -m paddle_tpu.fluid.flash_bench [bert|moonlight|dropout ...]
Prints one JSON line per shape, kernel and tile; ``form`` says which form
of the backward the lowering takes at the shape (``fused``: ``bwd`` alone
runs in a step, ``dq`` and ``dkv`` are what it replaced; ``two_pass``: no
``bwd`` record), and a ``bwd`` record exists at the chooser's pick only
(its one tile is the head).  Each kernel is timed alone (delta is passed
to the dK/dV pass; the pair of passes reads column statistics at every
shape, ``pallas_ops._row_stats``), by the bench.py fence (async dispatch,
one scalar fetch, RTT subtracted).  A time is a chip's: off a TPU the
module refuses to run.
"""

import json
import sys

import numpy as np

TILES = ((128, 128), (256, 256), (512, 128), (128, 512), (512, 256),
         (256, 512), (512, 512))


def _operands(shape, dtype=None):
    import jax
    import jax.numpy as jnp
    from .mesh_utils import local_devices

    rng = np.random.default_rng(0)
    dev = local_devices()[0]
    dtype = dtype or jnp.bfloat16

    def arr(*dims, dtype=dtype, scale=1.0):
        return jax.device_put(
            (rng.standard_normal(dims, dtype=np.float32) * scale)
            .astype(dtype), dev)
    if shape == "bert":
        B, H, S, D = 32, 12, 512, 64
        q, k, v, g = (arr(B * H, S, D) for _ in range(4))
        return dict(q=q, k=k, v=v, g=g, bias=arr(B, 1, S, S, scale=0.1),
                    heads=H, rope=None, causal=False, scale=D ** -0.5)
    if shape == "smallthinker":
        H, H_kv, S, D = 28, 4, 16384, 128
        return dict(q=arr(H, S, D), k=arr(H_kv, S, D), v=arr(H_kv, S, D),
                    g=arr(H, S, D), bias=None, causal=True, rope=None,
                    scale=D ** -0.5)
    H, S = 16, 4096
    q, k, v, g = (arr(H, S, 128) for _ in range(4))
    return dict(q=q, k=k, v=v, g=g, bias=None, causal=True,
                rope=(arr(H, S, 64), arr(1, S, 64)), scale=192 ** -0.5)


def _corner(fn):
    """``fn`` jitted down to one scalar that needs every output, for the
    fence."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda *args: sum(
        jnp.sum(x[..., :1, :1].astype(jnp.float32))
        for x in jax.tree.leaves(fn(*args))))


# the sliding windows a shape's sweep runs, one after the other (0: none)
WINDOWS = {"smallthinker": (0, 4096)}


def _kernel_calls(ops, window=0):
    """``{kernel: zero-argument call}``, each running ONE jitted kernel on
    ``ops`` (under the sliding ``window``); built anew for every tile, so
    nothing traced is reused."""
    import functools
    import jax
    from .ops import pallas_ops as po

    scale, causal = float(ops["scale"]), ops["causal"]
    arrays = {n: ops[n] for n in ("q", "k", "v", "g", "bias", "rope")}
    if ops["bias"] is not None:
        # the mask as the fused_attention op hands it to the kernels
        q4 = ops["q"].reshape(-1, ops["heads"], *ops["q"].shape[1:])
        arrays["bias"] = po._kernel_bias(ops["bias"], q4, ops["k"].shape[1])
    # the dQ pass forms delta itself where the lowering lets it
    in_kernel = po._delta_in_kernel(ops["k"].shape[1], causal,
                                    ops["bias"] is not None)

    def forward(a):
        return po._flash_forward(a["q"], a["k"], a["v"], a["bias"], scale,
                                 with_lse=True, causal=causal,
                                 rope=a["rope"], window=window)

    def dq(a, lse, delta):
        return po._flash_dq(a["q"], a["k"], a["v"], a["bias"], scale, lse,
                            a["g"], causal, None if in_kernel else delta,
                            a["rope"], window)[0]

    def dkv(a, lse, delta):
        return po._flash_dkv(a["q"], a["k"], a["v"], a["bias"], scale, lse,
                             a["g"], causal, delta, a["rope"], window)

    def bwd(a, lse, delta):
        return po._flash_bwd(a["q"], a["k"], a["v"], a["bias"], scale, lse,
                             a["g"], causal, None if in_kernel else delta)[:3]

    out, lse = jax.jit(forward)(arrays)
    delta = po._row_delta(ops["g"], out)
    # the two passes read their statistics as columns, the fused kernel as
    # rows (``pallas_ops._row_stats``)
    column = [po._kernel_stat(stat, False) for stat in (lse, delta)]
    row = [po._kernel_stat(stat, True) for stat in (lse, delta)]
    return {"fwd": functools.partial(_corner(forward), arrays),
            "dq": functools.partial(_corner(dq), arrays, *column),
            "dkv": functools.partial(_corner(dkv), arrays, *column),
            "bwd": functools.partial(_corner(bwd), arrays, *row)}


def _require_tpu():
    import jax
    if jax.default_backend() != "tpu":
        raise RuntimeError("flash_bench times the chip's kernels: no TPU "
                           "here (%s)" % jax.default_backend())


def _layout_calls(ops):
    """``{(layout, kernel): zero-argument call}`` of the one-tile pair on
    the ``bert`` operands: ``bhsd`` as ``_kernel_calls`` runs them, ``bshd``
    the same heads as ``[B, S, H * D]``, read and written in place."""
    import functools
    import jax
    from .ops import pallas_ops as po

    scale, H = float(ops["scale"]), ops["heads"]
    q4 = ops["q"].reshape(-1, H, *ops["q"].shape[1:])
    bias = po._kernel_bias(ops["bias"], q4, ops["k"].shape[1])
    flat = {n: ops[n] for n in ("q", "k", "v", "g")}
    minor = {n: jax.jit(lambda x: po._heads_minor(x.reshape(q4.shape)))(x)
             for n, x in flat.items()}

    lse = jax.jit(lambda a: po._flash_forward(
        a["q"], a["k"], a["v"], bias, scale, with_lse=True)[1])(flat)
    calls = {
        ("bhsd", "fwd"): (lambda a: po._flash_forward(
            a["q"], a["k"], a["v"], bias, scale, with_lse=True), flat),
        ("bhsd", "bwd"): (lambda a: po._flash_bwd(
            a["q"], a["k"], a["v"], bias, scale, lse[:, None], a["g"],
            False, None)[:3], flat),
        ("bshd", "fwd"): (lambda a: po._flash_fwd_in_place(
            a["q"], a["k"], a["v"], bias, scale, H), minor),
        ("bshd", "bwd"): (lambda a: po._backward_in_place(
            a["q"], a["k"], a["v"], bias, scale, False, H, lse, a["g"]),
            minor)}
    return {key: functools.partial(_corner(fn), arrays)
            for key, (fn, arrays) in calls.items()}


def layouts(steps=30):
    """Yield one record per dtype, layout and kernel of the one-tile pair
    at the ``bert`` shape (module docstring)."""
    import jax.numpy as jnp
    from .timing import timed_steps

    _require_tpu()
    for dtype in (jnp.float32, jnp.bfloat16):
        for (layout, kernel), call in _layout_calls(
                _operands("bert", dtype)).items():
            dt, _ = timed_steps(lambda i: call(), steps, warmup=3,
                                fetch=lambda out: float(out))
            yield dict(shape="bert", kernel=kernel, layout=layout,
                       dtype=jnp.dtype(dtype).name, block_q=512, block_k=512,
                       chosen=True, form="fused",
                       ms=round(dt / steps * 1e3, 4))


DROPOUT_SHAPES = ((16, 512), (128, 128))


def dropout(steps=30):
    """Yield one record per shape, rate, source of bits and kernel of the
    in-place pair with dropout drawn inside it (module docstring)."""
    import functools
    import jax
    import jax.numpy as jnp
    from .mesh_utils import local_devices
    from .ops import pallas_ops as po
    from .timing import timed_steps

    _require_tpu()
    H, D = 12, 64
    rng = np.random.default_rng(0)
    dev = local_devices()[0]
    seed = jax.device_put(jnp.array([20240], jnp.int32), dev)
    chip_bits = po._CHIP_BITS
    try:
        for B, S in DROPOUT_SHAPES:
            a = {n: jax.device_put(rng.standard_normal(
                (B, S, H * D), dtype=np.float32), dev)
                for n in ("q", "k", "v", "g")}
            bias = jax.device_put(rng.standard_normal(
                (B, S, S), dtype=np.float32) * 0.1, dev)
            for rate, bits in ((0.0, "none"), (0.1, "core"), (0.1, "hash")):
                po._CHIP_BITS = "core" if bits == "none" else bits
                drawn = seed if rate else None

                def fwd(a, rate=rate, drawn=drawn):
                    return po._flash_fwd_in_place(a["q"], a["k"], a["v"],
                                                  bias, D ** -0.5, H, False,
                                                  True, rate, drawn)
                lse = jax.jit(fwd)(a)[1]

                def bwd(a, rate=rate, drawn=drawn):
                    return po._backward_in_place(a["q"], a["k"], a["v"],
                                                 bias, D ** -0.5, False, H,
                                                 lse, a["g"], rate, drawn)
                for kernel, fn in (("fwd", fwd), ("bwd", bwd)):
                    call = functools.partial(_corner(fn), a)
                    dt, _ = timed_steps(lambda i: call(), steps, warmup=3,
                                        fetch=lambda out: float(out))
                    yield dict(shape="dropout", kernel=kernel, batch=B,
                               seq=S, layout="bshd", dtype="float32",
                               rate=rate, bits=bits,
                               ms=round(dt / steps * 1e3, 4))
    finally:
        po._CHIP_BITS = chip_bits


def sweep(shape, steps=30):
    """Yield one record per kernel and tile of ``shape`` (and per sliding
    window, where the shape has layers of several kinds: ``WINDOWS``); the
    first of a kernel's records is the chooser's own pick."""
    _require_tpu()
    ops = _operands(shape)
    for window in WINDOWS.get(shape, (0,)):
        yield from _sweep_window(shape, ops, window, steps)


def _sweep_window(shape, ops, window, steps):
    from .ops import pallas_ops as po
    from .timing import timed_steps

    chooser = po._tiles
    key = po._shape_key(ops["q"], ops["k"], ops["v"], ops["bias"],
                        ops["causal"], ops["rope"], window)
    fused = po._fused_backward(*key)
    try:
        for forced in (None,) + TILES:
            po._tiles = chooser if forced is None else \
                (lambda kernel, *shape: (True,) + forced)
            for kernel, call in _kernel_calls(ops, window).items():
                if kernel == "bwd" and not (fused and forced is None):
                    continue
                _, block_q, block_k = po._tiles(kernel, *key)
                try:
                    dt, _ = timed_steps(lambda i: call(), steps, warmup=3,
                                        fetch=lambda out: float(out))
                    rec = {"ms": round(dt / steps * 1e3, 4)}
                except Exception as e:      # a tile Mosaic refuses
                    rec = {"error": str(e)[:200]}
                yield dict(shape=shape, kernel=kernel, window=window,
                           block_q=block_q, block_k=block_k,
                           chosen=forced is None,
                           form="fused" if fused else "two_pass", **rec)
    finally:
        po._tiles = chooser


def main():
    for shape in sys.argv[1:] or ("bert", "moonlight"):
        for rec in dropout() if shape == "dropout" else sweep(shape):
            print(json.dumps(rec))
            sys.stdout.flush()
        for rec in layouts() if shape == "bert" else ():
            print(json.dumps(rec))
            sys.stdout.flush()


if __name__ == "__main__":
    main()
