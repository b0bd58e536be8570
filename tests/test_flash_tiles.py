"""The flash kernels' tile chooser (``pallas_ops._tiles``): what it picks
from a shape, as a pure function; the kernels' numerics at tiles above
128 x 128, interpreted on the CPU against the XLA composition at the
tolerances of test_pallas_attention.py; and the counter that says which
tile engaged."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.fluid import telemetry
from paddle_tpu.fluid.ops import pallas_ops
from paddle_tpu.fluid.ops.pallas_ops import (_reference_attention,
                                             flash_attention)

KERNELS = ("fwd", "dq", "dkv")

# (S_q, S_kv, D, D_v, R, has_bias, causal, itemsize): ``_tiles``'s arguments
# after the kernel's name
SHAPES = {
    # bert_base_s512_flash: BH=384, bf16, the padding mask as a bias
    "flash_cell": (512, 512, 64, 64, 0, True, False, 2),
    # moonlight_ep8share_s4096_train: 16 heads, 128 + 64 | 128, causal
    "moonlight_cell": (4096, 4096, 128, 128, 64, False, True, 2),
    "s128_d16": (128, 128, 16, 16, 0, True, False, 4),
    "cross_128x256": (128, 256, 16, 16, 0, False, False, 4),
    "s384": (384, 384, 64, 64, 0, False, False, 2),
    "s640": (640, 640, 64, 64, 0, True, False, 2),
    "s64": (64, 64, 16, 16, 0, False, False, 4),
    "s8192_bias": (8192, 8192, 64, 64, 0, True, False, 2),
    "s16384_causal": (16384, 16384, 64, 64, 0, False, True, 2),
}

CHOSEN = {
    "flash_cell": {k: (512, 512) for k in KERNELS},
    "moonlight_cell": {k: (512, 512) for k in KERNELS},
    "s128_d16": {k: (128, 128) for k in KERNELS},
    "cross_128x256": {k: (128, 256) for k in KERNELS},
    # divisors only: 384 = 3 x 128, 640 = 5 x 128
    "s384": {k: (128, 128) for k in KERNELS},
    "s640": {k: (128, 128) for k in KERNELS},
    # shorter than the least side: the sequence is one block
    "s64": {k: (64, 64) for k in KERNELS},
    # a [512, 8192] bias tile and the whole K and V leave the dK/dV pass,
    # whose bias tile is [8192, block_k], no room for 512 columns
    "s8192_bias": {"fwd": (512, 512), "dq": (512, 512), "dkv": (512, 256)},
    "s16384_causal": {k: (512, 512) for k in KERNELS},
}


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("shape", sorted(CHOSEN))
def test_chooser_picks_the_largest_tile_that_fits(shape, kernel):
    ok, block_q, block_k = pallas_ops._tiles(kernel, *SHAPES[shape])
    assert ok
    assert (block_q, block_k) == CHOSEN[shape][kernel]
    S_q, S_kv = SHAPES[shape][:2]
    assert S_q % block_q == 0 and S_kv % block_k == 0


@pytest.mark.parametrize("kernel", KERNELS + ("dbias",))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_estimate_fits_the_budget_and_the_limit_it_hands_on(shape, kernel):
    """The chosen tile's estimate is within the budget, no smaller tile
    asks for more, and ``vmem_limit_bytes`` — set only past the compiler's
    16 MiB default — is never under the estimate."""
    _, block_q, block_k = pallas_ops._tiles(kernel, *SHAPES[shape])
    need = pallas_ops._vmem_bytes(kernel, block_q, block_k, *SHAPES[shape])
    assert need <= pallas_ops._VMEM_BUDGET_BYTES
    limit = pallas_ops._vmem_limit(need)
    if limit is None:
        assert need < pallas_ops._VMEM_SCOPED_DEFAULT_BYTES
    else:
        assert need < limit <= 128 << 20
    for bq, bk in ((block_q // 2, block_k), (block_q, block_k // 2)):
        if min(bq, bk) >= 128:
            assert pallas_ops._vmem_bytes(kernel, bq, bk,
                                          *SHAPES[shape]) <= need


@pytest.mark.parametrize("shape,why", [
    ((192, 192, 16, 16, 0, False, False, 4), "no side divides 192"),
    ((128, 200, 16, 16, 0, False, False, 4), "nor 200"),
    ((32768, 32768, 128, 128, 0, False, True, 2),
     "the dK/dV pass's whole Q side alone is past the budget"),
])
def test_shapes_without_a_tile_compose(shape, why):
    assert not pallas_ops._flash_fits(*shape), why


def test_the_budget_is_a_share_of_the_chip():
    """A quarter of a v5e core's 128 MiB, above the 16 MiB default: the
    Moonlight cell's dK/dV pass at 512 x 512 is the kernel that needs the
    raised limit."""
    assert pallas_ops._VMEM_SCOPED_DEFAULT_BYTES < \
        pallas_ops._VMEM_BUDGET_BYTES <= (128 << 20) // 4
    need = pallas_ops._vmem_bytes("dkv", 512, 512, *SHAPES["moonlight_cell"])
    assert pallas_ops._vmem_limit(need) > 16 << 20
    assert pallas_ops._vmem_limit(
        pallas_ops._vmem_bytes("fwd", 512, 512,
                               *SHAPES["flash_cell"])) is None


# -- numerics above 128 x 128, interpreted ----------------------------------

def _close(got, want, atol=2e-4):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=atol)


@pytest.fixture(scope="module")
def biased_s512():
    """S=512, D=64 with a bias: one 512 x 512 tile a head in every kernel
    (no rescale in the forward, the dQ pass's row held as values)."""
    rng = np.random.RandomState(21)
    BH, S, D = 2, 512, 64
    q, k, v, g = (jnp.asarray(rng.randn(BH, S, D).astype(np.float32) * 0.5)
                  for _ in range(4))
    bias = jnp.asarray((rng.randn(BH, S, S) * 0.3).astype(np.float32))
    scale = D ** -0.5
    for kernel in KERNELS + ("dbias",):
        assert pallas_ops._tiles(
            kernel, *pallas_ops._shape_key(q, k, v, bias, False, None)
        )[1:] == (512, 512)
    want, vjp = jax.vjp(lambda *a: _reference_attention(*a, scale),
                        q, k, v, bias)
    got, fvjp = jax.vjp(lambda *a: flash_attention(*a, scale), q, k, v, bias)
    names = ("dq", "dk", "dv", "dbias")
    return dict(zip(names, fvjp(g)), out=got), \
        dict(zip(names, vjp(g)), out=want)


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv", "dbias"])
def test_one_tile_a_head_matches_the_composition(biased_s512, what):
    got, want = biased_s512
    _close(got[what], want[what], atol=2e-5 if what == "out" else 2e-4)


def _rotary_case(S, D, D_v, R, seed):
    """Two heads of one sequence sharing one rotary key head, causal."""
    rng = np.random.RandomState(seed)

    def arr(*dims):
        return jnp.asarray(rng.randn(*dims).astype(np.float32) * 0.3)
    args = (arr(2, S, D), arr(2, S, D), arr(2, S, D_v), arr(2, S, R),
            arr(1, S, R))
    scale = (D + R) ** -0.5

    def composed(q, k, v, qr, kr):
        return _reference_attention(
            *pallas_ops._compose_rope(q, k, (qr, kr)), v, None, scale,
            causal=True)

    def flash(q, k, v, qr, kr):
        return flash_attention(q, k, v, None, scale, True, (qr, kr))
    g = arr(2, S, D_v)
    want, vjp = jax.vjp(composed, *args)
    got, fvjp = jax.vjp(flash, *args)
    names = ("dq", "dk", "dv", "dqr", "dkr")
    return dict(zip(names, fvjp(g)), out=got), \
        dict(zip(names, vjp(g)), out=want)


@pytest.fixture(scope="module")
def rotary_s1024():
    """Causal S=1024 at latent attention's head sizes (128 + 64 | 128):
    512 x 512 tiles, so each looped sweep walks a tile wholly below the
    diagonal and one on it."""
    shape = (1024, 1024, 128, 128, 64, False, True, 4)
    for kernel in KERNELS:
        assert pallas_ops._tiles(kernel, *shape)[1:] == (512, 512)
    return _rotary_case(1024, 128, 128, 64, seed=22)


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv", "dqr", "dkr"])
def test_looped_sweeps_match_the_composition(rotary_s1024, what):
    got, want = rotary_s1024
    _close(got[what], want[what])


@pytest.mark.parametrize("block_q,block_k", [(512, 256), (256, 512),
                                             (128, 512), (512, 128)])
def test_looped_sweeps_with_unequal_sides(monkeypatch, block_q, block_k):
    """The loop bounds — every tile the diagonal crosses or that lies below
    it, none above — hold for block_q != block_k, which the chooser gives
    where VMEM holds one side back."""
    monkeypatch.setattr(pallas_ops, "_tiles",
                        lambda kernel, *shape: (True, block_q, block_k))
    got, want = _rotary_case(1024, 16, 8, 8, seed=23)
    for what in want:
        _close(got[what], want[what])


# -- the counter --------------------------------------------------------------

@pytest.mark.parametrize("form,causal,with_bias,S", [
    ("unrolled", False, True, 512), ("looped", True, False, 256),
    ("unrolled", False, True, 1024), ("looped", True, False, 1024)])
def test_flash_tiles_total_counts_each_kernel_of_a_lowering(form, causal,
                                                            with_bias, S):
    """One lowering of forward and backward counts one ``fwd`` call and,
    where a head is one tile, one ``bwd`` call, else one ``dq`` and one
    ``dkv``, at the tile the chooser picked (trace-time, like
    ``fused_attention_lowered_total``), in either form."""
    counter = telemetry.registry().get("flash_tiles_total")
    x = jax.ShapeDtypeStruct((2, S, 16), jnp.float32)
    bias = jax.ShapeDtypeStruct((2, S, S), jnp.float32) if with_bias \
        else None
    block = min(S, 512)
    labels = [dict(kernel=k, block_q=block, block_k=block)
              for k in KERNELS + ("bwd", "dbias")]
    before = [counter.value(**lb) for lb in labels]
    jax.jit(jax.grad(
        lambda q, k, v, b: flash_attention(q, k, v, b, 0.25, causal).sum(),
        argnums=(0, 1, 2))).lower(x, x, x, bias)
    fused = int(S <= 512)
    # the dbias pass is traced wherever there is a bias (XLA drops it when
    # nothing reads its output)
    assert [counter.value(**lb) - b for lb, b in zip(labels, before)] == \
        [1, 1 - fused, 1 - fused, fused, int(with_bias)]


@pytest.mark.parametrize("S,bias_rows,stats,bias", [
    (128, 0, "row", "none"), (128, 2, "row", "sequence"),
    (128, 4, "row", "head"), (1024, 0, "column", "none"),
    (1024, 2, "column", "sequence"), (1024, 4, "column", "head")])
def test_flash_tiles_total_says_which_layout_and_bias_a_call_took(
        S, bias_rows, stats, bias):
    """``stats``: rows where a head is one tile, columns where the backward
    is two passes; ``bias``: ``sequence`` for a ``[B, S_q, S_kv]`` bias the
    H heads of a sequence read at block row ``i // H``, ``head`` for one a
    head, ``none``.  Every kernel of the lowering carries both."""
    counter = telemetry.registry().get("flash_tiles_total")
    x = jax.ShapeDtypeStruct((4, S, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((bias_rows, S, S), jnp.float32) \
        if bias_rows else None
    # the dbias pass is traced wherever there is a bias, labelled alike
    kernels = (("fwd", "bwd") if stats == "row" else ("fwd", "dq", "dkv")) \
        + (("dbias",) if bias_rows else ())
    labels = [dict(kernel=k, stats=stats, bias=bias) for k in kernels]
    before, total = [counter.value(**lb) for lb in labels], counter.value()
    jax.jit(jax.grad(
        lambda q, k, v, b: flash_attention(q, k, v, b, 0.25).sum(),
        argnums=(0, 1, 2))).lower(x, x, x, b)
    assert [counter.value(**lb) - n for lb, n in zip(labels, before)] == \
        [1] * len(kernels)
    assert counter.value() - total == len(kernels)


# -- one backward kernel or two: the rule, from shapes alone -----------------

# (S_q, S_kv, D, D_v, R, has_bias, causal, itemsize) -> fused
FUSED = {
    "flash_cell": True,                 # S=512, D=64, bf16, a bias
    "s128_d16": True,
    "cross_128x256": True,              # a tile need not be square
    "s64": True,                        # shorter than the least side
    "s512_causal_d128": True,
    "s512_f32_bias": True,
    "s384": False,                      # 3 x 128: three tiles a side
    "s640": False,
    "s1024_bias": False,                # two 512-row tiles a side
    "cross_512x1024": False,            # dQ is summed over two k tiles
    "moonlight_cell": False,            # S=4096, and a rotary pair
    "ouro_cell": False,                 # S=4096, D=128, causal
    "s512_rotary": False,               # one tile, but a rotary pair
    "s8192_bias": False,
    # one tile in both passes, yet the fused kernel holds both sides and
    # all three outputs at once: past the budget the pair of passes runs
    "s512_d1536": False,
}
RULE_SHAPES = dict(
    SHAPES,
    s512_causal_d128=(512, 512, 128, 128, 0, False, True, 2),
    s512_f32_bias=(512, 512, 64, 64, 0, True, False, 4),
    s1024_bias=(1024, 1024, 64, 64, 0, True, False, 2),
    cross_512x1024=(512, 1024, 64, 64, 0, False, False, 2),
    ouro_cell=(4096, 4096, 128, 128, 0, False, True, 2),
    s512_rotary=(512, 512, 128, 128, 64, False, True, 2),
    s512_d1536=(512, 512, 1536, 1536, 0, False, False, 2),
)


@pytest.mark.parametrize("shape", sorted(FUSED))
def test_backward_is_one_kernel_where_a_head_is_one_tile(shape):
    key = RULE_SHAPES[shape]
    assert pallas_ops._fused_backward(*key) is FUSED[shape]
    assert pallas_ops._tiles("bwd", *key) == (FUSED[shape],) + key[:2]
    if FUSED[shape]:
        for kernel in ("dq", "dkv"):
            assert pallas_ops._tiles(kernel, *key) == (True,) + key[:2]
        assert pallas_ops._vmem_bytes("bwd", *key[:2], *key) <= \
            pallas_ops._VMEM_BUDGET_BYTES


def test_a_wide_head_of_one_tile_keeps_the_pair_for_the_budget_alone():
    key = RULE_SHAPES["s512_d1536"]
    for kernel in ("dq", "dkv"):
        assert pallas_ops._tiles(kernel, *key) == (True, 512, 512)
    assert pallas_ops._vmem_bytes("bwd", 512, 512, *key) > \
        pallas_ops._VMEM_BUDGET_BYTES


# -- read in place or split: the rule, from the heads and the shape alone ----

# (heads, shape key) -> whether the kernels read [B, S, heads * D] in place
IN_PLACE = {
    # bert_base_s512_flash as its step hands it over: float32, the mask
    "flash_cell_f32": (12, RULE_SHAPES["s512_f32_bias"], True),
    "flash_cell_bf16": (12, RULE_SHAPES["flash_cell"], True),
    "one_head_of_128": (5, RULE_SHAPES["s512_causal_d128"], True),
    "cross_128x256_d16": (8, RULE_SHAPES["cross_128x256"], True),
    # a pair of heads a block, and an odd head over
    "odd_heads_of_64": (11, RULE_SHAPES["flash_cell"], False),
    "four_heads_of_16": (4, RULE_SHAPES["s128_d16"], False),
    # no one-tile backward: two passes, on split heads
    "s1024": (12, RULE_SHAPES["s1024_bias"], False),
    "s384": (12, RULE_SHAPES["s384"], False),
    "ouro_cell": (16, RULE_SHAPES["ouro_cell"], False),
    "rotary": (16, RULE_SHAPES["s512_rotary"], False),
    # V's head is not Q's: one block index cannot serve both
    "dv_wider": (4, (128, 128, 64, 128, 0, False, False, 4), False),
    # 96 does not divide 128; 256 is wider than a block
    "heads_of_96": (4, (128, 128, 96, 96, 0, False, False, 4), False),
    "heads_of_256": (4, (128, 128, 256, 256, 0, False, False, 2), False),
    # grouped key/value heads have no one-tile backward
    "grouped": (4, (128, 128, 64, 64, 0, False, True, 2, 2), False),
}


@pytest.mark.parametrize("case", sorted(IN_PLACE))
def test_heads_are_read_in_place_where_they_pack_into_a_block(case):
    heads, key, want = IN_PLACE[case]
    assert pallas_ops._in_place(heads, *key) is want
    if want:
        assert pallas_ops._fused_backward(*key)


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("case", sorted(
    c for c in IN_PLACE if IN_PLACE[c][2]))
def test_estimate_counts_the_heads_a_cell_holds(case, kernel):
    """A cell of ``128 // D`` heads holds blocks that many times as wide
    (Q, K, V, dO and the outputs) and a statistic row a head; the score
    tiles stay one head's.  Still within the budget, and more than one
    head's cell asks for."""
    heads, key, _ = IN_PLACE[case]
    pack = 128 // key[2]
    one = pallas_ops._vmem_bytes(kernel, *key[:2], *key, rows=True)
    cell = pallas_ops._vmem_bytes(kernel, *key[:2], *key, rows=True,
                                  heads=pack)
    assert cell <= pallas_ops._VMEM_BUDGET_BYTES
    if pack == 1:
        assert cell == one
    else:
        # the lanes a narrow head's block is padded to are the cell's
        # other heads: what grows is the statistics and the accumulators
        assert one < cell < one * pack


def test_flash_cell_pair_in_float32_is_handed_its_own_limit():
    """The flash cell's cell of two heads in float32: the forward's
    estimate and its eighth stay under the compiler's 16 MiB scoped
    default, the backward's (16.5 MB: seven blocks of 256 KB and the mask
    twice, four float32 score tiles) pass it, so Mosaic is handed
    ``vmem_limit_bytes`` (compiled for a v5e, test_program_spans.py)."""
    key = RULE_SHAPES["s512_f32_bias"]
    need = {kernel: pallas_ops._vmem_bytes(kernel, 512, 512, *key, rows=True,
                                           heads=2)
            for kernel in ("fwd", "bwd")}
    assert pallas_ops._vmem_limit(need["fwd"]) is None, need
    assert (16 << 20) < pallas_ops._vmem_limit(need["bwd"]) < (20 << 20), need


@pytest.mark.parametrize("heads,layout", [(2, "bshd"), (3, "bhsd")])
def test_flash_tiles_total_says_which_operand_layout_a_call_took(heads,
                                                                 layout):
    """``layout``: ``bshd`` for ``fwd`` and ``bwd`` on ``[B, S, H * D]``
    read in place, ``bhsd`` for every call on ``[BH, S, D]``; the other
    labels say what they said."""
    counter = telemetry.registry().get("flash_tiles_total")
    S, D = 128, 64
    labels = [dict(kernel=k, layout=layout, stats="row", bias="none",
                   block_q=S, block_k=S) for k in ("fwd", "bwd")]
    before, total = [counter.value(**lb) for lb in labels], counter.value()
    if layout == "bshd":
        x = jax.ShapeDtypeStruct((2, S, heads * D), jnp.float32)

        def loss(q, k, v):
            return pallas_ops.flash_attention_in_place(
                q, k, v, None, 0.125, False, heads)[0].sum()
    else:
        x = jax.ShapeDtypeStruct((2 * heads, S, D), jnp.float32)

        def loss(q, k, v):
            return flash_attention(q, k, v, None, 0.125).sum()
    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x)
    assert [counter.value(**lb) - n for lb, n in zip(labels, before)] == \
        [1, 1]
    assert counter.value() - total == 2
    assert counter.value(layout="bshd") + counter.value(layout="bhsd") == \
        counter.value()


# -- attention dropout drawn inside the in-place kernels (PR 40) -------------

def _rebuilt_mask(seed, n_heads, S_q, S_kv, rate):
    """The keep masks the interpreted kernels draw, rebuilt outside any
    kernel from ``_keep_bits``' interpreted branch (``_hash_bits``): bool
    ``[n_heads, S_q, S_kv]``, head ``n`` the row of ``[B * H, ...]``."""
    t = pallas_ops._keep_threshold(rate)
    with jax.ensure_compile_time_eval():      # also while a step is traced
        return np.stack([
            np.asarray((pallas_ops._hash_bits(seed, n, (S_q, S_kv))
                        ^ jnp.int32(-2 ** 31)) < t)
            for n in range(n_heads)])


def test_the_kernels_mask_is_the_rebuilt_one_and_keeps_nine_in_ten():
    """The mask read back by a kernel of the attention kernels' grid
    (``_drawn_mask``: two sequences, four heads of 64, two cells of two a
    sequence) is the one rebuilt outside it; over 128 x 128 x 8 draws it
    keeps within 5 sigma of 0.9."""
    got = np.asarray(pallas_ops._drawn_mask(jnp.array([7], jnp.int32), 2, 4,
                                            64, 128, 128, 0.1))
    want = _rebuilt_mask(7, 8, 128, 128, 0.1)
    assert got.shape == want.shape and (got.astype(bool) == want).all()
    assert abs(want.mean() - 0.9) < 5 * np.sqrt(0.9 * 0.1 / want.size)


@pytest.mark.parametrize("other", ["seed", "head_in_cell", "cell", "sequence"])
def test_seeds_heads_and_cells_draw_masks_of_their_own(other):
    """Head 0 of sequence 0 under seed 7 against one that differs in one
    of them: two independent masks agree on 0.9^2 + 0.1^2 = 0.82 of their
    elements, the same one on all."""
    seed, head = {"seed": (8, 0), "head_in_cell": (7, 1), "cell": (7, 2),
                  "sequence": (7, 4)}[other]
    mask = {s: np.asarray(pallas_ops._drawn_mask(jnp.array([s], jnp.int32),
                                                 2, 4, 64, 128, 128, 0.1))
            for s in {7, seed}}
    agree = (mask[7][0] == mask[seed][head]).mean()
    assert abs(agree - 0.82) < 0.02, agree


@pytest.mark.parametrize("rate,dropout", [(0.0, "none"), (0.1, "in_kernel")])
def test_flash_tiles_total_says_whether_a_call_draws_its_dropout(rate,
                                                                  dropout):
    """``dropout``: ``in_kernel`` for ``fwd`` and ``bwd`` in place that draw
    the keep mask themselves, ``none`` for every other call."""
    counter = telemetry.registry().get("flash_tiles_total")
    x = jax.ShapeDtypeStruct((2, 128, 2 * 64), jnp.float32)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32) if rate else None
    labels = [dict(kernel=k, layout="bshd", dropout=dropout)
              for k in ("fwd", "bwd")]
    before, total = [counter.value(**lb) for lb in labels], counter.value()

    def loss(q, k, v, seed):
        return pallas_ops.flash_attention_in_place(
            q, k, v, None, 0.125, False, 2, True, rate, seed)[0].sum()
    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x, seed)
    assert [counter.value(**lb) - n for lb, n in zip(labels, before)] == \
        [1, 1]
    assert counter.value() - total == 2
    assert counter.value(dropout="none") + \
        counter.value(dropout="in_kernel") == counter.value()


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_estimate_counts_the_bits_a_dropout_tile_draws(kernel):
    """With dropout a cell also holds a tile's int32 bits and the float32
    tile the mask leaves, and the flash cell's pair of heads in float32
    still fits the budget (the route does not read the rate)."""
    key = RULE_SHAPES["s512_f32_bias"]
    plain, drawn = (pallas_ops._vmem_bytes(kernel, 512, 512, *key, rows=True,
                                           heads=2, bits=bits)
                    for bits in (False, True))
    assert drawn - plain == 512 * 512 * 8
    assert drawn <= pallas_ops._VMEM_BUDGET_BYTES


@pytest.mark.parametrize("S_q,S_kv,D,want", [
    (512, 512, 64, True),        # bert_base_s512_dropout: 262144 > 98304
    (256, 256, 64, True),
    (128, 128, 64, False),       # bert_base_s128_dropout: 16384 < 24576
    (512, 512, 128, True),
    (256, 256, 128, False),
    (512, 128, 64, True),        # 65536 > 49152
    (128, 512, 64, False)])      # 65536 < 73728
def test_dropout_is_drawn_in_the_kernels_where_scores_outnumber_operands(
        S_q, S_kv, D, want):
    """An op with dropout takes the kernels in place where a head's scores
    outnumber the Q, K and V the kernels keep for it, else it composes."""
    assert pallas_ops._drop_in_kernels(S_q, S_kv, D) is want
