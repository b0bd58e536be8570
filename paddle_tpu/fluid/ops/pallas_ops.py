"""Pallas TPU kernels for hot ops.

``fused_attention``: a flash-attention forward — blockwise online-softmax
``softmax(QK^T * scale + bias) V`` computed in VMEM without materializing
the [S, S] score matrix in HBM (the reference computes attention as
matmul + softmax + matmul ops through cuDNN/cuBLAS; the TPU-native hot
path is one fused kernel).  Backward is the tiled FlashAttention-2 pair
(dQ pass + dK/dV pass) recomputing probabilities from the forward's
saved logsumexp — [S, S] never exists in HBM in either direction for
dq/dk/dv — and ONE kernel for all three where a head is one tile ("One
backward kernel" below).  Bias gradients are exact too, via a separate
tiled pass whose [S, S]-sized output is inherent to d(bias) itself; when
the bias is a non-trainable mask the op's grad lowering skips that pass
(and under ``jax.grad`` XLA dead-code-eliminates it).  Non-tileable shapes
fall back to differentiating the identical XLA composition.

What goes from forward to backward is the logsumexp alone, lane-dense
(``[BH, S_q]``; the op's ``LSE`` output, ``[B, H, S_q]``; how it enters
and leaves the kernels is "Small operands" below): the dQ pass
(or the fused backward) forms delta = sum_d dO * O = sum_j P * dP from the
P and dP tiles it computes anyway, so ``out`` is no residual, and
``fused_attention_grad`` has a lowering of its own that runs the backward
kernels on the forward op's ``LSE`` (the generic replay of the forward
lowering would trace a second forward kernel: XLA merges replayed HLO,
never two custom calls).

Every kernel goes through ``_pallas_call``: Mosaic compiles it when the
computation is lowered for a TPU, and Pallas interpret mode runs it on any
other platform (CPU tests, virtual meshes), so behavior is identical
everywhere.

Tiles and VMEM: a grid cell holds ``block_q`` rows of its own operand
(``block_k`` in the dK/dV pass) and the WHOLE sequence of the other side —
K and V in the forward, dQ and dbias passes, Q and dO (plus a [S_q,
block_k] bias tile) in the dK/dV pass — and walks that side in tiles of
``block_k`` (``block_q``) rows.  A cell and a tile each cost the same
fixed time whatever their size (the pipeline's step; a serial matmul ->
row max -> exp -> row sum -> matmul round; MXU weights loaded for the rows
streamed), so ``_tiles`` picks, per kernel and from the call's shapes
alone, the largest ``block_q x block_k`` from {512, 256, 128} that divides
the sequences and whose VMEM estimate (``_vmem_bytes``: own blocks and
bias tile double-buffered, the other side double- or single-buffered as
``_whole_seq`` decides, the row statistics as they lie, the float32 score / P
/ dP / dS tiles, accumulators, the dQ pass's scratches) fits
``_VMEM_BUDGET_BYTES`` = 32 MiB, a quarter of a v5e core's VMEM.  At
S=512, D=64 with a bias that is ONE 512 x 512 tile a head in every
kernel (grid ``(BH, 1)``; the forward is a plain softmax with no rescale,
the backward is the fused kernel); at 16 heads, S=4096, 128 + 64 | 128
causal it is 512 x 512 too.  The compiler's scoped default
is 16 MiB: where the estimate and an eighth over it pass that,
``_pallas_call`` hands Mosaic ``vmem_limit_bytes`` = that sum
(``_vmem_limit``; the Moonlight dK/dV pass: 15.5 -> 17.4 MiB), and where no
tile fits the budget the op composes (``_flash_fits``: S=32768 at D=128).
Mosaic's own count is lower than the estimate (compiled for a v5e, libtpu
0.0.34: 3.0 / 4.2 / 4.2 MiB for forward, dQ and dK/dV at the flash cell's
shape against 6.8 / 9.0 / 11.0 estimated, 2.75 against 11.5 for the fused
backward that runs there; 7.9 / 9.7 / 7.7 against 10.5 / 13.3 / 15.5 for
Moonlight's), which leaves room for what XLA parks in VMEM inside a
step.  Past a budget's worth of K/V the sequence has to be streamed block
by block through the grid.  The dQ pass that forms delta over more than
one tile holds the row's P and dP in two ``[block_q, S_kv]`` float32
scratches (counted by the chooser, which gives such a pass fewer rows:
256 x 512 at S=4096 with a bias); past ``_DELTA_IN_KERNEL_MAX_SKV`` = 4096
the forward keeps ``out`` and the backward passes delta in, as every
caller did before.

One backward kernel (PR 33): where ``_tiles`` gives BOTH the dQ and the
dK/dV pass a tile that covers the whole of ``S_q`` and ``S_kv`` (each a
grid of ``(BH, 1)``: neither gradient is accumulated across grid cells, so
nothing forces two passes), there is no rotary pair and the kernel's own
estimate fits the budget, the backward is ``flash_bwd``
(``_fused_backward``, from the call's shapes alone; ``_bwd_kernel``): S, P,
dP and dS are formed once a head and feed ``dQ = dS K``, ``dK = dS^T Q``
and ``dV = P^T dO`` — the same five products in the same dtypes as the
pair, which forms the scores and dP twice.  A cell holds Q, K, V, dO, the
bias tile, both row statistics and the three outputs double-buffered, the
float32 S / P, dP, dS and widened bias, the input-dtype copies of P and dS
with their transposes and the products' float32 results: 11.5 MiB by the
estimate at S=512, D=64 in bfloat16 with a bias.  It takes a passed delta
(ring attention's K/V are a shard of the row) or forms its own, and writes
it out only where the dbias pass reads it.  S <= 512 at the BERT widths,
causal or not (the mask on the one tile); S=1024 and beyond, 384 or 640
(three and five 128-row tiles) and every rotary pair keep the pair of
passes.  On the chip at the flash cell's shape a head costs 3.81 us in
``flash_bwd`` against 3.65 + 4.25 in the pair, and the same with the exp,
both transposes or the scale taken out of the body: at one tile a head
the cell's copies bound it, not its vector work — the lane-padded
``[S, 1]`` statistics first (0.73 us more with delta passed in too, 0.78
less with the logsumexp read as a lane-dense ``[1, S]`` row), then the
512 KB bias tile (0.29 less read once a sequence at block row ``i // H``,
nothing once the statistics are rows) (PERF.md, PR 33).

Small operands (PR 36): what the kernels' row statistics and a shared mask
cost to cross HBM.  The logsumexp the forward writes and the delta the
backward forms, is handed or hands to the dbias pass are one float32 a
row.  As ``[BH, S_q, 1]`` (rows on sublanes, what a ``[bq, bk]`` score tile
broadcasts against as it lies) XLA:TPU pads the size-1 minor dimension to
128 lanes: 100.7 MB for 0.79 MB of numbers at BH=384, S=512, a layer.
Where a head is one tile (``_row_stats``: the shapes of ``_fused_backward``,
the ones the copies bound; ``flash_fwd``, ``flash_bwd`` and ``flash_dbias``)
they are ``[BH, 1, S_q]``, a block of ``(1, 1, block_q)`` that Mosaic takes
because its second-to-last dimension is the whole dimension, laid out in
(1, 128) tiles at the size of its numbers; the kernel turns the row to a
column once a tile (``_stat_column``) and a column it formed to a row
before it writes it (``_stat_store``).  Every multi-pass call keeps the
columns, and the dQ and dK/dV passes know nothing else: such a call's
traced program is the one it was, so the steps of the long-sequence cells
keep their schedule and their memory (with rows there too the kernels gain
under a millisecond of 18.9 and XLA holds 254 MB more of the Moonlight
step's temporaries: ``_row_stats``).  Which layout a call takes is the
kernels' own business: ``_flash_forward`` hands the logsumexp out and
``_flash_backward`` takes it and a delta in as ``[BH, S_q]``, and the op's
``LSE`` is ``[B, H, S_q]`` either way.  And a bias that is the same for
every head of a sequence (a padding mask ``[B, 1, S_q, S_kv]``) whose
gradient nobody wants enters as ``[B, S_q, S_kv]`` and is read at block row
``i // H`` (``_kernel_bias``, ``_bias_row``), as the shared rotary key head
and a grouped key/value head are: no ``[BH, S_q, S_kv]`` copy (201 MB a
layer in the flash cell) is built or read, at any shape; where no kernel
has a tile the composition repeats it (``_reference_attention``).  A bias
with a head dimension, and one whose gradient is wanted (``flash_dbias``
writes a head's), go a block a head.

Operands in place (PR 38): where the kernels' operands lie in HBM.  A
Mosaic custom call takes its operands as they lie, and a program that
hands the op ``[B, H, S, D]`` spells ``fc -> reshape -> transpose`` before
it and the reverse after it: XLA runs each as a copy that writes 64
numbers on 128 lanes (twice the bytes), keeps the split Q, K and V for the
backward, and does the same for dO, dQ, dK and dV: 13.3 of the flash cell's
140 ms and 1.8 GB of its reserve (PERF.md section 6, PR 38).  An op with
``num_heads`` takes Q ``[B, S_q, H * D]``, K and V ``[B, S_kv, H * D]`` as
the projections' matmuls left them.  Where a head is one tile in forward
and backward (``_fused_backward``: a cell owns whole rows, nothing is
summed across cells) and the heads pack whole into 128 lanes
(``_in_place``), ``flash_fwd`` and ``flash_bwd`` run over those arrays:
grid ``(B, H * D // 128)``, blocks ``(1, S, 128)`` at ``(b, 0, g)`` for Q,
K, V, dO, O, dQ, dK and dV (``_cell_specs``).  A block's minor dimension
must be a multiple of 128 or the whole array's, and 64 of 768 is neither,
so a cell takes a PAIR of heads of 64 (one of 128, four of 32) and runs the
one-tile body on each head's lanes in turn (``_lanes``, a static slice at
lane 64 that Mosaic takes as written), storing into its lanes, so every
output leaves as full rows; the mask ``[B, S_q, S_kv]`` is the cell's one
block at row ``b`` and the statistics stay ``[B * H, 1, S_q]``, a row a
head.  Every other op in that layout (a sequence-parallel mesh, several
tiles a head, heads that do not pack, grouped heads, a rotary pair, a
wanted bias gradient, dropout the kernels do not draw) is split to ``[B,
H, S, D]`` inside the lowering and takes the path it took.

Dropout in the kernels (PR 40): an op in that layout with attention-
probability dropout (BERT as published, rate 0.1) runs the same two
kernels, which draw the keep mask themselves, per head a ``[S_q, S_kv]``
tile of bits from the TensorCore's generator seeded with the op's seed
and the head (``_keep_mask``; the seed is 32 bits of the op's own key,
``_dropout_seed``, which the grad op remakes, so ``flash_bwd`` draws what
``flash_fwd`` drew): O = (P M) V / ((1 - rate) l) with the undropped
logsumexp, and in the backward dV = (P M / (1 - rate))^T dO, dP = (dO
V^T) M / (1 - rate).  No mask exists in HBM.  The rate is static: at 0
nothing is drawn and the program is the one it was (the sha1 pin of
``tests/test_fused_attention_grad.py``).  It does so where a head's scores
outnumber the Q, K, V the kernels keep for it (``_drop_in_kernels``: S=512
at D=64, not S=128, where the step would hold 1.18 GB more than the
composition's) and the bias wants no gradient; every other dropout op
composes (``_attn_core``).  Interpreted, the bits are a counter hash
(``_hash_bits``): the core's generator has no interpreter.

Latent attention (PR 28): V's head size may differ from Q's and K's, and
a head may have a second, rotary part whose keys are ONE head shared by
the sequence's heads (``rope``: the kernels add ``qr kr^T`` to the scores
and read ``kr`` at block row ``i // H``, so no per-head copy of it and no
padding of 128 + 64 into one head size exists in HBM; the dK/dV pass
writes each head's ``dkr`` and the caller sums them).  At the Moonlight
cell's shapes (16 heads, S=4096, 128 + 64 | 128, bf16, causal) the dK/dV
pass's Q side — Q, its rotary part, dO and the two lane-padded column
statistics — is 7 MiB and asked for 16.4 MiB double-buffered inside the
step, so whole-sequence operands beyond ``_DOUBLE_BUFFER_MAX_BYTES`` take
one buffer (``_whole_seq``).  Under the causal mask without a bias the
three kernels walk the other side's tiles in ONE ``fori_loop`` bounded by
the diagonal instead of a static unroll with a ``cond`` a tile
(``_loops_over_blocks``): at S=4096 the unrolled bodies were most of a
step's tracing, lowering and Mosaic compile time (3.4 s to lower and 5.6 s
to compile a layer's three kernels against 0.4 and 0.6, compiled for a v5e
here); that dQ pass is one sweep and takes delta from the kept ``out``.
The rotary pair exists in that looped form alone (``_rope_runs_looped``):
an op with a pair and a bias, or without the causal mask, composes one head
size before the kernels (``_attention_route``).

Sliding window (PR 39): with ``window`` = W under the causal mask query
``i`` sees keys ``i - W < j <= i`` (``_in_band``).  The band bounds the
looped sweeps on the side the diagonal leaves open: the forward and the dQ
pass start at the k tile that holds the oldest key their block's FIRST row
sees (``_first_k_block``), the dK/dV pass stops after the q tile of the
youngest query that sees its block's LAST key (``_last_q_block``), and the
tiles on either edge are masked by the ONE ``_band_mask``: at S=16384 with
W=4096 a head visits 252 of the 528 tiles of 512.  A row that sees nothing
of its first tile leaves ``exp(0)`` terms in its running sums, which the
rescale multiplies by ``exp(-1e30 - m)`` = 0 when its own keys come (every
row sees itself).  A windowed call is always the looped pair of passes
(``_fused_backward`` is False: never ``flash_bwd``, never in place), has a
plan of its own (``_shape_key``'s last element) and runs under the scope
``attn_window``; what a cell HOLDS is the causal call's (the whole other
side stays in VMEM).  ``W >= S_kv`` is the causal call itself (``_band``),
traced as it was; beside a bias the op composes and masks the same band; a
rotary pair and the sequence-parallel islands refuse a window by name.
"""

import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from ..registry import register_grad_lower, register_op

_NEG = -1e30


# The two branches of each one-tile call ``_pallas_call`` was handed a
# ``cache_key`` for, by that key and the operands' avals: a step traces a
# kernel once for all its layers of one shape (PR 40: twelve layers' fresh
# ``flash_fwd`` / ``flash_bwd`` traces were most of what the kernels add to
# a step's set-up).
_CALLS = {}
_CALLS_MAX = 256


def _pallas_call(kernel, name, vmem_limit_bytes=None, platform_bound=False,
                 cache_key=None, **kwargs):
    """``pl.pallas_call`` whose mode follows the platform the computation
    is LOWERED for, not the process's default backend: compiled by Mosaic
    for a TPU, interpreted for anything else (a ``CPUPlace`` executor on a
    TPU host included).  ``lax.platform_dependent`` lowers only the chosen
    branch, so a TPU executable never holds an interpreted kernel.
    ``platform_bound``: the kernel body takes ``interpret=`` and is bound
    with the branch it runs in (``_keep_bits``: the core's generator has
    no interpreter).

    ``name`` is the kernel's stable name: XLA:TPU names the custom call's
    instruction after it (``%flash_dq.3`` in a device trace, where an
    unnamed kernel reads ``branch_1_fun``), and it is the named scope
    around the call in every instruction's ``op_name``.

    ``vmem_limit_bytes`` (``_vmem_limit``) raises Mosaic's scoped VMEM
    limit for this kernel; None leaves the compiler's default.

    Inside a ``shard_map`` the outputs vary over every mesh axis an input
    varies over; saying so in ``out_shape`` lets the kernels run under
    ``check_vma=True``.

    ``cache_key``: everything the kernel body, its grid and its blocks
    depend on beside the operands' avals (a caller that gives one keeps
    no operand in the body's closure): the branches are built once for it
    and reused (``_CALLS``), so ``pl.pallas_call``'s own jit traces the
    body once, and every call traces the same program as a fresh one."""
    out_shape = kwargs.pop("out_shape")
    mosaic = {} if vmem_limit_bytes is None else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes)}

    def body(interpret):
        return functools.partial(kernel, interpret=interpret) \
            if platform_bound else kernel

    def branches(vma):
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma),
            out_shape)
        return (pl.pallas_call(body(False), out_shape=shapes, name=name,
                               **mosaic, **kwargs),
                pl.pallas_call(body(True), out_shape=shapes, name=name,
                               interpret=True, **kwargs))

    def call(*args):
        vma = frozenset().union(*(jax.typeof(a).vma for a in args))
        if cache_key is None:
            tpu, default = branches(vma)
        else:
            key = (cache_key, name, vmem_limit_bytes,
                   platform_bound and _CHIP_BITS, vma,
                   tuple((a.shape, a.dtype) for a in args))
            if key not in _CALLS:
                if len(_CALLS) >= _CALLS_MAX:
                    _CALLS.clear()
                _CALLS[key] = branches(vma)
            tpu, default = _CALLS[key]
        with jax.named_scope(name):
            return jax.lax.platform_dependent(*args, tpu=tpu,
                                              default=default)
    return call


def _reference_attention(q, k, v, bias, scale, causal=False, window=0):
    """[BH, S, D] composition — the oracle and the vjp target.  A bias of
    fewer rows than ``q`` is one the heads of a sequence share
    (``_kernel_bias``: ``[B, S_q, S_kv]``), repeated here, so its
    cotangent comes back summed over a sequence's heads.  ``window``: the
    causal band (``_in_band``)."""
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if bias is not None:
        if bias.shape[0] != q.shape[0]:
            bias = jnp.repeat(bias, q.shape[0] // bias.shape[0], axis=0)
        s = s + bias
    if causal:
        allowed = _in_band(jnp.arange(q.shape[1])[:, None],
                           jnp.arange(k.shape[1])[None, :], window)
        s = jnp.where(allowed[None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _in_band(qpos, kpos, window=0):
    """Which keys a query sees under the causal mask: ``kpos <= qpos``, and
    with a sliding ``window`` W > 0 the last W of them alone, ``kpos > qpos
    - W`` (the query's own position among them).  0: no window."""
    allowed = qpos >= kpos
    return allowed & (kpos > qpos - window) if window else allowed


def _scores(q, ks, scale, qr=None, krs=None):
    """[bq, bk] float32 scaled scores of one tile: ``q ks^T``, plus
    ``qr krs^T`` where the head has a second (rotary) part whose keys
    ``krs`` are shared by every head of the sequence."""
    s = jnp.dot(q, ks.T, preferred_element_type=jnp.float32)
    if qr is not None:
        s = s + jnp.dot(qr, krs.T, preferred_element_type=jnp.float32)
    return s * scale


def _online_softmax(carry, s, vs, dtype):
    """Fold one tile into the row's running ``(m, l, acc)``: scores ``s``
    [bq, bk] float32, values ``vs`` [bk, D_v].  ``carry`` None is the row's
    first tile, which has nothing to rescale — a row that is ONE tile
    (block_k = S_kv) is a plain softmax."""
    m_tile = s.max(axis=-1, keepdims=True)
    if carry is None:
        p = jnp.exp(s - m_tile)
        return m_tile, p.sum(axis=-1, keepdims=True), \
            jnp.dot(p.astype(dtype), vs, preferred_element_type=jnp.float32)
    m, l, acc = carry
    m_new = jnp.maximum(m, m_tile)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    return m_new, l * alpha + p.sum(axis=-1, keepdims=True), \
        acc * alpha + jnp.dot(p.astype(dtype), vs,
                              preferred_element_type=jnp.float32)


def _attention_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                      scale, block_k, causal=False, qr_ref=None,
                      kr_ref=None, window=0):
    # dots run in the INPUT dtype (bf16 under pure-bf16 AMP — a single
    # fast MXU pass) and accumulate fp32 via preferred_element_type;
    # casting inputs to fp32 first forces multi-pass fp32 MXU emulation
    q = q_ref[0]                                  # [bq, D], native dtype
    qr = None if qr_ref is None else qr_ref[0]    # [bq, R]
    S = k_ref.shape[1]
    bq = q.shape[0]
    num_kb = S // block_k
    pid = pl.program_id(1)          # q-block index (hoisted: program_id
    #                                 is not available inside cond branches)

    if _loops_over_blocks(causal, bias_ref is not None):
        def step(kb, carry):
            rows = _rows(kb, block_k)
            s = _scores(q, k_ref[0, rows, :], scale, qr,
                        None if kr_ref is None else kr_ref[0, rows, :])
            s = _band_mask(s, pid * bq, kb * block_k, window)
            return _online_softmax(carry, s, v_ref[0, rows, :], q.dtype)
        # the k tiles that start at or before this q block's last row (and,
        # under a window, from the one its first row's band reaches: a row
        # that sees nothing of its first tile is flushed by the rescale
        # when its own keys come)
        m, l, acc = jax.lax.fori_loop(
            _first_k_block(pid, bq, block_k, window),
            jnp.minimum(pl.cdiv((pid + 1) * bq, block_k), num_kb), step,
            (jnp.full((bq, 1), _NEG, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32),
             jnp.zeros((bq, v_ref.shape[2]), jnp.float32)))
    else:
        carry = None
        for kb in range(num_kb):                      # static unroll
            ks = k_ref[0, kb * block_k:(kb + 1) * block_k, :]   # [bk, D]
            vs = v_ref[0, kb * block_k:(kb + 1) * block_k, :]

            def blk(carry, ks=ks, vs=vs, kb=kb):
                s = _scores(q, ks, scale)
                s = _add_bias(s, bias_ref, 0, bq, kb * block_k, block_k)
                if causal:
                    s = _band_mask(s, pid * bq, kb * block_k)
                return _online_softmax(carry, s, vs, q.dtype)

            if causal and kb:
                # tiles fully above the diagonal contribute nothing — skip
                # their dots (roughly halves causal attention FLOPs); the
                # first tile holds column 0, which every row sees
                live = (pid + 1) * bq > kb * block_k
                carry = jax.lax.cond(live, blk, lambda c: c, carry)
            else:
                carry = blk(carry)
        m, l, acc = carry
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # logsumexp per row — the statistic the tiled backward replays
    # against; inference (with_lse=False) omits the output entirely so
    # it pays neither the in-kernel log nor the fp32 per-row HBM write
    # (an unused output of a pallas_call is still computed)
    if lse_ref is not None:
        _stat_store(lse_ref, m + jnp.log(l))


def _rope_runs_looped(rope, causal, bias, window=0):
    """A rotary pair exists in the kernels' looped sweeps alone — under the
    causal mask, without a bias: the decoder's case.  ``_attention_route``
    composes one head size for every other op.  No model pairs one with a
    sliding window: refused by name."""
    if rope is not None and not _loops_over_blocks(causal,
                                                   bias is not None):
        raise ValueError("the flash kernels take a rotary pair only under "
                         "the causal mask and without a bias")
    if rope is not None and window:
        raise NotImplementedError("the flash kernels take a rotary pair or "
                                  "a sliding window, not both")



def _rows(block, size):
    """Rows ``block * size .. + size`` of a whole-sequence operand, for a
    traced block index."""
    return pl.ds(pl.multiple_of(block * size, size), size)


def _add_bias(s, bias_ref, rows, row_len, cols, col_len, h=0):
    """Scores plus the bias tile at static offsets, widened to float32.
    ``h``: the head, of those a cell holds (``_lanes``), whose tile it is;
    a block of one tile serves them all (a mask the heads of a sequence
    share)."""
    if bias_ref is None:
        return s
    return s + bias_ref[min(h, bias_ref.shape[0] - 1), rows:rows + row_len,
                        cols:cols + col_len].astype(jnp.float32)


def _band_mask(s, q0, k0, window=0):
    """Mask the scores outside the causal band (``_in_band``: above the
    diagonal and, under a ``window``, more than ``window - 1`` keys behind
    it) for a [bq, bk] block whose rows start at absolute position q0 and
    columns at k0.  Rank-2 iota (lax.broadcasted_iota) — Mosaic rejects
    rank-1 iota on TPU."""
    bq, bk = s.shape
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(_in_band(qpos, kpos, window), s, _NEG)


def _first_k_block(qb, block_q, block_k, window):
    """The first k tile q block ``qb`` visits in the looped sweeps: 0, and
    under a window the tile that holds the oldest key the block's FIRST row
    sees, ``qb * block_q - window + 1`` (the tiles before it are outside
    every row's band)."""
    if not window:
        return 0
    return jnp.maximum(qb * block_q - (window - 1), 0) // block_k


def _last_q_block(kb, block_k, block_q, num_qb, window):
    """One past the last q tile that visits k block ``kb`` in the dK/dV
    pass's looped sweep: every tile to the end, and under a window the tile
    of the youngest query that still sees the block's LAST key, ``(kb + 1)
    * block_k - 1 + window - 1``."""
    if not window:
        return num_qb
    return jnp.minimum(num_qb,
                       pl.cdiv((kb + 1) * block_k + window - 1, block_q))


def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
               dq_ref, delta_out_ref, p_scr, dp_scr, *, scale, block_k,
               causal=False, qr_ref=None, kr_ref=None, dqr_ref=None,
               window=0):
    """FlashAttention-2 backward, dQ pass: one q block vs all k tiles.
    p is recomputed from the saved LSE — no [S, S] materialization.

    delta_i = sum_d dO_id O_id = sum_j P_ij dP_ij.  With ``delta_ref`` the
    caller passed it and every k tile is one pass.  Without it
    (``delta_ref`` None) the kernel forms it from what it computes anyway:
    the row's P and dP tiles are held while P * dP is summed — as values
    where the row is ONE tile (block_k = S_kv), else in the ``[bq, S_kv]``
    float32 scratches ``p_scr`` / ``dp_scr`` — then dS and dQ come from
    the held tiles: the same five products, no ``out`` operand, and delta
    is written to ``delta_out_ref`` for the dK/dV and dbias passes.

    With a rotary part (``qr_ref`` [bq, R], ``kr_ref`` [S_kv, R], the keys
    shared by the sequence's heads; in the looped sweep only,
    ``_rope_runs_looped``) the scores hold the second dot and ``dqr_ref``
    takes ``dS kr``."""
    q = q_ref[0]                                   # [bq, D]
    qr = None if qr_ref is None else qr_ref[0]
    do = do_ref[0].astype(q.dtype)                 # [bq, D]
    lse = lse_ref[0]                               # [bq, 1] fp32
    S = k_ref.shape[1]
    bq, D = q.shape
    pid = pl.program_id(1)
    blocks = [(kb, slice(kb * block_k, (kb + 1) * block_k))
              for kb in range(S // block_k)]

    def live(kb):
        return (pid + 1) * bq > kb * block_k

    def tiles(kb, cols):
        s = _scores(q, k_ref[0, cols, :], scale)
        s = _add_bias(s, bias_ref, 0, bq, kb * block_k, block_k)
        if causal:
            s = _band_mask(s, pid * bq, kb * block_k)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v_ref[0, cols, :].T,
                     preferred_element_type=jnp.float32)
        return p, dp

    held = None
    if delta_ref is not None:
        delta = delta_ref[0]                       # [bq, 1] fp32
    elif len(blocks) == 1:
        held = tiles(*blocks[0])                   # the whole row, as values
        delta = (held[0] * held[1]).sum(axis=-1, keepdims=True)
    else:
        # tiles above the causal diagonal are skipped in both sweeps:
        # their P is zero, so they add nothing to delta or dQ
        w = jnp.zeros((bq, block_k), jnp.float32)
        for kb, cols in blocks:
            def hold(w, kb=kb, cols=cols):
                p, dp = tiles(kb, cols)
                p_scr[:, cols] = p
                dp_scr[:, cols] = dp
                return w + p * dp
            w = jax.lax.cond(live(kb), hold, lambda w: w, w) if causal \
                else hold(w)
        delta = w.sum(axis=-1, keepdims=True)      # [bq, 1] fp32
    if delta_ref is None:
        delta_out_ref[0] = delta

    acc = jnp.zeros((bq, D), jnp.float32)
    if _loops_over_blocks(causal, bias_ref is not None):
        # one sweep over the live k tiles; delta is passed in
        # (``_delta_in_kernel``), so nothing is held between two sweeps
        acc_r = None if qr_ref is None else jnp.zeros(qr.shape, jnp.float32)

        def step(kb, accs):
            rows = _rows(kb, block_k)
            ks = k_ref[0, rows, :]
            krs = None if kr_ref is None else kr_ref[0, rows, :]
            s = _band_mask(_scores(q, ks, scale, qr, krs), pid * bq,
                           kb * block_k, window)
            dp = jnp.dot(do, v_ref[0, rows, :].T,
                         preferred_element_type=jnp.float32)
            ds = (jnp.exp(s - lse) * (dp - delta) * scale).astype(q.dtype)
            acc, acc_r = accs
            acc = acc + jnp.dot(ds, ks, preferred_element_type=jnp.float32)
            if krs is not None:
                acc_r = acc_r + jnp.dot(ds, krs,
                                        preferred_element_type=jnp.float32)
            return acc, acc_r
        acc, acc_r = jax.lax.fori_loop(
            _first_k_block(pid, bq, block_k, window),
            jnp.minimum(pl.cdiv((pid + 1) * bq, block_k), S // block_k),
            step, (acc, acc_r))
        if qr_ref is not None:
            dqr_ref[0] = acc_r.astype(dqr_ref.dtype)
    else:
        def ds_tile(kb, cols):
            if delta_ref is not None:
                p, dp = tiles(kb, cols)
            else:
                p, dp = held if held is not None \
                    else (p_scr[:, cols], dp_scr[:, cols])
            return (p * (dp - delta) * scale).astype(q.dtype)

        for kb, cols in blocks:
            def blk(acc, kb=kb, cols=cols):
                return acc + jnp.dot(ds_tile(kb, cols), k_ref[0, cols, :],
                                     preferred_element_type=jnp.float32)
            acc = jax.lax.cond(live(kb), blk, lambda a: a, acc) if causal \
                else blk(acc)
    dq_ref[0] = acc.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, block_q, causal=False,
                qr_ref=None, kr_ref=None, dkr_ref=None, window=0):
    """dK/dV pass: one k block vs all q tiles.  With a rotary part
    (``qr_ref`` [S_q, R], ``kr_ref`` [bk, R]; in the looped sweep only)
    ``dkr_ref`` takes THIS head's ``dS^T qr`` in float32; the caller sums
    it over the heads that share the rotary keys."""
    ks = k_ref[0]                                  # [bk, D]
    vs = v_ref[0]
    krs = None if kr_ref is None else kr_ref[0]    # [bk, R]
    S = q_ref.shape[1]
    bk, D = ks.shape
    pid = pl.program_id(1)
    dk = jnp.zeros((bk, D), jnp.float32)
    dv = jnp.zeros(vs.shape, jnp.float32)
    num_qb = S // block_q

    def tile(carry, q, do, lse, delta, s, qr=None):
        """One [bq, bk] tile's part of dV, dK and (with ``qr``) dKr, from
        its float32 scores ``s``."""
        dk, dv = carry
        p = jnp.exp(s - lse)                       # [bq, bk]
        dv = dv + jnp.dot(p.astype(q.dtype).T, do,
                          preferred_element_type=jnp.float32)
        dp = jnp.dot(do, vs.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        if qr is None:
            return dk + jnp.dot(ds.T, q,
                                preferred_element_type=jnp.float32), dv
        return (dk[0] + jnp.dot(ds.T, q, preferred_element_type=jnp.float32),
                dk[1] + jnp.dot(ds.T, qr,
                                preferred_element_type=jnp.float32)), dv

    if _loops_over_blocks(causal, bias_ref is not None):
        if krs is not None:
            dk = (dk, jnp.zeros(krs.shape, jnp.float32))

        def step(qb, carry):
            rows = _rows(qb, block_q)
            q = q_ref[0, rows, :]
            qr = None if qr_ref is None else qr_ref[0, rows, :]
            s = _band_mask(_scores(q, ks, scale, qr, krs), qb * block_q,
                           pid * bk, window)
            return tile(carry, q, do_ref[0, rows, :], lse_ref[0, rows, :],
                        delta_ref[0, rows, :], s, qr)
        # from the first q tile whose last row reaches this k block (under
        # a window: to the last whose first row still sees it)
        dk, dv = jax.lax.fori_loop(
            (pid * bk) // block_q,
            _last_q_block(pid, bk, block_q, num_qb, window), step,
            (dk, dv))
        if krs is not None:
            dk, dkr = dk
            dkr_ref[0] = dkr.astype(dkr_ref.dtype)
    else:
        for qb in range(num_qb):
            rows = slice(qb * block_q, (qb + 1) * block_q)

            def blk(carry, rows=rows, qb=qb):
                q = q_ref[0, rows, :]
                s = _scores(q, ks, scale)
                s = _add_bias(s, bias_ref, qb * block_q, block_q, 0, bk)
                if causal:
                    s = _band_mask(s, qb * block_q, pid * bk)
                return tile(carry, q, do_ref[0, rows, :],
                            lse_ref[0, rows, :], delta_ref[0, rows, :], s)

            if causal:
                # q tiles entirely before this k block see none of it
                live = (qb + 1) * block_q > pid * bk
                dk, dv = jax.lax.cond(live, blk, lambda c: c, (dk, dv))
            else:
                dk, dv = blk((dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, delta_out_ref, *, scale, causal=False,
                heads=1, rate=0.0, seed_ref=None, interpret=False):
    """The whole backward of a head that is ONE tile (``_fused_backward``):
    S, P, dP and dS are formed once and feed all three gradients, where
    the dQ and dK/dV passes each rebuild them.  The same five products in
    the same dtypes as that pair: MXU operands in the input dtype, float32
    scores, exp and accumulation.

    delta comes from ``delta_ref`` where the caller passed it (it MUST pass
    it where K/V are a shard of the row), else it is the row sum of P * dP
    over the tile, which is the whole row; ``delta_out_ref`` takes it for
    the dbias pass.

    ``heads``: the heads a cell holds side by side on its blocks' lanes
    (``_in_place``: operands ``[B, S, H * D]``, a block 128 lanes wide);
    each runs this body on its lanes and stores into its lanes.

    ``rate`` (in place only): the forward's keep mask M, drawn again from
    the same seed, cell and head (``_keep_mask``).  dV = (P M / (1 -
    rate))^T dO and dP = (dO V^T) M / (1 - rate), the gradient of the
    undropped P; delta = rowsum(P dP) is still rowsum(dO O), and dS, dQ,
    dK follow as without it."""
    for h in range(heads):
        q, ks, vs = (_lanes(ref, h, heads)         # [S_q, D], [S_kv, D | D_v]
                     for ref in (q_ref, k_ref, v_ref))
        do = _lanes(do_ref, h, heads).astype(q.dtype)          # [S_q, D_v]
        s = _add_bias(_scores(q, ks, scale), bias_ref, 0, q.shape[0], 0,
                      ks.shape[0], h)
        if causal:
            s = _band_mask(s, 0, 0)
        p = jnp.exp(s - _stat_column(lse_ref, h))
        dp = jnp.dot(do, vs.T, preferred_element_type=jnp.float32)
        pv = p
        if rate:
            keep = _keep_mask(seed_ref, h, heads, s.shape, rate, interpret)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - rate)), 0.0)
            pv = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
        delta = (p * dp).sum(axis=-1, keepdims=True) if delta_ref is None \
            else _stat_column(delta_ref, h)
        if delta_out_ref is not None:
            _stat_store(delta_out_ref, delta, h)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        _store_lanes(dq_ref, h, heads,
                     jnp.dot(ds, ks, preferred_element_type=jnp.float32))
        _store_lanes(dk_ref, h, heads,
                     jnp.dot(ds.T, q, preferred_element_type=jnp.float32))
        _store_lanes(dv_ref, h, heads,
                     jnp.dot(pv.astype(q.dtype).T, do,
                             preferred_element_type=jnp.float32))


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *, scale,
                causal=False, heads=1, rate=0.0, seed_ref=None,
                interpret=False):
    """The forward of heads that are ONE tile each and lie side by side on
    their blocks' lanes (``_in_place``): what ``_attention_kernel`` does
    at one tile, a plain softmax with nothing to rescale, on each head's
    lanes in turn, so the output leaves as full rows.

    ``rate``: attention-probability dropout with the keep mask M drawn
    here (``_keep_mask``), O = (P M) V / ((1 - rate) l); ``l`` and the
    logsumexp are the undropped P's, as the composition's (``_attn_core``)
    softmax is taken before its mask."""
    for h in range(heads):
        q = _lanes(q_ref, h, heads)
        s = _add_bias(_scores(q, _lanes(k_ref, h, heads), scale), bias_ref,
                      0, q.shape[0], 0, k_ref.shape[1], h)
        if causal:
            s = _band_mask(s, 0, 0)
        if rate:
            m = s.max(axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = p.sum(axis=-1, keepdims=True)
            keep = _keep_mask(seed_ref, h, heads, s.shape, rate, interpret)
            acc = jnp.dot(jnp.where(keep, p, 0.0).astype(q.dtype),
                          _lanes(v_ref, h, heads),
                          preferred_element_type=jnp.float32)
            l = jnp.maximum(l, 1e-30)
            _store_lanes(o_ref, h, heads, acc / (l * (1.0 - rate)))
        else:
            m, l, acc = _online_softmax(None, s, _lanes(v_ref, h, heads),
                                        q.dtype)
            l = jnp.maximum(l, 1e-30)
            _store_lanes(o_ref, h, heads, acc / l)
        if lse_ref is not None:
            _stat_store(lse_ref, m + jnp.log(l), h)


# Where the flash kernels' dropout bits come from on the chip: "core", the
# TensorCore's own generator (``pltpu.prng_seed`` / ``prng_random_bits``),
# or "hash", the counter hash the interpreter draws (``_hash_bits``).
# ``flash_bench``'s ``dropout`` row times both (PERF.md section 6, PR 40).
_CHIP_BITS = "core"


def _keep_bits(seed, head, shape, interpret):
    """32 random bits (int32) for each score of one head's ``shape`` tile:
    ``head`` (its row of ``[B * H, ...]``, ``_keep_mask``) of a call
    seeded with ``seed`` (a scalar).  The forward and the backward call it
    with the same two and the same shape, so they draw the same bits.  On
    the chip the core's generator, seeded with both (Mosaic's
    ``prng_set_seed_32`` takes at most two values: the head is one number,
    not its grid cell and place in the cell); interpreted
    (``_pallas_call``'s ``platform_bound``: ``prng_seed`` has no CPU
    lowering, and the interpreter's ``prng_random_bits`` is all zeros) a
    counter hash of both and the element's row and column, which a test
    can rebuild outside the kernel."""
    if interpret or _CHIP_BITS == "hash":
        return _hash_bits(seed, head, shape)
    pltpu.prng_seed(seed, head)
    return pltpu.prng_random_bits(shape)


def _mix32(x):
    """A bijective avalanche of int32 lanes (Wellons' ``lowbias32``), with
    wrapping int32 products and logical shifts, as Mosaic has them; lax
    ops on numpy constants, which cost the least to trace."""
    lax = jax.lax
    for shift, factor in ((16, 0x7FEB352D), (15, 0x846CA68B), (16, None)):
        x = lax.bitwise_xor(x, lax.shift_right_logical(x, np.int32(shift)))
        if factor is not None:
            x = lax.mul(x, np.uint32(factor).view(np.int32))
    return x


def _hash_bits(seed, head, shape):
    """``_keep_bits``' counter hash: the seed and the head mixed in turn,
    then each element's index ``row * cols + col`` mixed with the result."""
    lax = jax.lax
    key = _mix32(lax.add(_mix32(lax.convert_element_type(seed, np.int32)),
                         lax.convert_element_type(head, np.int32)))
    index = lax.add(
        lax.mul(lax.broadcasted_iota(np.int32, shape, 0), np.int32(shape[1])),
        lax.broadcasted_iota(np.int32, shape, 1))
    return _mix32(lax.bitwise_xor(index, key))


def _keep_threshold(rate):
    """The keep rule's bound, ``round((1 - rate) * 2**32)``, moved by
    ``-2**31`` into int32: unsigned ``bits < t`` is signed ``bits ^ -2**31 <
    t - 2**31``, so a keep is drawn with probability ``1 - rate`` to
    ``2**-32``."""
    t = min(int(round((1.0 - rate) * 2.0 ** 32)), 2 ** 32 - 1)
    return t - 2 ** 31


def _keep_mask(seed_ref, h, heads, shape, rate, interpret):
    """Which scores of head ``h`` of the ``heads`` a grid cell ``(b, g)``
    holds (``_in_place``) survive dropout at ``rate`` (bool ``shape``):
    the head is row ``(b * cells + g) * heads + h`` of ``[B * H, ...]``,
    its bits (``_keep_bits``) are kept below the threshold, compared
    without a sign (``_keep_threshold``)."""
    head = (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)) * \
        heads + h
    bits = _keep_bits(seed_ref[0], head, shape, interpret)
    return jax.lax.lt(jax.lax.bitwise_xor(bits, np.int32(-2 ** 31)),
                      np.int32(_keep_threshold(rate)))


def _drawn_mask(seed, batch, heads, D, S_q, S_kv, rate):
    """The keep masks ``flash_fwd`` / ``flash_bwd`` in place draw for
    operands ``[batch, S, heads * D]`` from ``seed`` (int32 ``[1]``), read
    back as int32 ``[batch * heads, S_q, S_kv]`` (1 kept) by a kernel of
    the same grid that draws them as the attention kernels do, on the chip
    from the core's generator: the oracle of ``chip_smoke.py`` and the
    tests."""
    pack = _LANES // D
    cells = heads // pack

    def kern(seed_ref, mask_ref, interpret):
        for h in range(pack):
            mask_ref[h] = _keep_mask(seed_ref, h, pack, (S_q, S_kv), rate,
                                     interpret).astype(jnp.int32)

    return _pallas_call(
        kern, "flash_keep_mask", platform_bound=True,
        grid=(batch, cells),
        in_specs=[_seed_spec()],
        out_specs=pl.BlockSpec((pack, S_q, S_kv),
                               lambda b, g: (b * cells + g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((batch * heads, S_q, S_kv),
                                       jnp.int32),
    )(seed)


def _lanes(ref, h, heads):
    """Head ``h`` of a block ``[1, S, heads * D]`` whose lanes hold
    ``heads`` heads side by side: ``[S, D]``.  One head a block is the
    block."""
    if heads == 1:
        return ref[0]
    D = ref.shape[2] // heads
    return ref[0, :, h * D:(h + 1) * D]


def _store_lanes(ref, h, heads, value):
    """Write head ``h``'s ``[S, D]`` result into its lanes of the block
    (``_lanes``), in the block's dtype."""
    if heads == 1:
        ref[0] = value.astype(ref.dtype)
    else:
        D = ref.shape[2] // heads
        ref[0, :, h * D:(h + 1) * D] = value.astype(ref.dtype)


def _dbias_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                  delta_ref, db_ref, *, scale, block_k, causal=False):
    """d(bias) = ds, recomputed tile-wise.  Its output is [S, S]-sized by
    definition (the gradient OF the [S, S] bias); a separate pallas_call
    so XLA drops the whole pass when the bias is not trainable."""
    q = q_ref[0]
    do = do_ref[0].astype(jnp.float32)
    lse = _stat_column(lse_ref)
    delta = _stat_column(delta_ref)
    S = k_ref.shape[1]
    bq, D = q.shape
    pid = pl.program_id(1)
    for kb in range(S // block_k):
        ks = k_ref[0, kb * block_k:(kb + 1) * block_k, :]
        vs = v_ref[0, kb * block_k:(kb + 1) * block_k, :]

        def blk(ks=ks, vs=vs, kb=kb):
            s = jnp.dot(q, ks.T,
                        preferred_element_type=jnp.float32) * scale
            s = _add_bias(s, bias_ref, 0, bq, kb * block_k, block_k)
            if causal:
                s = _band_mask(s, pid * bq, kb * block_k)
            p = jnp.exp(s - lse)
            dp = jnp.dot(do.astype(q.dtype), vs.T,
                         preferred_element_type=jnp.float32)
            return p * (dp - delta)

        if causal:
            live = (pid + 1) * bq > kb * block_k
            ds = jax.lax.cond(
                live, blk,
                lambda: jnp.zeros((bq, block_k), jnp.float32))
        else:
            ds = blk()
        db_ref[0, :, kb * block_k:(kb + 1) * block_k] = \
            ds.astype(db_ref.dtype)


def _row_stats(kernel, *shape):
    """Whether ``kernel``'s row statistics (logsumexp, delta) at this shape
    (``_shape_key``) cross HBM as lane-dense rows ``[BH, 1, S_q]`` or as
    the columns ``[BH, S_q, 1]`` the kernels broadcast against a score tile
    as they are: rows in ``flash_fwd``, ``flash_bwd`` and ``flash_dbias``
    where a head is one tile in both backward passes (``_fused_backward``:
    one statistic block a head), columns in every multi-pass call.  The dQ
    and dK/dV passes know columns alone: no op runs them where a head is
    one tile.  The layout is these kernels' own: ``_flash_forward`` hands a
    statistic out and ``_flash_backward`` takes one in as ``[BH, S_q]``.

    XLA:TPU pads a column's size-1 minor dimension to 128 lanes: 100.7 MB
    for 0.79 MB of numbers at BH=384, S=512, written by the forward and
    read by the backward in every layer, which at one tile a head bounds
    the kernels (PERF.md section 6, PRs 33 and 36); a row is laid out in
    (1, 128) tiles at the size of its numbers.  The multi-pass calls keep
    the columns because their steps must keep their memory: with rows
    everywhere the long-sequence kernels gain little (-0.84 of 18.9 ms in
    the Moonlight cell) while XLA schedules the SAME step otherwise around
    the changed custom calls, 254 MB more of temporaries there (ledger, PR
    35; the same compile here, with or without the barriers of
    ``_forward_keeping_lse``: PERF.md section 6, PR 36).  From the shape
    alone: the two schedules of the backward already part there."""
    return kernel in ("fwd", "bwd", "dbias") and _fused_backward(*shape)


def _row_stat_spec(block_q, rows=False):
    """Block of a per-row statistic: ``(1, block_q, 1)`` of ``[BH, S_q,
    1]``, rows on sublanes, which a ``[bq, bk]`` score tile broadcasts
    against without a relayout; with ``rows`` (``_row_stats``) ``(1, 1,
    block_q)`` of ``[BH, 1, S_q]``.  (A [BH, S_q] array cut into
    (1, block_q) blocks is refused by the Mosaic lowering: the
    second-to-last block dim must be a multiple of 8 or the whole dim; in
    the row layout it is the whole dim, 1.)"""
    if rows:
        return pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j))
    return pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0))


def _kernel_stat(stat, rows):
    """A statistic ``[BH, S_q]`` (or None) as a kernel takes it: ``[BH, 1,
    S_q]`` with ``rows`` (``_row_stats``), else ``[BH, S_q, 1]``."""
    if stat is None:
        return None
    return stat[:, None] if rows else stat[..., None]


def _stat_column(ref, h=0):
    """A statistic's block as the float32 column ``[bq, 1]`` a ``[bq,
    bk]`` score tile broadcasts against: read as it lies from a column
    block ``[1, bq, 1]``; a row block ``[heads, 1, bq]`` (head ``h``'s row)
    is turned once a tile, one number a row against the tile's ``bq x bk``
    scores."""
    return ref[h].T if ref.shape[1] == 1 else ref[h]


def _stat_store(ref, column, h=0):
    """Write a ``[bq, 1]`` column the kernel formed to its statistic's
    block, turned to a row where the block is one (head ``h``'s)."""
    ref[h] = column.T if ref.shape[1] == 1 else column


# Tile sides the chooser tries, largest first.  128 is the least the
# (8, 128) layout allows; 512 x 512 is where a float32 score tile is 1 MiB.
_TILE_SIDES = (512, 256, 128)

# What one kernel may ask of VMEM by the chooser's estimate: a quarter of a
# v5e TensorCore's 128 MiB.  The compiler's scoped default is 16 MiB, a
# default and not the chip: past it ``_vmem_limit`` raises the kernel's
# own limit to what the estimate says.
_VMEM_BUDGET_BYTES = 32 << 20
_VMEM_SCOPED_DEFAULT_BYTES = 16 << 20

# VMEM the whole-sequence operands of one grid cell may take when double-
# buffered (the pipeline's default) before ``_whole_seq`` single-buffers
# them.
_DOUBLE_BUFFER_MAX_BYTES = 12 << 20

# Longest S_kv at which the dQ pass holds the row's P and dP tiles (2 x
# block_q x S_kv x 4 bytes of VMEM scratch) to form delta itself; beyond
# it the caller keeps ``out`` and passes delta in (module docstring).
_DELTA_IN_KERNEL_MAX_SKV = 4096


def _lane_padded_bytes(rows, cols, itemsize):
    """VMEM bytes of a [rows, cols] operand: the minor dimension is laid
    out in whole 128-lane tiles (a [S, 1] float32 statistic costs what a
    [S, 128] one does; as a row it is [1, S]: ``_row_stats``)."""
    return rows * -(-cols // 128) * 128 * itemsize


def _whole_seq(operands):
    """``pipeline_mode`` keywords for the BlockSpecs of a pass's
    whole-sequence operands (``(rows, cols, itemsize)`` each).  Their block
    index changes only with the grid's outer index (one head of one
    sequence), yet the pipeline double-buffers them; where that would take
    more than ``_DOUBLE_BUFFER_MAX_BYTES`` they get ONE buffer: the next
    head's copy then waits for the last cell of this one, once a head."""
    need = 2 * sum(_lane_padded_bytes(*o) for o in operands)
    if need <= _DOUBLE_BUFFER_MAX_BYTES:
        return {}
    return {"pipeline_mode": pl.Buffered(1)}


def _loops_over_blocks(causal, has_bias):
    """Whether a kernel walks the other side's tiles in a ``fori_loop``
    bounded by the causal diagonal instead of a static unroll with a
    ``cond`` a tile: under the causal mask and without an additive bias
    (a bias tile would need a dynamic slice along lanes).  One traced body
    instead of S / block: at S=4096 the unrolled kernels took most of a
    step's tracing, lowering and Mosaic compile time, and the tiles above
    the diagonal are never visited."""
    return causal and not has_bias


def _delta_in_kernel(S_kv, causal=False, has_bias=False):
    """Whether the dQ pass forms delta itself (from held P and dP tiles)
    and the forward keeps no ``out``.  Not where it walks the k tiles in a
    loop (``_loops_over_blocks``): that pass is one sweep and takes delta
    from ``_row_delta`` of the ``out`` the forward keeps."""
    return S_kv <= _DELTA_IN_KERNEL_MAX_SKV and \
        not _loops_over_blocks(causal, has_bias)


def _whole_side(kernel, S_q, S_kv, D, D_v, R, itemsize):
    """The whole-sequence operands of one grid cell, ``(rows, cols,
    itemsize)`` each: K, V and the shared rotary keys for the passes over
    q blocks; Q, dO, Q's rotary part and both row statistics for the dK/dV
    pass; none for the fused backward, whose one cell owns the head.  (No
    rotary part: R = 0, an operand of no bytes.)"""
    if kernel == "bwd":
        return []
    if kernel == "dkv":
        return [(S_q, D, itemsize), (S_q, D_v, itemsize), (S_q, R, itemsize),
                (S_q, 1, 4), (S_q, 1, 4)]
    return [(S_kv, D, itemsize), (S_kv, D_v, itemsize), (S_kv, R, itemsize)]


def _vmem_bytes(kernel, block_q, block_k, S_q, S_kv, D, D_v, R, has_bias,
                causal, itemsize, group=1, window=0, rows=False, heads=1,
                bits=False):
    """VMEM one grid cell of ``kernel`` ('fwd', 'dq', 'dkv', 'bwd' or
    'dbias') asks for at tiles of ``block_q x block_k``, from shapes alone:
    what the chooser holds against ``_VMEM_BUDGET_BYTES`` and
    ``_vmem_limit`` hands the compiler.  Every operand is lane-padded
    (``_lane_padded_bytes``); a row statistic is ``[block_q, 1]``.  With
    ``rows`` (``_row_stats``: 'fwd', 'bwd', 'dbias' where a head is one
    tile) its block is ``[1, block_q]``, and each one the kernel reads is
    turned to a ``[block_q, 1]`` column as a value (``_stat_column``).  The
    chooser counts columns, the larger count, because ``_row_stats`` is
    read off the chooser's own answer; ``_plan`` counts what the call
    really holds.  With ``heads`` ('fwd' and 'bwd' reading ``[B, S, H *
    D]`` in place, ``_in_place``) a cell's blocks hold that many heads
    side by side: every block of Q, K, V, dO and the outputs is ``heads``
    times as wide and each statistic has a row a head, while the score
    tiles below stay one head's, the heads taking their turns.  A sliding
    ``window`` bounds the sweeps, not what a cell holds: the whole other
    side stays in VMEM, so the count is the causal call's.

    * the cell's own blocks, in and out, twice (the pipeline's two
      buffers): ``block_q`` rows of Q, its rotary part, dO, the row
      statistics and the outputs; in the dK/dV pass ``block_k`` rows of K,
      V, the rotary keys and dK, dV (float32 where ``group`` query heads
      share a key/value head: a partial a query head, ``_flash_dkv``), and
      the per-head float32 dKr; in the fused backward ('bwd': ``block_q``
      = S_q, ``block_k`` = S_kv) Q, K,
      V, dO, both row statistics and dQ, dK, dV;
    * the bias tile, twice: ``[block_q, S_kv]``, ``[S_q, block_k]`` in the
      dK/dV pass, and as much again for the dbias pass's output;
    * the whole other side (``_whole_side``), twice or once as
      ``_whole_seq`` decides;
    * the float32 tiles a step of the sweep holds at once — scores, P, dP
      and dS, the widened bias tile — at ``block_q x block_k x 4`` each,
      and the copies in the input dtype that feed the MXU (P, dS, and in
      the dK/dV pass and the fused backward their transposes);
    * the float32 accumulators, and the forward's two running statistics;
    * the dQ pass's two ``[block_q, S_kv]`` float32 scratches, where it
      forms delta over more than one tile (``_delta_in_kernel``);
    * with ``bits`` (dropout drawn in the kernel, ``_keep_mask``) the int32
      bits of a tile and the float32 tile the mask leaves."""
    def stat(b):
        return (heads, b, 4) if rows else (b, 1, 4)
    D, D_v = D * heads, D_v * heads
    if kernel == "bwd":
        own = [(block_q, D, itemsize), (block_q, D_v, itemsize),   # Q, dO
               stat(block_q), stat(block_q),                       # lse, delta
               (block_k, D, itemsize), (block_k, D_v, itemsize),   # K, V
               (block_q, D, itemsize), (block_k, D, itemsize),     # dQ, dK
               (block_k, D_v, itemsize)]                           # dV
        bias_tile = (block_q, block_k, itemsize)
        acc = [(block_q, D, 4), (block_k, D, 4), (block_k, D_v, 4)]
        wide, narrow = 4, 4
    elif kernel == "dkv":
        b = block_k
        out = itemsize if group == 1 else 4
        own = [(b, D, itemsize), (b, D_v, itemsize), (b, R, itemsize),  # in
               (b, D, out), (b, D_v, out), (b, R, 4)]                   # out
        bias_tile = (S_q, b, itemsize)
        acc = [(b, D, 4), (b, D_v, 4), (b, R, 4)]
        wide, narrow = 4, 4
    else:
        b = block_q
        own = [(b, D, itemsize), (b, R, itemsize), stat(b)]    # Q, Qr, lse
        bias_tile = (b, S_kv, itemsize)
        if kernel == "fwd":
            own += [(b, D_v, itemsize)]                        # out
            acc = [(b, D_v, 4), (b, 1, 4), (b, 1, 4)]          # acc, m, l
            wide, narrow = 2, 1
        else:
            own += [(b, D_v, itemsize), stat(b)]               # dO, delta
            if kernel == "dq":
                own += [(b, D, itemsize), (b, R, itemsize)]    # dQ, dQr
                acc = [(b, D, 4), (b, R, 4)]
                wide, narrow = 4, 1
            else:
                own += [bias_tile]                             # dbias
                acc = []
                wide, narrow = 4, 0
    if has_bias:
        own += [bias_tile]
    whole = _whole_side(kernel, S_q, S_kv, D, D_v, R, itemsize)
    need = 2 * sum(_lane_padded_bytes(*o) for o in own)
    need += (1 if _whole_seq(whole) else 2) * sum(
        _lane_padded_bytes(*o) for o in whole)
    need += block_q * block_k * (4 * (wide + has_bias) + itemsize * narrow)
    need += sum(_lane_padded_bytes(*o) for o in acc)
    if rows and kernel != "fwd":
        need += 2 * _lane_padded_bytes(block_q, 1, 4)     # lse, delta turned
    if kernel == "dq" and S_kv > block_k and \
            _delta_in_kernel(S_kv, causal, has_bias):
        need += 2 * block_q * S_kv * 4
    if bits:
        need += block_q * block_k * (4 + 4)
    return need


def _vmem_limit(need):
    """The ``vmem_limit_bytes`` a kernel whose estimate is ``need`` is
    compiled with: none while an eighth over the estimate stays within the
    compiler's scoped default, else the estimate and that eighth (what the
    estimate cannot see: Mosaic's own scratch, an operand XLA parks in
    VMEM inside a step)."""
    limit = need + need // 8
    return None if limit <= _VMEM_SCOPED_DEFAULT_BYTES else limit


def _tiles(kernel, S_q, S_kv, D, D_v, R, has_bias, causal, itemsize,
           group=1, window=0):
    """``(ok, block_q, block_k)`` of one kernel ('fwd', 'dq', 'dkv',
    'bwd', 'dbias') at one shape: the largest tile, sides from
    ``_TILE_SIDES`` that divide the sequence, whose ``_vmem_bytes`` fits
    ``_VMEM_BUDGET_BYTES``.  A grid cell's fixed cost (the pipeline's step,
    the serial matmul -> row max -> exp -> row sum -> matmul round, MXU
    weights loaded for the streamed rows) is paid per tile, not per FLOP,
    so the largest tile wins; of two equal areas the one with more rows of
    the grid's own side, which cuts the cells.  ``ok`` False: no side
    divides a sequence (the caller composes), or nothing fits.

    'bwd' is the fused backward, whose one tile is the whole head where
    ``_fused_backward`` lets it run.  A sliding ``window`` exists in the
    looped sweeps alone (under the causal mask, without a bias): with a
    bias no kernel has a tile, and the caller composes."""
    if window and not _loops_over_blocks(causal, has_bias):
        return False, min(_TILE_SIDES[-1], S_q), min(_TILE_SIDES[-1], S_kv)
    if kernel == "bwd":
        return _fused_backward(S_q, S_kv, D, D_v, R, has_bias, causal,
                               itemsize, group, window), S_q, S_kv

    def sides(S):
        # a sequence shorter than the least side is one block
        return [S] if S < _TILE_SIDES[-1] else \
            [t for t in _TILE_SIDES if S % t == 0]

    def size(tile):
        return tile[0] * tile[1], tile[kernel == "dkv"]
    for block_q, block_k in sorted(
            ((bq, bk) for bq in sides(S_q) for bk in sides(S_kv)),
            key=size, reverse=True):
        if _vmem_bytes(kernel, block_q, block_k, S_q, S_kv, D, D_v, R,
                       has_bias, causal, itemsize, group,
                       window) <= _VMEM_BUDGET_BYTES:
            return True, block_q, block_k
    return False, min(_TILE_SIDES[-1], S_q), min(_TILE_SIDES[-1], S_kv)


def _fused_backward(S_q, S_kv, D, D_v, R, has_bias, causal, itemsize,
                    group=1, window=0):
    """Whether the backward at this shape (``_shape_key``) is ONE kernel
    (``_bwd_kernel``) and not the dQ pass followed by the dK/dV pass, from
    the shape alone: where the chooser gives BOTH passes a tile that covers
    the whole of ``S_q`` and ``S_kv`` (each a grid of ``(BH, 1)``: neither
    gradient is accumulated across cells, so nothing forces two passes that
    each rebuild S, P, dP and dS), there is no rotary pair (it exists in
    the looped sweeps alone), no group of query heads over one key/value
    head (dK and dV are then sums over the group: ``_flash_dkv``), no
    sliding window (the band's bounds live in the looped sweeps) and the
    kernel's own estimate fits the budget.  S <= 512 at the BERT widths,
    bias or none, causal or not (the mask on the one tile: there is no
    diagonal to skip).  These are also the shapes whose kernels can read
    ``[B, S, H * D]`` operands in place (``_in_place``): a cell that owns
    its heads' whole rows may as well own several heads' lanes."""
    shape = (S_q, S_kv, D, D_v, R, has_bias, causal, itemsize)
    return not R and group == 1 and not window and \
        all(_tiles(kernel, *shape) == (True, S_q, S_kv)
            for kernel in ("dq", "dkv")) and \
        _vmem_bytes("bwd", S_q, S_kv, *shape) <= _VMEM_BUDGET_BYTES


# The lanes of a vector register, and the least a block's minor dimension
# may be short of the whole array's.
_LANES = 128


def _in_place(heads, *shape):
    """Whether the kernels at this shape (``_shape_key``) read Q, K, V and
    dO as ``[B, S, heads * D]``, where a projection's matmul left them, and
    write O, dQ, dK and dV the same way, where the next matmul reads them:
    no head split before the kernels and no merge after them, which XLA
    runs as copies that pad 64 numbers to 128 lanes and keeps for the
    backward (PERF.md section 6, PR 38).  Where a head is one tile in
    forward and backward (``_fused_backward``: a cell owns its heads'
    whole rows, so nothing is summed across cells) and the heads pack
    whole into a block of 128 lanes: a block's minor dimension must be a
    multiple of 128 or the array's own, and 64 of ``H * 64`` is neither,
    so a grid cell takes ``128 // D`` heads, a PAIR at D = 64, one at D =
    128, and runs them in turn on their lanes (``_lanes``).  V's head
    size must be Q's (one block index serves every operand), and the
    wider blocks must fit the budget."""
    S_q, S_kv, D, D_v = shape[:4]
    if not _fused_backward(*shape) or D != D_v or _LANES % D or \
            heads % (_LANES // D):
        return False
    return all(_vmem_bytes(kernel, S_q, S_kv, *shape, rows=True,
                           heads=_LANES // D) <= _VMEM_BUDGET_BYTES
               for kernel in ("fwd", "bwd"))


def _in_place_shape(q, k, bias, causal, heads):
    """``_shape_key`` of operands ``[B, S, heads * D]``."""
    D = q.shape[2] // heads
    return (q.shape[1], k.shape[1], D, D, 0, bias is not None, bool(causal),
            q.dtype.itemsize, 1, 0)


def _cell_specs(q, k, bias, heads):
    """``(grid, heads a cell, rows, stat, bias spec)`` of a kernel whose
    one tile is the head (``flash_bwd``, and ``flash_fwd`` in place):
    ``rows(n, d)`` the BlockSpec of the cell's ``n`` rows of an operand
    whose heads are ``d`` wide, ``stat`` that of its row statistics ``[BH,
    1, S_q]``.

    ``heads`` None: operands ``[BH, S, D]``, grid ``(BH,)``, a head a
    cell.  Else ``[B, S, heads * D]`` read in place (``_in_place``): grid
    ``(B, heads * D // 128)``, blocks ``(1, S, 128)`` at ``(b, 0, g)``, the
    statistics of the cell's ``128 // D`` heads ``128 // D`` consecutive
    rows, a bias of ``[B, S_q, S_kv]`` the one tile of sequence ``b`` for
    all of them and one of ``[BH, S_q, S_kv]`` a tile a head."""
    S_q, S_kv = q.shape[1], k.shape[1]
    bias_spec = None
    if heads is None:
        if bias is not None:
            row = _bias_row(q, bias)
            bias_spec = pl.BlockSpec((1, S_q, S_kv), lambda i: (row(i), 0, 0))
        return ((q.shape[0],), 1,
                lambda n, d: pl.BlockSpec((1, n, d), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, 1, S_q), lambda i: (i, 0, 0)), bias_spec)
    pack = _LANES // (q.shape[2] // heads)
    cells = heads // pack
    if bias is not None and bias.shape[0] == q.shape[0]:
        bias_spec = pl.BlockSpec((1, S_q, S_kv), lambda b, g: (b, 0, 0))
    elif bias is not None:
        bias_spec = pl.BlockSpec((pack, S_q, S_kv),
                                 lambda b, g: (b * cells + g, 0, 0))
    return ((q.shape[0], cells), pack,
            lambda n, d: pl.BlockSpec((1, n, d * pack),
                                      lambda b, g: (b, 0, g)),
            pl.BlockSpec((pack, 1, S_q), lambda b, g: (b * cells + g, 0, 0)),
            bias_spec)


def _seed_spec():
    """The block of a kernel's dropout seed (int32 ``[1]``,
    ``_dropout_seed``): the whole array in SMEM, where a scalar is read."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _shape_key(q, k, v, bias, causal, rope, window=0):
    """What the chooser sees of a call: ``_tiles``'s arguments after the
    kernel's name (the last two: the query heads that share a key/value
    head, and the sliding window, so a windowed call never shares a plan
    with a causal one)."""
    return (q.shape[1], k.shape[1], q.shape[2], v.shape[2],
            0 if rope is None else rope[0].shape[2], bias is not None,
            bool(causal), q.dtype.itemsize, q.shape[0] // k.shape[0],
            int(window))


def _band(window, causal, S_kv):
    """The sliding window a call runs with: ``window`` keys back from the
    query, itself among them; 0 (none) where the window covers the whole
    sequence, which IS the causal call.  A window needs the causal mask."""
    window = int(window or 0)
    if window < 0 or (window and not causal):
        raise ValueError("attention: window=%d needs causal=True and a "
                         "positive size" % window)
    return 0 if window >= S_kv else window


@contextlib.contextmanager
def _window_scope(window):
    """``attn_window`` around a windowed call's kernels, so a device trace
    tells them from the full layers' (the same kernel names)."""
    if not window:
        yield
        return
    with jax.named_scope("attn_window"):
        yield


def _flash_fits(*shape):
    """Whether every kernel of forward and backward has a tile at this
    shape (``_shape_key``); else the caller composes."""
    return all(_tiles(kernel, *shape)[0]
               for kernel in ("fwd", "dq", "dkv") + ("dbias",) * shape[5])


def _plan(kernel, q, bias, *shape, heads=None, rate=0.0):
    """One kernel call's ``(block_q, block_k, keywords for the whole-
    sequence BlockSpecs, vmem_limit_bytes)`` at this shape
    (``_shape_key``), counted in ``flash_tiles_total`` with the layout of
    its operands (``heads``: the H of ``[B, S, H * D]`` operands read in
    place, ``_in_place``; None for ``[BH, S, D]``), of its row statistics
    (``_row_stats``), which block row its bias is read at (``_bias_row``)
    and whether it draws a dropout mask (``rate``, in place only)."""
    _, block_q, block_k = _tiles(kernel, *shape)
    rows = _row_stats(kernel, *shape)
    _m_tiles.inc(kernel=kernel, block_q=block_q, block_k=block_k,
                 window=shape[9],
                 stats="row" if rows else "column",
                 bias="none" if bias is None
                 else "head" if bias.shape[0] == q.shape[0] * (heads or 1)
                 else "sequence",
                 layout="bhsd" if heads is None else "bshd",
                 dropout="in_kernel" if rate else "none")
    S_q, S_kv, D, D_v, R, _, _, itemsize = shape[:8]
    return (block_q, block_k,
            _whole_seq(_whole_side(kernel, S_q, S_kv, D, D_v, R, itemsize)),
            _vmem_limit(_vmem_bytes(
                kernel, block_q, block_k, *shape, rows=rows,
                heads=1 if heads is None else _LANES // D, bits=bool(rate))))


_m_tiles = telemetry.counter(
    "flash_tiles_total",
    "flash kernel calls traced, by kernel ('fwd', 'dq', 'dkv', 'dbias'; "
    "'bwd': dQ, dK and dV in one call, where a head is one tile) and the "
    "tile the chooser picked for the call's shape: block_q rows of Q by "
    "block_k rows of K a step of the sweep; stats: how the row statistics "
    "cross HBM ('row': [BH, 1, S_q], lane-dense, in 'fwd', 'bwd' and "
    "'dbias' where a head is one tile; 'column': [BH, S_q, 1], every "
    "multi-pass call, and 'dq' and 'dkv' always); bias: 'sequence' where "
    "one [S_q, S_kv] bias is read by every head of a sequence at block row "
    "i // H, 'head' where each head has its own, 'none'; layout: 'bhsd' "
    "where the operands are [BH, S, D], a head a cell, 'bshd' where 'fwd' "
    "and 'bwd' read Q, K, V and dO as [B, S, H * D] where the projections "
    "left them and write O, dQ, dK and dV the same way, 128 // D heads a "
    "cell; window: the sliding window whose band bounds the call's sweeps "
    "(0: none, the causal or the full call); dropout: 'in_kernel' where "
    "'fwd' or 'bwd' in place draws the attention-probability keep mask "
    "itself, 'none' everywhere else")


def _rope_specs(rope, q_block, k_block, whole_mode=None):
    """Block specs of a rotary pair ``(qr [BH, S_q, R], kr [B, S_kv, R])``:
    ``qr`` cut like Q (``q_block`` rows), ``kr`` like K, except that grid
    row ``i`` (one head of one sequence) reads the ONE rotary key head of
    its sequence, ``i // H``.  ``None`` for a block = the whole sequence
    (``whole_mode``: ``_whole_seq``'s keywords for it)."""
    qr, kr = rope
    heads = qr.shape[0] // kr.shape[0]
    R = qr.shape[2]

    def spec(rows, whole, row_of):
        if rows is None:
            return pl.BlockSpec((1, whole, R), lambda i, j: (row_of(i), 0, 0),
                                **(whole_mode or {}))
        return pl.BlockSpec((1, rows, R), lambda i, j: (row_of(i), j, 0))
    return [spec(q_block, qr.shape[1], lambda i: i),
            spec(k_block, kr.shape[1], lambda i: i // heads)]


def _kv_row(q, k):
    """Grid row ``i`` (one query head of one sequence, Q ``[B * H, S, D]``)
    -> the block row of its key/value head in K and V ``[B * H_kv, S, D]``:
    with ``G = H / H_kv`` query heads to a key/value head, head ``h`` reads
    head ``h // G``, and ``(i // H) * H_kv + (i % H) // G`` is ``i // G``.
    K and V are read where they lie, as the shared rotary key head is
    (``_rope_specs``): no copy at H heads exists in HBM, and a group's G
    consecutive grid rows name the same block, which the pipeline fetches
    once.  At ``H_kv == H`` the row is ``i`` itself, with no division in
    the index map."""
    return _shared_row(q, k, "key/value heads")


def _shared_row(q, x, what):
    """Grid row ``i`` of Q ``[BH, ...]`` -> the block row of an operand
    ``x`` whose every row serves ``BH / x.shape[0]`` consecutive grid rows
    (``what``, for the refusal of counts that do not divide)."""
    group = q.shape[0] // x.shape[0]
    if group * x.shape[0] != q.shape[0]:
        raise ValueError("flash attention: %d query heads over %d %s"
                         % (q.shape[0], x.shape[0], what))
    if group == 1:
        return lambda i: i
    return lambda i: i // group


def _bias_row(q, bias):
    """Grid row ``i`` (one head of one sequence, Q ``[B * H, S_q, D]``) ->
    the block row of its bias: a bias ``[B * H, S_q, S_kv]`` has a block a
    head; one of ``[B, S_q, S_kv]`` (a padding mask) is the same for the H
    heads of a sequence and is read where it lies, at ``i // H``, as the
    shared rotary key head is (``_rope_specs``): no copy a head exists in
    HBM, and a sequence's H consecutive grid rows name the same block,
    which the pipeline fetches once."""
    return _shared_row(q, bias, "rows of bias")


def _compose_rope(q, k, rope):
    """The one-head-size form of a rotary pair, for the composition paths:
    Q and K with the rotary part appended, the shared key head repeated."""
    if rope is None:
        return q, k
    qr, kr = rope
    return (jnp.concatenate([q, qr], axis=-1),
            jnp.concatenate([k, jnp.repeat(kr, q.shape[0] // kr.shape[0],
                                           axis=0)], axis=-1))


def _flash_forward(q, k, v, bias, scale, *, with_lse=False,
                   causal=False, rope=None, window=0):
    """q: [BH, S_q, D]; k: [BH, S_kv, D]; v: [BH, S_kv, D_v]
    (cross-attention supported; D_v may differ from D, the output has D_v);
    bias: [BH, S_q, S_kv], [B, S_q, S_kv] (the same for the H = BH / B
    heads of a sequence: ``_bias_row``) or None; rope: ``(qr [BH, S_q, R],
    kr [B, S_kv, R])`` or None — a second part of every head whose keys
    are shared by the heads of a sequence (``_scores``).  ``with_lse``:
    ``(out, logsumexp [BH, S_q] float32)``, whichever way the kernel wrote
    it (``_row_stats``).  ``window`` (``_band``): under the causal mask a
    query sees its last ``window`` keys alone."""
    BH, S_q, D = q.shape
    S_kv = k.shape[1]
    D_v = v.shape[2]
    if causal and S_q != S_kv:
        # the diagonal alignment for unequal lengths is ambiguous
        # (top-left for truncated self-attention, bottom-right for
        # KV-cache decode) — refuse rather than silently pick one
        raise ValueError(
            "causal=True needs S_q == S_kv (got %d vs %d); apply an "
            "explicit bias for cross-length causal masking"
            % (S_q, S_kv))
    window = _band(window, causal, S_kv)
    shape = _shape_key(q, k, v, bias, causal, rope, window)
    kv = _kv_row(q, k)
    if not _flash_fits(*shape):
        if shape[8] != 1:
            raise ValueError(
                "flash attention: grouped key/value heads at a shape the "
                "kernels have no tile for; repeat K and V to the query "
                "heads first (the fused_attention op does)")
        out = _reference_attention(*_compose_rope(q, k, rope), v, bias,
                                   scale, causal=causal, window=window)
        if not with_lse:
            return out
        # (with_lse is only requested by _fa_fwd AFTER the same
        # tileability check, so this fallback never computes an LSE)
        raise AssertionError("with_lse requested for a non-tileable "
                             "shape — caller bug")
    _rope_runs_looped(rope, causal, bias, window)
    if rope is not None and shape[8] != 1:
        raise ValueError("the flash kernels take a rotary pair or grouped "
                         "key/value heads, not both")
    block_q, block_k, whole, vmem = _plan("fwd", q, bias, *shape)
    rows = _row_stats("fwd", *shape)
    grid = (BH, S_q // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, S_kv, D), lambda i, j: (kv(i), 0, 0), **whole),
        pl.BlockSpec((1, S_kv, D_v), lambda i, j: (kv(i), 0, 0), **whole),
    ]
    args = [q, k, v]
    if bias is not None:
        row = _bias_row(q, bias)
        in_specs.append(pl.BlockSpec((1, block_q, S_kv),
                                     lambda i, j: (row(i), j, 0)))
        args.append(bias)
    if rope is not None:
        in_specs += _rope_specs(rope, block_q, None, whole)
        args += list(rope)
    n_in = len(args)

    def kern(*refs):
        q_ref, k_ref, v_ref = refs[:3]
        bias_ref = refs[3] if bias is not None else None
        qr_ref, kr_ref = refs[n_in - 2:n_in] if rope is not None \
            else (None, None)
        o_ref = refs[n_in]
        lse_ref = refs[n_in + 1] if with_lse else None
        _attention_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                          scale=scale, block_k=block_k, causal=causal,
                          qr_ref=qr_ref, kr_ref=kr_ref, window=window)

    out_specs = [pl.BlockSpec((1, block_q, D_v), lambda i, j: (i, j, 0))]
    out_shape = [jax.ShapeDtypeStruct((BH, S_q, D_v), q.dtype)]
    if with_lse:
        out_specs.append(_row_stat_spec(block_q, rows))
        out_shape.append(jax.ShapeDtypeStruct(
            (BH, 1, S_q) if rows else (BH, S_q, 1), jnp.float32))
    with _window_scope(window):
        res = _pallas_call(
            kern, "flash_fwd", vmem_limit_bytes=vmem,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
        )(*args)
    if not with_lse:
        return res[0]
    return res[0], (res[1][:, 0] if rows else res[1][..., 0])


def _row_delta(g, out):
    """delta = sum_d dO * O per row, ``[BH, S_q]`` float32: for callers
    that hold ``out`` (ring attention's GLOBAL output; S_kv beyond
    ``_DELTA_IN_KERNEL_MAX_SKV``; the looped sweeps)."""
    return jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)


def _flash_dq(q, k, v, bias, scale, lse, g, causal, delta, rope=None,
              window=0):
    """The dQ pass (grid over q blocks): ``(dq, delta)``.  With
    ``delta=None`` the kernel forms it (``_dq_kernel``) and writes it as a
    further output; a passed delta comes back as it went in.  With a rotary
    pair ``dq`` is the pair ``(dq, dqr)``."""
    BH, S_q, D = q.shape
    S_kv = k.shape[1]
    D_v = v.shape[2]
    block_q, block_k, whole, vmem = _plan(
        "dq", q, bias, *_shape_key(q, k, v, bias, causal, rope, window))
    _rope_runs_looped(rope, causal, bias, window)
    in_kernel = delta is None
    kv = _kv_row(q, k)
    q_block = pl.BlockSpec((1, block_q, D), lambda i, j: (i, j, 0))
    in_specs = [q_block,
                pl.BlockSpec((1, S_kv, D), lambda i, j: (kv(i), 0, 0),
                             **whole),
                pl.BlockSpec((1, S_kv, D_v), lambda i, j: (kv(i), 0, 0),
                             **whole)]
    args = [q, k, v]
    if bias is not None:
        row = _bias_row(q, bias)
        in_specs.append(pl.BlockSpec((1, block_q, S_kv),
                                     lambda i, j: (row(i), j, 0)))
        args.append(bias)
    if rope is not None:
        in_specs += _rope_specs(rope, block_q, None, whole)
        args += list(rope)
    in_specs += [pl.BlockSpec((1, block_q, D_v), lambda i, j: (i, j, 0)),
                 _row_stat_spec(block_q)]                       # dO, lse
    args += [g, lse]
    out_specs = [q_block]
    out_shape = [jax.ShapeDtypeStruct((BH, S_q, D), q.dtype)]
    if rope is not None:
        out_specs.append(_rope_specs(rope, block_q, None)[0])
        out_shape.append(jax.ShapeDtypeStruct(rope[0].shape, rope[0].dtype))
    scratch = []
    if in_kernel:
        out_specs.append(_row_stat_spec(block_q))
        out_shape.append(jax.ShapeDtypeStruct((BH, S_q, 1), jnp.float32))
        # a row that is one tile is held as values (``_dq_kernel``)
        scratch = [pltpu.VMEM((block_q, S_kv), jnp.float32)] * 2 \
            if S_kv > block_k else []
    else:
        in_specs.append(_row_stat_spec(block_q))
        args.append(delta)

    def kern(q_ref, k_ref, v_ref, *refs):
        refs = list(refs)
        bias_ref = refs.pop(0) if bias is not None else None
        qr_ref, kr_ref = (refs.pop(0), refs.pop(0)) if rope is not None \
            else (None, None)
        do_ref, lse_ref = refs.pop(0), refs.pop(0)
        delta_ref = None if in_kernel else refs.pop(0)
        dq_ref = refs.pop(0)
        dqr_ref = refs.pop(0) if rope is not None else None
        delta_out_ref = refs.pop(0) if in_kernel else None
        p_scr, dp_scr = refs or (None, None)
        _dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, delta_out_ref, p_scr, dp_scr,
                   scale=scale, block_k=block_k, causal=causal,
                   qr_ref=qr_ref, kr_ref=kr_ref, dqr_ref=dqr_ref,
                   window=window)

    with _window_scope(window):
        res = _pallas_call(
            kern, "flash_dq", vmem_limit_bytes=vmem,
            grid=(BH, S_q // block_q),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
        )(*args)
    return (res[0] if rope is None else (res[0], res[1])), \
        (res[-1] if in_kernel else delta)


def _flash_dkv(q, k, v, bias, scale, lse, g, causal, delta, rope=None,
               window=0):
    """The dK/dV pass (grid over k blocks, the whole Q side of a head in
    VMEM): ``(dk, dv)``.  With a rotary pair ``dk`` is the pair ``(dk,
    dkr)``, ``dkr`` summed over the heads that share the rotary keys.

    Where G query heads share a key/value head (``_kv_row``) the grid still
    runs over QUERY heads: each reads its key/value head's block and writes
    its own part of dK and dV as a float32 partial ``[B * H, S_kv, D]``,
    and the G parts are summed here, outside, as ``dkr`` is.  (A grid over
    key/value heads that sweeps its group inside needs the group's whole Q
    side in VMEM, G times 12 MiB at S=8192, or fetches it again for every k
    block; the partials cost one write and one read of ``2 * 4 * S_kv * D``
    bytes a query head.  PERF.md section 6, PR 34.)"""
    BH, S_q, D = q.shape
    S_kv = k.shape[1]
    D_v = v.shape[2]
    block_q, block_k, whole, vmem = _plan(
        "dkv", q, bias, *_shape_key(q, k, v, bias, causal, rope, window))
    kv = _kv_row(q, k)
    group = BH // k.shape[0]
    in_specs = [
        pl.BlockSpec((1, S_q, D), lambda i, j: (i, 0, 0), **whole),  # q
        pl.BlockSpec((1, block_k, D), lambda i, j: (kv(i), j, 0)),  # k
        pl.BlockSpec((1, block_k, D_v), lambda i, j: (kv(i), j, 0)),  # v
    ]
    args = [q, k, v]
    if bias is not None:
        row = _bias_row(q, bias)
        in_specs.append(pl.BlockSpec((1, S_q, block_k),
                                     lambda i, j: (row(i), 0, j)))
        args.append(bias)
    if rope is not None:
        in_specs += _rope_specs(rope, None, block_k, whole)
        args += list(rope)

    def kern(q_ref, k_ref, v_ref, *refs):
        refs = list(refs)
        bias_ref = refs.pop(0) if bias is not None else None
        qr_ref, kr_ref = (refs.pop(0), refs.pop(0)) if rope is not None \
            else (None, None)
        _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, *refs[:5],
                    scale=scale, block_q=block_q, causal=causal,
                    qr_ref=qr_ref, kr_ref=kr_ref,
                    dkr_ref=refs[5] if rope is not None else None,
                    window=window)
    in_specs += [
        pl.BlockSpec((1, S_q, D_v), lambda i, j: (i, 0, 0), **whole),  # dO
        pl.BlockSpec((1, S_q, 1), lambda i, j: (i, 0, 0), **whole),   # lse
        pl.BlockSpec((1, S_q, 1), lambda i, j: (i, 0, 0), **whole),   # delta
    ]
    out_specs = [
        pl.BlockSpec((1, block_k, D), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, block_k, D_v), lambda i, j: (i, j, 0))]
    out_shape = [
        jax.ShapeDtypeStruct((BH, S_kv, D),
                             k.dtype if group == 1 else jnp.float32),
        jax.ShapeDtypeStruct((BH, S_kv, D_v),
                             v.dtype if group == 1 else jnp.float32)]
    if rope is not None:
        R = rope[1].shape[2]
        out_specs.append(
            pl.BlockSpec((1, block_k, R), lambda i, j: (i, j, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((BH, S_kv, R), jnp.float32))
    with _window_scope(window):
        dk, dv, *dkr = _pallas_call(
            kern, "flash_dkv", vmem_limit_bytes=vmem,
            grid=(BH, S_kv // block_k),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
        )(*args, g, lse, delta)
    if rope is not None:
        kr = rope[1]
        dk = (dk, dkr[0].reshape(kr.shape[0], -1, S_kv, kr.shape[2])
              .sum(axis=1).astype(kr.dtype))
    if group > 1:
        with jax.named_scope("flash_dkv_group_sum"):
            dk, dv = (parts.reshape(-1, group, *parts.shape[1:]).sum(axis=1)
                      .astype(x.dtype) for parts, x in ((dk, k), (dv, v)))
    return dk, dv


def _flash_bwd(q, k, v, bias, scale, lse, g, causal, delta,
               delta_out=False, heads=None, rate=0.0, seed=None):
    """The fused backward (``_bwd_kernel``; where ``_fused_backward`` says a
    head is one tile): ``(dq, dk, dv, delta)`` from one call, a grid cell a
    head.  ``lse`` and delta are rows, ``[BH, 1, S_q]`` (``_row_stats``).
    A passed delta is used and comes back as it went in; with
    ``delta=None`` the kernel forms it, and writes it out only where
    ``delta_out`` asks (the dbias pass reads it), else None comes back.

    With ``heads`` (``_in_place``) Q, K, V and dO are ``[B, S, heads * D]``
    and so are dQ, dK and dV; a cell runs ``128 // D`` heads
    (``_cell_specs``), and with ``rate`` draws the forward's keep mask
    again from ``seed`` (``_seed_spec``)."""
    S_q, S_kv = q.shape[1], k.shape[1]
    D, D_v = (q.shape[2], v.shape[2]) if heads is None \
        else (q.shape[2] // heads,) * 2
    shape = _shape_key(q, k, v, bias, causal, None) if heads is None \
        else _in_place_shape(q, k, bias, causal, heads)
    vmem = _plan("bwd", q, bias, *shape, heads=heads, rate=rate)[3]
    delta_out = delta_out and delta is None
    grid, pack, rows, stat, bias_spec = _cell_specs(q, k, bias, heads)
    in_specs = [rows(S_q, D), rows(S_kv, D), rows(S_kv, D_v)]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(bias_spec)
        args.append(bias)
    in_specs += [rows(S_q, D_v), stat]                  # dO, lse
    args += [g, lse]
    if delta is not None:
        in_specs.append(stat)
        args.append(delta)
    if rate:
        in_specs.append(_seed_spec())
        args.append(seed)
    out_specs = [rows(S_q, D), rows(S_kv, D), rows(S_kv, D_v)]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                 jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if delta_out:
        out_specs.append(stat)
        out_shape.append(jax.ShapeDtypeStruct(lse.shape, jnp.float32))

    has_bias, has_delta = bias is not None, delta is not None

    def kern(q_ref, k_ref, v_ref, *refs, **drawn):
        refs = list(refs)
        bias_ref = refs.pop(0) if has_bias else None
        do_ref, lse_ref = refs.pop(0), refs.pop(0)
        delta_ref = refs.pop(0) if has_delta else None
        if rate:
            drawn.update(rate=rate, seed_ref=refs.pop(0))
        _bwd_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                    delta_ref, *refs[:3], refs[3] if delta_out else None,
                    scale=scale, causal=causal, heads=pack, **drawn)

    dq, dk, dv, *formed = _pallas_call(
        kern, "flash_bwd", vmem_limit_bytes=vmem, platform_bound=bool(rate),
        cache_key=("bwd", scale, causal, heads, rate, delta_out),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
    )(*args)
    return dq, dk, dv, (formed[0] if delta_out else delta)


def _flash_fwd_in_place(q, k, v, bias, scale, heads, causal=False,
                        with_lse=True, rate=0.0, seed=None):
    """``flash_fwd`` on operands as the projections left them
    (``_in_place``): q ``[B, S_q, heads * D]``, k and v ``[B, S_kv, heads *
    D]``, bias ``[B, S_q, S_kv]`` (the heads of a sequence share it),
    ``[B * heads, S_q, S_kv]`` or None -> ``(out [B, S_q, heads * D],
    logsumexp [B * heads, S_q] float32 or None)``: what ``_flash_forward``
    gives for the same heads as ``[B * heads, S, D]``, with no copy of any
    of them.  ``rate``: attention-probability dropout, its keep mask drawn
    in the kernel from ``seed`` (int32 ``[1]``, ``_dropout_seed``)."""
    B, S_q, _ = q.shape
    D = q.shape[2] // heads
    vmem = _plan("fwd", q, bias, *_in_place_shape(q, k, bias, causal, heads),
                 heads=heads, rate=rate)[3]
    grid, pack, rows, stat, bias_spec = _cell_specs(q, k, bias, heads)
    in_specs = [rows(S_q, D), rows(k.shape[1], D), rows(k.shape[1], D)]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(bias_spec)
        args.append(bias)
    if rate:
        in_specs.append(_seed_spec())
        args.append(seed)

    has_bias = bias is not None

    def kern(q_ref, k_ref, v_ref, *refs, **drawn):
        refs = list(refs)
        bias_ref = refs.pop(0) if has_bias else None
        if rate:
            drawn.update(rate=rate, seed_ref=refs.pop(0))
        _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, refs[0],
                    refs[1] if with_lse else None, scale=scale,
                    causal=causal, heads=pack, **drawn)

    out_specs = [rows(S_q, D)]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if with_lse:
        out_specs.append(stat)
        out_shape.append(jax.ShapeDtypeStruct((B * heads, 1, S_q),
                                              jnp.float32))
    res = _pallas_call(
        kern, "flash_fwd", vmem_limit_bytes=vmem, platform_bound=bool(rate),
        cache_key=("fwd_in_place", scale, causal, heads, with_lse, rate),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
    )(*args)
    return res[0], (res[1][:, 0] if with_lse else None)


def _flash_backward(q, k, v, bias, scale, lse, g, causal=False, delta=None,
                    bias_grad=True, rope=None, window=0):
    """Tiled dQ/dK/dV — recomputes p blockwise from the saved LSE
    (``[BH, S_q]`` float32, as ``_flash_forward`` hands it out; laid out
    here as the shape's kernels take it, ``_row_stats``); the [S, S]
    score matrix never exists in HBM (FlashAttention-2 backward).  Where a
    head is one tile in both passes (``_fused_backward``, from the call's
    shapes alone: S <= 512 at the BERT widths) ONE kernel, ``flash_bwd``,
    forms S, P, dP and dS once for all three gradients; every other shape
    runs the dQ pass and then the dK/dV pass, which each rebuild them.

    ``delta`` (``[BH, S_q]`` float32, ``_row_delta``) is passed
    by a caller that holds ``out``; it MUST be by one whose K/V are a shard
    of the row (ring attention: a delta summed over one device's K/V is
    wrong).
    With ``delta=None`` the fused kernel or the dQ kernel forms it over the
    whole row and hands it to the other passes, so no ``out`` is needed at
    all.  ``bias_grad=False`` skips the dbias pass, which writes a head's
    ``[S_q, S_kv]``; for a bias the heads of a sequence share (``_bias_row``)
    the heads' parts are summed here, outside.  With a rotary pair
    ``dq`` and ``dk`` are pairs, ``(dq, dqr)`` and ``(dk, dkr)``, ``dkr``
    summed over the heads that share the rotary keys
    (``_rope_runs_looped``: never beside a bias)."""
    BH, S_q, D = q.shape
    S_kv = k.shape[1]
    D_v = v.shape[2]
    window = _band(window, causal, S_kv)
    shape = _shape_key(q, k, v, bias, causal, rope, window)
    want_dbias = bias is not None and bias_grad
    rows = _row_stats("bwd", *shape)
    lse, delta = _kernel_stat(lse, rows), _kernel_stat(delta, rows)
    if _fused_backward(*shape):
        dq, dk, dv, delta = _flash_bwd(q, k, v, bias, scale, lse, g, causal,
                                       delta, delta_out=want_dbias)
    else:
        dq, delta = _flash_dq(q, k, v, bias, scale, lse, g, causal, delta,
                              rope, window)
        dk, dv = _flash_dkv(q, k, v, bias, scale, lse, g, causal, delta,
                            rope, window)

    dbias = None
    if want_dbias:
        block_q, block_k, whole, vmem = _plan("dbias", q, bias, *shape)
        kv, row = _kv_row(q, k), _bias_row(q, bias)
        db_specs = [
            pl.BlockSpec((1, block_q, D), lambda i, j: (i, j, 0)),  # q
            pl.BlockSpec((1, S_kv, D), lambda i, j: (kv(i), 0, 0), **whole),
            pl.BlockSpec((1, S_kv, D_v), lambda i, j: (kv(i), 0, 0),
                         **whole),
            pl.BlockSpec((1, block_q, S_kv),
                         lambda i, j: (row(i), j, 0)),
            pl.BlockSpec((1, block_q, D_v), lambda i, j: (i, j, 0)),  # dO
            _row_stat_spec(block_q, rows),                          # lse
            _row_stat_spec(block_q, rows),                          # delta
        ]
        dbias = _pallas_call(
            functools.partial(_dbias_kernel, scale=scale,
                              block_k=block_k, causal=causal),
            "flash_dbias", vmem_limit_bytes=vmem,
            grid=(BH, S_q // block_q),
            in_specs=db_specs,
            out_specs=pl.BlockSpec((1, block_q, S_kv),
                                   lambda i, j: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, S_q, S_kv), bias.dtype),
        )(q, k, v, bias, g, lse, delta)
        if bias.shape[0] != BH:
            dbias = dbias.reshape(bias.shape[0], -1, S_q, S_kv) \
                .astype(jnp.float32).sum(axis=1).astype(bias.dtype)
    return dq, dk, dv, dbias


def _forward_keeping_lse(q, k, v, bias, scale, causal, rope=None, window=0):
    """Training forward on a tileable shape: (out, what the backward
    needs beside its inputs).  The row statistic leaves as ``[BH, S_q]``
    behind an ``optimization_barrier``.  Where the kernel wrote a column
    (``_row_stats``: every multi-pass shape) XLA:TPU lays its ``[BH, S_q,
    1]`` out with the size-1 minor dimension padded to 128 lanes, and
    without the barrier it cancels a squeeze/expand pair and keeps THAT
    buffer alive from the forward to the backward, in every layer.  Where
    it wrote a row the squeeze is a reshape of ``[BH, 1, S_q]`` and no
    padded buffer exists on either side; the barrier stays, one program
    for both layouts.  ``out`` is kept only where the dQ pass cannot form
    delta itself."""
    out, lse = _flash_forward(q, k, v, bias, scale, with_lse=True,
                              causal=causal, rope=rope, window=window)
    lse = jax.lax.optimization_barrier(lse)
    return out, lse, (None if _delta_in_kernel(k.shape[1], causal,
                                               bias is not None)
                      else out)


def _backward_from_lse(q, k, v, bias, scale, causal, lse, out, g,
                       bias_grad=True, rope=None, window=0):
    """dq, dk, dv, dbias from the residuals of ``_forward_keeping_lse``.
    ``lse`` (``[BH, S_q]``) and ``g`` pass one barrier together, so
    ``_flash_backward``'s expansion to its kernels' layout (for a column
    the lane-padded ``[BH, S_q, 1]``) cannot be scheduled before the
    layer's output gradient exists."""
    lse, g = jax.lax.optimization_barrier((lse, g))
    return _flash_backward(
        q, k, v, bias, scale, lse, g, causal=causal,
        delta=None if out is None else _row_delta(g, out),
        bias_grad=bias_grad, rope=rope, window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 7))
def flash_attention(q, k, v, bias, scale, causal=False, rope=None, window=0):
    """``rope``: a rotary pair ``(qr [BH, S_q, R], kr [B, S_kv, R])`` or
    None (``_flash_forward``); its gradient is the pair ``(dqr, dkr)``.
    ``window``: a sliding window under the causal mask (``_band``)."""
    return _flash_forward(q, k, v, bias, scale, causal=causal, rope=rope,
                          window=window)


def _fa_fwd(q, k, v, bias, scale, causal, rope=None, window=0):
    window = _band(window, causal, k.shape[1])
    if not _flash_fits(*_shape_key(q, k, v, bias, causal, rope, window)):
        # non-tileable shapes keep the exact-composition fallback
        return _flash_forward(q, k, v, bias, scale, causal=causal,
                              rope=rope, window=window), \
            (q, k, v, bias, rope, None, None)
    out, lse, kept = _forward_keeping_lse(q, k, v, bias, scale, causal, rope,
                                          window)
    return out, (q, k, v, bias, rope, lse, kept)


def _fa_bwd(scale, causal, window, res, g):
    q, k, v, bias, rope, lse, out = res
    if lse is None:                        # composition fallback path
        def composed(q_, k_, v_, b_, r_):
            return _reference_attention(*_compose_rope(q_, k_, r_), v_, b_,
                                        scale, causal=causal, window=window)
        _, vjp = jax.vjp(composed, q, k, v, bias, rope)
        return vjp(g)
    dq, dk, dv, dbias = _backward_from_lse(q, k, v, bias, scale, causal,
                                           lse, out, g, rope=rope,
                                           window=window)
    if rope is None:
        return dq, dk, dv, dbias, None
    return dq[0], dk[0], dv, dbias, (dq[1], dk[1])


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 7))
def flash_attention_lse(q, k, v, bias, scale, causal=False, rope=None,
                        window=0):
    """``flash_attention`` on a tileable shape that also returns the
    logsumexp rows ``[BH, S_q]`` (float32): the training forward of the
    ``fused_attention`` op, whose grad op reads them back as its ``LSE``
    input.  The statistic is a residual, not a differentiable output: its
    cotangent is dropped."""
    return _forward_keeping_lse(q, k, v, bias, scale, causal, rope,
                                window)[:2]


def _fal_fwd(q, k, v, bias, scale, causal, rope=None, window=0):
    out, lse, kept = _forward_keeping_lse(q, k, v, bias, scale, causal, rope,
                                          window)
    return (out, lse), (q, k, v, bias, rope, lse, kept)


def _fal_bwd(scale, causal, window, res, gs):
    return _fa_bwd(scale, causal, window, res, gs[0])


flash_attention_lse.defvjp(_fal_fwd, _fal_bwd)


def _heads_major(x, heads):
    """``[B, S, heads * D]`` -> ``[B, heads, S, D]``: the head split a
    program used to spell with ``reshape2`` and ``transpose2``."""
    B, S, width = x.shape
    return x.reshape(B, S, heads, width // heads).transpose(0, 2, 1, 3)


def _heads_minor(x):
    """``[B, H, S, D]`` -> ``[B, S, H * D]``: the merge after attention."""
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention_in_place(q, k, v, bias, scale, causal, heads,
                             with_lse=True, rate=0.0, seed=None):
    """``flash_attention_lse`` on operands ``[B, S, heads * D]`` at a shape
    the kernels read in place (``_in_place``): ``(out [B, S_q, heads * D],
    logsumexp [B * heads, S_q] or None)``.  The statistic is a residual,
    not a differentiable output.  ``rate``: attention-probability dropout
    drawn in the kernels from ``seed`` (``_dropout_seed``); the bias is
    then a mask that wants no gradient (``_op_in_place``), and none is
    formed."""
    return _flash_fwd_in_place(q, k, v, bias, scale, heads, causal, with_lse,
                               rate, seed)


def _fip_fwd(q, k, v, bias, scale, causal, heads, with_lse, rate, seed):
    out, lse = _flash_fwd_in_place(q, k, v, bias, scale, heads, causal,
                                   rate=rate, seed=seed)
    return (out, lse if with_lse else None), (q, k, v, bias, lse, seed)


def _fip_bwd(scale, causal, heads, with_lse, rate, res, gs):
    q, k, v, bias, lse, seed = res
    if bias is None or rate:
        return _backward_in_place(q, k, v, bias, scale, causal, heads, lse,
                                  gs[0], rate, seed) + (None, None)
    # a bias under jax's own differentiation may want its gradient, and
    # the dbias pass writes a head's ``[BH, S_q, S_kv]``: the heads are
    # split for it, and XLA drops what nothing reads
    q, k, v, g = (_flat(_heads_major(x, heads)) for x in (q, k, v, gs[0]))
    dq, dk, dv, dbias = _backward_from_lse(q, k, v, bias, scale, causal, lse,
                                           None, g)
    return tuple(_heads_minor(d.reshape(-1, heads, *d.shape[1:]))
                 for d in (dq, dk, dv)) + (dbias, None)


flash_attention_in_place.defvjp(_fip_fwd, _fip_bwd)


def _backward_in_place(q, k, v, bias, scale, causal, heads, lse, g,
                       rate=0.0, seed=None):
    """``(dq, dk, dv)`` as ``[B, S, heads * D]`` from operands and ``g`` in
    that layout and the forward's logsumexp ``[B * heads, S_q]``: ONE
    ``flash_bwd`` call that forms delta itself (the one tile is the whole
    row), for a bias whose gradient nobody wants; with ``rate`` it draws
    the forward's keep mask again from the forward's ``seed``."""
    return _flash_bwd(q, k, v, bias, scale, lse[:, None], g.astype(q.dtype),
                      causal, None, heads=heads, rate=rate, seed=seed)[:3]


def _dropout_seed(ctx):
    """The dropout seed of a ``fused_attention`` op's in-kernel mask, int32
    ``[1]``: 32 bits of the op's own key (``ctx.rng()``: per op, per step),
    which its grad op remakes from the same key (``__op_seed__``)."""
    return jax.lax.bitcast_convert_type(
        jax.random.bits(ctx.rng(), (1,), jnp.uint32), jnp.int32)


def _sp_attention(q, k, v, mesh, axis, mode, scale, causal, bias=None):
    """Sequence-parallel attention island inside a GSPMD-compiled step:
    shard_map over the ``axis`` ('sp') mesh axis so the sequence dim stays
    sharded through attention — ring ppermute (mode='ring') or Ulysses
    all-to-all head exchange (mode='ulysses') rides ICI instead of the
    full K/V all-gather GSPMD would otherwise insert.  q/k/v: [B, H, S, D]
    with S sharded; batch rides 'dp' too when divisible.

    bias [B, 1|H, S, S] (padding masks etc.) is q-row-sharded over 'sp'
    with full kv columns local: the ring slices the arriving block's
    column window, Ulysses reshards it with the head exchange."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import ring_attention, ulysses_attention

    sizes = dict(mesh.shape)
    B = q.shape[0]
    dp_ok = "dp" in sizes and sizes["dp"] > 1 and B % sizes["dp"] == 0 \
        and _axis_is_auto(mesh, "dp")
    bdim = "dp" if dp_ok else None
    spec = P(bdim, None, axis, None)
    in_specs = [spec, spec, spec]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(P(bdim if bias.shape[0] == B else None,
                          None, axis, None))
        args.append(bias)

    def body(qb, kb, vb, *rest):
        # local block [Bl, H, Sl, D] -> the helpers' [Bl, Sl, H, D]
        qt = jnp.transpose(qb, (0, 2, 1, 3))
        kt = jnp.transpose(kb, (0, 2, 1, 3))
        vt = jnp.transpose(vb, (0, 2, 1, 3))
        bb = rest[0] if rest else None   # [Bl, 1|H, Sl, S] already
        fn = ulysses_attention if mode == "ulysses" else ring_attention
        ot = fn(qt, kt, vt, axis_name=axis, causal=causal, scale=scale,
                bias=bb)
        return jnp.transpose(ot, (0, 2, 1, 3))

    from ..mesh_utils import shard_map
    return shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=spec)(*args)


def _axis_is_auto(mesh, name):
    """True when ``name`` is a GSPMD (auto) axis of ``mesh`` — inside a
    manual shard_map region (the pipeline), axes like 'dp'/'pp' are
    Manual and an inner island must not mention them in its specs."""
    from jax.sharding import AxisType
    d = dict(zip(mesh.axis_names, mesh.axis_types))
    return d.get(name, AxisType.Auto) == AxisType.Auto


def _attn_core_remat(scale, causal, dropout, rng_axes=(), window=0):
    """jax.checkpoint-wrapped _attn_core with the static config bound.

    Without remat every attention layer's [B, H, S_q, S_kv] score and
    prob tensors persist as autodiff residuals until the backward pass
    (the composition path already costs 7x the flash path's temp bytes
    at S=512 for ONE layer, measured via Executor.compiled_memory); the
    checkpoint bounds saved residuals to the layer's INPUTS — across an
    N-layer stack that is the difference between N score matrices live
    and one.  The dropout mask replays EXACTLY in the recompute because
    the PRNG key is an input, not a side effect.  (XLA:CPU's
    temp-byte counter does not reflect remat scheduling — the guarantee
    here is jax.checkpoint's residual contract, visible as the +FLOPs
    the FLOP-budget test pins for RecomputeOptimizer.)"""
    def fn(qb, kb, vb, bb, q_offset, key):
        return _attn_core(qb, kb, vb, bb, scale, causal, q_offset,
                          dropout, key, rng_axes, window)
    return jax.checkpoint(fn)


def _attn_core(qb, kb, vb, bb, scale, causal, q_offset, dropout, key,
               rng_axes=(), window=0):
    """Exact attention composition on rank-4 blocks, with optional
    attention-probability dropout (upscale_in_train semantics, matching
    layers.dropout): qb [B, H, S_q, D], kb/vb [B, H, S_kv, D], bb
    [B, 1|H, S_q, S_kv] or None.  ``q_offset`` is the global index of
    this block's first q row (non-zero inside the SP shard_map island, so
    the causal mask stays aligned); ``rng_axes`` are mesh axes whose
    index folds into the dropout key (decorrelates masks across shards —
    the lowering.py rng contract); ``window``: the causal band
    (``_in_band``)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb,
                   preferred_element_type=jnp.float32) * scale
    if bb is not None:
        s = s + bb.astype(s.dtype)
    if causal:
        qi = q_offset + jnp.arange(qb.shape[2])[:, None]
        ki = jnp.arange(kb.shape[2])[None, :]
        s = jnp.where(_in_band(qi, ki, window), s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if dropout:
        for ax in rng_axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        keep = jax.random.bernoulli(key, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(qb.dtype), vb)


def _sp_gather_attention(q, k, v, mesh, axis, scale, causal, bias,
                         dropout, key):
    """Sequence-parallel attention for the cases the flash ring/Ulysses
    island does not cover (VERDICT r4 item 6): CROSS-attention
    (S_q != S_kv) and attention-probability DROPOUT.

    q rows stay sharded over ``axis``; k/v arrive sequence-sharded and
    are all-gathered over ICI inside the island, so each device attends
    its local q rows against the full memory.  Per-device score block is
    [B, H, S_q/sp, S_kv] — 1/sp of the full score matrix, the same
    memory a row-sharded unfused attention would cost.  With dropout off
    the local compute is the flash kernel (no score matrix at all);
    with dropout on it is the exact composition, keys folded with the
    device's axis indices."""
    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh.shape)
    B, H, S_q, D = q.shape
    dp_ok = "dp" in sizes and sizes["dp"] > 1 and B % sizes["dp"] == 0 \
        and _axis_is_auto(mesh, "dp")
    bdim = "dp" if dp_ok else None
    spec_q = P(bdim, None, axis, None)
    kv_sharded = k.shape[2] % sizes[axis] == 0
    spec_kv = P(bdim, None, axis if kv_sharded else None, None)
    in_specs = [spec_q, spec_kv, spec_kv]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(P(bdim if bias.shape[0] == B else None,
                          None, axis, None))
        args.append(bias)
    if key is not None:
        in_specs.append(P())
        args.append(key)
    rng_axes = (axis,) + (("dp",) if dp_ok else ())

    def body(qb, kb, vb, *rest):
        rest = list(rest)
        bb = rest.pop(0) if bias is not None else None
        kloc = rest.pop(0) if key is not None else None
        if kv_sharded:
            kb = jax.lax.all_gather(kb, axis, axis=2, tiled=True)
            vb = jax.lax.all_gather(vb, axis, axis=2, tiled=True)
        Bl, Hl, Sl, Dl = qb.shape
        Skv = kb.shape[2]
        if not dropout and not causal:
            # cross-attention fast path: flash on the local rows
            of = flash_attention(qb.reshape(Bl * Hl, Sl, Dl),
                                 kb.reshape(Bl * Hl, Skv, Dl),
                                 vb.reshape(Bl * Hl, Skv, Dl),
                                 _kernel_bias(bb, qb, Skv), scale,
                                 causal=False)
            return of.reshape(Bl, Hl, Sl, Dl)
        q_off = jax.lax.axis_index(axis) * Sl
        return _attn_core_remat(scale, causal, dropout, rng_axes)(
            qb, kb, vb, bb, q_off, kloc)

    # check_vma=False: off a TPU the flash fast path is an INTERPRETED
    # pallas_call, whose grid loop slices varying blocks at unvarying
    # indices — jax's varying-axes check rejects that lowering
    from ..mesh_utils import shard_map
    return shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=spec_q, check_vma=False)(*args)


def _is_test(ctx):
    return bool(ctx.attr("is_test", False) or ctx.state.is_test)


def _op_dropout(ctx):
    """The attention-probability dropout rate an op runs with: its
    ``attn_dropout``, 0 under ``is_test``."""
    return 0.0 if _is_test(ctx) else \
        float(ctx.attr("attn_dropout", 0.0) or 0.0)


def _attention_route(ctx, q, k, v, qr=None):
    """Which path a ``fused_attention`` op — or its grad op, which
    carries the same attributes — takes, from what it can observe:
    ``(sp_active, dropout, flash, kernels)``.  ``sp_active``: the
    sequence-parallel transpiler stamped the op and the step compiles over
    a mesh carrying that axis; ``dropout``: the attention-probability rate
    in effect; ``kernels``: not ``sp_active``, and every kernel has a tile
    at the shape (``_flash_fits``); ``flash``: that, without dropout, so
    the Pallas kernels run on the op's operands (Q, K, V ``[B, H | H_kv,
    S, D]``) as they are, grouped key/value heads included (``_kv_row``).
    Dropout is drawn inside the kernels only where they read ``[B, S, H *
    D]`` in place (``_op_in_place``, which reads ``kernels``); every other
    op with dropout composes.  A rotary pair (``qr``: the op's ``QRope`` as
    ``[B, H, S_q, R]``) is among them only under the causal mask and
    without a bias (``_rope_runs_looped``); any other op with a pair
    composes one head size first.  A sliding window (``_op_window``) runs
    in the kernels under the causal mask without a bias; beside a bias the
    op composes."""
    dropout = _op_dropout(ctx)
    sp_axis = ctx.attr("sp_axis", None)
    mesh = getattr(ctx.state, "mesh", None)
    sp = dict(mesh.shape).get(sp_axis, 1) if (sp_axis and mesh is not None) \
        else 1
    S_q = q.shape[2]
    sp_active = sp > 1 and S_q % sp == 0 and _axis_is_auto(mesh, sp_axis)
    causal, has_bias = bool(ctx.attr("causal", False)), \
        ctx.has_input("BiasQK")
    kernels = not sp_active and \
        not (qr is not None and (has_bias or not causal)) and \
        _flash_fits(S_q, k.shape[2], q.shape[3], v.shape[3],
                    0 if qr is None else qr.shape[3], has_bias, causal,
                    q.dtype.itemsize, q.shape[1] // k.shape[1],
                    _op_window(ctx, k.shape[2]))
    return sp_active, dropout, kernels and not dropout, kernels


def _op_window(ctx, S_kv):
    """The sliding window of a ``fused_attention`` op or its grad op
    (attribute ``window``: a query sees its last ``window`` keys, itself
    among them; 0 or absent: none), as the call runs it (``_band``: one
    that covers the sequence IS the causal call)."""
    return _band(ctx.attr("window", 0), bool(ctx.attr("causal", False)),
                 S_kv)


def _kv_group(q, k, v, qr=None):
    """Query heads to a key/value head, from the op's operands ``[B, H |
    H_kv, S, D]``; refuses head counts that do not divide, K and V that
    differ, and grouped heads beside a rotary pair (whose keys are one
    shared head already)."""
    H, H_kv = q.shape[1], k.shape[1]
    if v.shape[1] != H_kv or H % H_kv:
        raise ValueError("fused_attention: %d query heads over %d key and "
                         "%d value heads" % (H, H_kv, v.shape[1]))
    if H != H_kv and qr is not None:
        raise NotImplementedError("fused_attention: grouped key/value heads "
                                  "with a rotary pair")
    return H // H_kv


def _norm_bias(spb, q, S_kv):
    """Normalize every broadcastable bias shape ([S,S], [B,S,S],
    [B,1,1,S] key-padding, ...) to the rank-4 [B, 1|H, S_q, S_kv] the
    shard_map specs partition on."""
    if spb is None:
        return None
    B, H, S_q, _ = q.shape
    if spb.ndim == 3:               # [B|1, S_q, S_kv]: insert head dim
        spb = spb[:, None]
    hb = H if (spb.ndim == 4 and spb.shape[1] == H) else 1
    return jnp.broadcast_to(spb.astype(q.dtype), (B, hb, S_q, S_kv))


def _flat(x):
    """[B, H, S, D] -> the kernels' [BH, S, D]."""
    return x.reshape((-1,) + x.shape[2:])


def _kernel_bias(bias, q, S_kv, per_head=False):
    """Any broadcastable bias -> what the kernels take, in q's dtype, or
    None: ``[B, S_q, S_kv]`` where it is the same for every head of a
    sequence (its head dimension is 1 after ``_norm_bias``: a padding
    mask), which the kernels read at block row ``i // H`` (``_bias_row``);
    ``[BH, S_q, S_kv]`` where it has a head dimension, or where
    ``per_head`` asks for a head's own copy (a bias whose gradient is
    wanted: ``flash_dbias`` writes a head's)."""
    if bias is None:
        return None
    B, H, S_q, _ = q.shape
    bias = _norm_bias(bias, q, S_kv)
    if bias.shape[1] == 1 and not per_head:
        return bias[:, 0]
    return _flat(jnp.broadcast_to(bias, (B, H, S_q, S_kv)))


@register_op("fused_attention")
def _fused_attention(ctx, op):
    """Fused multi-head attention core: Q [B, H, S_q, D], K/V
    [B, H, S_kv, D] (cross-attention supported; + optional additive
    BiasQK [B, 1|H, S_q, S_kv]) → Out [B, H, S_q, D].

    With the attribute ``num_heads`` = H the heads lie in the minor
    dimension: Q [B, S_q, H * D], K [B, S_kv, H_kv * D], V [B, S_kv, H_kv *
    D_v] (QRope [B, S_q, H * R], KRope [B, S_kv, R]) → Out [B, S_q, H *
    D_v]; BiasQK and ``LSE`` are what they are without it.  Where
    ``_op_in_place`` says so (the flash route, a head one tile, H_kv = H,
    D_v = D, ``128 // D`` heads to a block, no rotary pair) the kernels
    read and write those arrays as they lie (``flash_attention_in_place``);
    every other such op is the 4-D op below between a head split and a
    merge, traced here and outside any checkpoint, which is the graph the
    program used to spell with ``transpose2`` ops.  The rule is read from
    the operands and the attribute alone; an op without ``num_heads``
    traces what it traced.

    When the sequence-parallel transpiler stamped this op (``sp_axis``
    attr) and the step compiles over a mesh carrying that axis, the
    equal-length dropout-free path (with or without an additive
    bias/padding mask) routes through ring/Ulysses attention under
    shard_map (transpiler/sequence_parallel.py); cross-length attention
    and attention dropout route through the q-row-sharded gather island
    (``_sp_gather_attention`` — r5).  Off-mesh, dropout is drawn inside
    the kernels where they read the operands in place and
    ``_drop_in_kernels`` holds (``_op_in_place``), every other dropout op
    runs the exact composition, and everything else the flash kernels.

    ``LSE`` [B, H, S_q] float32 (an intermediate output, like
    ``batch_norm``'s ``SavedMean``) is written where the flash kernels run
    in a training program: ``fused_attention_grad`` reads it back and
    runs the backward kernels on it (``_fused_attention_grad``).  On
    every other path, and under ``is_test``, it stays unwritten and the
    kernel computes no statistic.

    Grouped key/value heads: K and V may carry ``H_kv`` heads with ``H %
    H_kv == 0``; query head ``h`` then reads key/value head ``h // (H /
    H_kv)``.  The flash kernels read K and V where they lie (``_kv_row``:
    no copy at H heads exists) and sum dK / dV over a group from float32
    parts (``_flash_dkv``); every other path — the composition, dropout,
    the sequence-parallel islands — repeats K and V to H heads first, and
    the repeat's transpose sums the gradient.

    V [B, H, S_kv, D_v] may have another head size than Q and K; Out has
    V's.  ``QRope`` [B, H, S_q, R] / ``KRope`` [B, 1, S_kv, R] (latent
    attention: the rotary part of each head, its keys ONE head shared by
    all H) add ``QRope KRope^T`` to the scores.  The flash kernels read the
    shared head as it is (``_scores``); every other path composes one head
    size first (``_compose_rope``).

    ``window`` = W > 0 (needs ``causal``): query ``i`` sees keys ``j`` with
    ``i - W < j <= i``.  The looped kernels' sweeps are bounded by the band
    on both sides (``_first_k_block``, ``_last_q_block``), so the tiles
    outside it are never visited; ``W >= S_kv`` is the causal op.  Beside a
    bias, under dropout or where no kernel has a tile the composition masks
    the same band; the sequence-parallel islands and a rotary pair refuse
    a window by name."""
    q = ctx.i("Q")
    k = ctx.i("K")
    v = ctx.i("V")
    bias = ctx.i_opt("BiasQK")
    qr, kr = ctx.i_opt("QRope"), ctx.i_opt("KRope")
    scale = ctx.attr("scale", 1.0)
    causal = bool(ctx.attr("causal", False))
    heads = int(ctx.attr("num_heads", 0) or 0)
    if heads:
        if _op_in_place(ctx, q, k, v, heads):
            _m_lowered.inc(shape="mha", path="flash", layout="bshd", window=0)
            B, S_q = q.shape[:2]
            rate = _op_dropout(ctx)
            out, lse = flash_attention_in_place(
                q, k, v, _kernel_bias(bias, _heads_major_shape(q, heads),
                                      k.shape[1]),
                float(scale), causal, heads,
                bool(op.output("LSE")) and not _is_test(ctx), rate,
                _dropout_seed(ctx) if rate else None)
            if lse is not None:
                ctx.set("LSE", lse.reshape(B, heads, S_q))
            ctx.set("Out", out)
            return
        # every other op in this layout is the op on [B, H, S, D] between
        # a head split and a merge, here and outside any checkpoint: the
        # graph the program used to spell with transpose2 ops
        q, k, v, qr, kr = _op_heads_major(q, k, v, qr, kr, heads)

    def put(out):
        ctx.set("Out", _heads_minor(out) if heads else out)
    B, H, S_q, D = q.shape
    S_kv = k.shape[2]
    if causal and S_q != S_kv:
        # every path refuses, not just flash: the mask alignment for
        # unequal lengths is ambiguous (top-left train vs bottom-right
        # KV-cache decode) — silently picking one would train a model
        # that diverges from the non-SP semantics
        raise ValueError(
            "fused_attention: causal=True needs S_q == S_kv (got %d vs "
            "%d) — the causal alignment for cross-length attention is "
            "ambiguous; pass an explicit additive bias instead"
            % (S_q, S_kv))
    group = _kv_group(q, k, v, qr)
    sp_active, dropout, flash, _ = _attention_route(ctx, q, k, v, qr)
    sp_axis = ctx.attr("sp_axis", None)
    mesh = getattr(ctx.state, "mesh", None)
    window = _op_window(ctx, S_kv)
    _m_lowered.inc(shape="mla" if qr is not None
                   else "gqa" if group > 1 else "mha",
                   path="sequence_parallel" if sp_active
                   else "flash" if flash else "composition", layout="bhsd",
                   window=window)
    if window and (sp_active or qr is not None):
        raise NotImplementedError(
            "fused_attention: a sliding window %s"
            % ("under sequence parallelism" if sp_active
               else "with a rotary pair"))
    if group > 1 and not flash:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    rope = None
    if qr is not None and flash:
        rope = (_flat(qr), kr.reshape(B, S_kv, -1))
    elif qr is not None:
        if sp_active:
            raise NotImplementedError(
                "fused_attention: a rotary pair under sequence parallelism")
        q = jnp.concatenate([q, qr], axis=-1)
        k = jnp.concatenate(
            [k, jnp.broadcast_to(kr, k.shape[:3] + kr.shape[3:])], axis=-1)

    if sp_active and (S_q != S_kv or dropout):
        # cross-attention and/or attention dropout: q rows stay sharded,
        # kv all-gathered in-island (VERDICT r4 item 6a/6b)
        put(_sp_gather_attention(q, k, v, mesh, sp_axis, float(scale),
                                 causal, _norm_bias(bias, q, S_kv),
                                 dropout, ctx.rng() if dropout else None))
        return
    if sp_active:
        put(_sp_attention(q, k, v, mesh, sp_axis,
                          ctx.attr("sp_mode", "ring"), float(scale),
                          causal, bias=_norm_bias(bias, q, S_kv)))
        return
    if dropout:
        # probability dropout has no in-kernel flash story — exact
        # composition, per-op key (ctx.rng() already folds axis_env +
        # extra axes; replayed identically by the grad op: __op_seed__
        # rides the grad attrs)
        put(_attn_core_remat(float(scale), causal, dropout, window=window)(
            q, k, v, _norm_bias(bias, q, S_kv), 0, ctx.rng()))
        return
    args = (_flat(q), _flat(k), _flat(v), _kernel_bias(bias, q, S_kv),
            float(scale), causal, rope, window)
    if flash and op.output("LSE") and not _is_test(ctx):
        out, lse = flash_attention_lse(*args)
        ctx.set("LSE", lse.reshape(B, H, S_q))
    else:
        out = flash_attention(*args)
    put(out.reshape(B, H, S_q, v.shape[3]))


def _heads_major_shape(x, heads):
    """The shape and dtype ``_heads_major`` would give, with nothing
    traced."""
    return jax.eval_shape(functools.partial(_heads_major, heads=heads), x)


def _op_heads_major(q, k, v, qr, kr, heads):
    """The operands of an op in the heads-minor layout (Q ``[B, S_q, H *
    D]``, K ``[B, S_kv, H_kv * D]``, V ``[B, S_kv, H_kv * D_v]``, a rotary
    pair ``[B, S_q, H * R]`` / ``[B, S_kv, R]``) as ``[B, H | H_kv | 1, S,
    D]``.  The key/value heads are as many as K is wide in heads of Q's
    size."""
    D = q.shape[2] // heads
    if D * heads != q.shape[2] or k.shape[2] % D:
        raise ValueError(
            "fused_attention: Q %s and K %s are not whole heads at "
            "num_heads=%d" % (q.shape, k.shape, heads))
    heads_kv = k.shape[2] // D
    return (_heads_major(q, heads), _heads_major(k, heads_kv),
            _heads_major(v, heads_kv),
            None if qr is None else _heads_major(qr, heads),
            None if kr is None else kr[:, None])


def _op_in_place(ctx, q, k, v, heads):
    """Whether an op (or its grad op) whose operands are ``[B, S, heads *
    D]`` runs the kernels on them as they lie: where the kernels have a
    tile (``_attention_route``'s ``kernels``: no sequence-parallel mesh)
    at a shape ``_in_place`` takes, with as many key/value heads as query
    heads and no rotary pair.  With attention dropout the kernels draw the
    mask themselves where they hold less than the composition
    (``_drop_in_kernels``) and the bias wants no gradient
    (``_bias_may_want_grad``: the dbias pass has no mask); every other op
    with dropout composes."""
    if ctx.has_input("QRope") or k.shape[2] != q.shape[2] or \
            v.shape[2] != q.shape[2] or q.shape[2] % heads or \
            _op_window(ctx, k.shape[1]):
        return False
    major = [_heads_major_shape(x, heads) for x in (q, k, v)]
    _, dropout, _, kernels = _attention_route(ctx, *major)
    if dropout and (_bias_may_want_grad(ctx) or not _drop_in_kernels(
            q.shape[1], k.shape[1], q.shape[2] // heads)):
        return False
    return kernels and _in_place(
        heads, *_in_place_shape(q, k, ctx.i_opt("BiasQK"),
                                ctx.attr("causal", False), heads))


def _drop_in_kernels(S_q, S_kv, D):
    """Whether an op with attention dropout holds less in the kernels than
    in the composition, from a head's shape: where its scores outnumber
    the operands the kernels keep for it from the forward to the backward,
    ``S_q * S_kv > (S_q + 2 * S_kv) * D``.  A Mosaic call reads Q, K and V
    as they lie in HBM, and the step keeps them whole, float32 under the
    BERT cells' pure-bf16 AMP, until the backward's call; the composition
    instead forms a layer's ``[B, H, S_q, S_kv]`` scores, probabilities
    and mask, one layer at a time, and XLA keeps less of the projections
    for its replay.  Compiled here for a v5e (PERF.md section 6, PR 40):
    at S=512, D=64, batch 16 the kernels' step reserves 183 MB LESS than
    the composition's; at S=128, batch 128 1.18 GB MORE, the projections'
    outputs (``role_fwd/fluid_mul``) 3.36 GB of it against 1.67.  Without
    dropout the kernels have no composition to weigh against: the rule is
    dropout's alone."""
    return S_q * S_kv > (S_q + 2 * S_kv) * D


def _bias_may_want_grad(ctx):
    """Whether the op's bias is a variable that a backward may
    differentiate: one that is not ``stop_gradient`` (``append_backward``
    gives the grad op a ``BiasQK@GRAD`` exactly then).  Read from the
    variable, so a forward op and its grad op agree."""
    names = ctx.op.input("BiasQK")
    if not names or not names[0]:
        return False
    var = ctx.block._find_var_recursive(names[0])
    return var is None or not var.stop_gradient


_m_lowered = telemetry.counter(
    "fused_attention_lowered_total",
    "fused_attention lowerings traced (a grad op that replays the forward "
    "counts again), by shape ('mla': with a rotary pair, 'gqa': fewer "
    "key/value heads than query heads, 'mha': neither) "
    "and path ('flash': the Pallas kernels, 'composition': XLA, "
    "'sequence_parallel': a shard_map island) and layout ('bshd': an op "
    "with num_heads whose [B, S, H * D] operands the kernels read in "
    "place, 128 // D heads a cell; 'bhsd': [B, H, S, D] operands, the "
    "op's own or split from [B, S, H * D] inside the lowering) and window "
    "(the sliding window the call runs with; 0: none)")

_m_grad_lowered = telemetry.counter(
    "fused_attention_grad_lowered_total",
    "fused_attention_grad ops lowered, by path ('residual' runs the flash "
    "backward kernels on the forward op's LSE, 'replay' differentiates a "
    "second run of the forward lowering) and by how dK / dV are summed "
    "over the query heads of a key/value head (kv_sum: 'none' at H_kv == "
    "H, 'partials': float32 parts a query head out of the dK/dV pass, "
    "summed outside it, 'repeat': the transpose of the composition's "
    "repeat)")


@register_grad_lower("fused_attention")
def _fused_attention_grad(ctx, op):
    """Where the forward op ran the flash kernels and left its ``LSE``,
    run the backward kernels directly on Q, K, V, BiasQK, ``Out@GRAD`` and
    ``LSE``: ``generic_grad_lower``'s replay would trace a second
    ``flash_fwd`` Mosaic call, which XLA cannot merge with the forward
    op's (it folds a replay of plain HLO, never a custom call).
    Everywhere else — sequence-parallel islands, the dropout composition,
    non-tileable shapes, a program built without the ``LSE`` slot — the
    replay stands.  An op whose kernels drew a dropout mask draws it again
    from the same key (``_dropout_seed``).

    An op with ``num_heads`` (operands ``[B, S, H * D]``,
    ``_fused_attention``) whose shape the kernels read in place runs ONE
    ``flash_bwd`` on them as they lie and writes dQ, dK and dV the same
    way, unless the bias wants its gradient (the dbias pass writes a
    head's ``[S_q, S_kv]`` from split heads); every other one is split,
    takes the path below and is merged."""
    from ..lowering import generic_grad_lower

    q, k, v = ctx.i("Q"), ctx.i("K"), ctx.i("V")
    lse, g = ctx.i_opt("LSE"), ctx.i_opt("Out@GRAD")
    qr, kr = ctx.i_opt("QRope"), ctx.i_opt("KRope")
    out = ctx.i_opt("Out")
    want = {slot: (op.output(slot + "@GRAD") or [""])[0]
            for slot in ("Q", "K", "V", "BiasQK", "QRope", "KRope")}
    heads = int(ctx.attr("num_heads", 0) or 0)
    if heads:
        if lse is not None and g is not None and not want["BiasQK"] and \
                _op_in_place(ctx, q, k, v, heads):
            _m_grad_lowered.inc(path="residual", kv_sum="none")
            rate = _op_dropout(ctx)
            grads = _backward_in_place(
                q, k, v, _kernel_bias(ctx.i_opt("BiasQK"),
                                      _heads_major_shape(q, heads),
                                      k.shape[1]),
                float(ctx.attr("scale", 1.0)),
                bool(ctx.attr("causal", False)), heads, _flat(lse), g,
                rate, _dropout_seed(ctx) if rate else None)
            for slot, grad in zip(("Q", "K", "V"), grads):
                if want[slot]:
                    ctx.env[want[slot]] = grad
            return
        # the backward kernels on [B, H, S, D] between a split and a merge
        # (``_fused_attention``); a replay below splits for itself
        q, k, v, qr, kr = _op_heads_major(q, k, v, qr, kr, heads)
        g, out = (x if x is None else _heads_major(x, heads)
                  for x in (g, out))
    S_q, S_kv = q.shape[2], k.shape[2]
    grouped = q.shape[1] != k.shape[1]
    if lse is None or g is None or \
            not _attention_route(ctx, q, k, v, qr)[2]:
        _m_grad_lowered.inc(path="replay",
                            kv_sum="repeat" if grouped else "none")
        generic_grad_lower(ctx, op, residual_slots=("LSE",))
        return
    _m_grad_lowered.inc(path="residual",
                        kv_sum="partials" if grouped else "none")
    bias = ctx.i_opt("BiasQK")
    rope = None if qr is None else \
        (_flat(qr), kr.reshape(kr.shape[0], S_kv, -1))
    if want["BiasQK"]:
        # the transpose of the bias's broadcast sums dbias back to its shape
        bf, bias_vjp = jax.vjp(
            lambda b: _kernel_bias(b, q, S_kv, per_head=True), bias)
    else:
        bf = _kernel_bias(bias, q, S_kv)
    dq, dk, dv, dbias = _backward_from_lse(
        _flat(q), _flat(k), _flat(v), bf, float(ctx.attr("scale", 1.0)),
        bool(ctx.attr("causal", False)), _flat(lse),
        None if _delta_in_kernel(S_kv, bool(ctx.attr("causal", False)),
                                 bias is not None)
        else _flat(out),
        _flat(g.astype(q.dtype)), bias_grad=bool(want["BiasQK"]), rope=rope,
        window=_op_window(ctx, S_kv))
    grads = {}
    if rope is not None:
        (dq, dqr), (dk, dkr) = dq, dk
        grads = {"QRope": dqr.reshape(qr.shape),
                 "KRope": dkr.reshape(kr.shape)}
    grads.update({"Q": dq.reshape(q.shape), "K": dk.reshape(k.shape),
                  "V": dv.reshape(v.shape)})
    if heads:
        grads = {slot: grad[:, 0] if slot == "KRope" else _heads_minor(grad)
                 for slot, grad in grads.items()}
    if dbias is not None:
        grads["BiasQK"], = bias_vjp(dbias)
    for slot, name in want.items():
        if name:
            ctx.env[name] = grads[slot]


# ---------------------------------------------------------------------------
# the routed experts' sums by token
# ---------------------------------------------------------------------------
#
# ``row_sum``: each token's float32 sum of the live rows of a row buffer
# that belong to it (the combine, weighted; the dispatch's backward), the
# buffer streamed once and only its live rows added, in their order.  A
# grid cell reads ``block`` rows of a column chunk and adds each live one
# into its token's row of the chunk's output, which stays in VMEM while the
# whole buffer passes (grid: chunk, then row block): an output row is read
# and written in VMEM, and the buffer's rows need no copy of their own.
# The composition it replaces gathered ``[T, H]`` once a choice, every
# token whether that choice is held here or not: ``k`` gathers of ``T``
# rows for ``T * k * held / E`` live ones, and XLA:TPU's row gather costs
# about 45 ns a row on a v5e at these widths, whatever the row's bytes.  A
# single row is no slice a DMA of a tiled HBM array takes (a tile holds 8
# rows), so the sum streams the buffer rather than fetching its rows one by
# one (PERF.md section 6).

# rows of the buffer a grid cell reads, at most
_SUM_BLOCK = 512
# VMEM for a column chunk's float32 output, both of its buffers
_SUM_OUT_BYTES = 32 << 20


def _sum_block(R):
    """Rows a grid cell reads: the largest power of two from
    ``_SUM_BLOCK`` down to 8 that divides ``R`` (``R`` itself below 8)."""
    block = _SUM_BLOCK
    while block > 8 and R % block:
        block //= 2
    return block if R % block == 0 else R


def _sum_columns(T, H):
    """Lanes of a column chunk: the widest multiple of 128 that divides
    ``H`` and whose ``[T, W]`` float32 output, double-buffered, fits
    ``_SUM_OUT_BYTES``; ``H`` itself where it is no multiple of 128."""
    if H % 128:
        return H
    for W in range(H, 128, -128):
        if H % W == 0 and 2 * T * W * 4 <= _SUM_OUT_BYTES:
            return W
    return 128


def _row_sum_kernel(n_ref, token_ref, *refs, block, weighted):
    """One grid cell (chunk ``c``, rows ``i * block ..``): ``n_ref`` [1]
    the live rows; ``token_ref`` / ``w_ref`` [1, 1, block] (SMEM) each
    row's token and weight; ``out_ref`` [T, W] float32, the chunk's sums,
    zeroed by the chunk's first cell."""
    if weighted:
        w_ref, rows_ref, out_ref, rows32 = refs
    else:
        rows_ref, out_ref, rows32 = refs
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    live = jnp.clip(n_ref[0] - i * block, 0, block)

    @pl.when(live > 0)
    def _():
        rows32[...] = rows_ref[...].astype(jnp.float32)

        def add(r, carry):
            row = rows32[pl.ds(r, 1), :]
            if weighted:
                row = row * w_ref[0, 0, r]
            t = token_ref[0, 0, r]
            out_ref[pl.ds(t, 1), :] = out_ref[pl.ds(t, 1), :] + row
            return carry
        jax.lax.fori_loop(0, live, add, 0)


def row_sum(rows, token_of, n_live, tokens, weight=None):
    """``[tokens, H]`` float32: ``out[t]`` is the sum over the live rows
    ``r < n_live`` with ``token_of[r] == t`` of ``rows[r]`` in float32
    (times ``weight[r]``), added in the order of the rows.  ``rows`` [R, H]
    (a row past ``n_live`` is never read: it may hold anything),
    ``token_of`` [R] int32, ``weight`` [R] float32.  Pallas call
    ``moe_row_sum``."""
    R, H = rows.shape
    pad = -R % 8 if R > 8 else 0
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        token_of = jnp.pad(token_of, (0, pad))
        if weight is not None:
            weight = jnp.pad(weight, (0, pad))
    block = _sum_block(R + pad)
    W = _sum_columns(tokens, H)
    cells = (R + pad) // block
    scalars = functools.partial(pl.BlockSpec, (1, 1, block),
                                lambda c, i: (i, 0, 0),
                                memory_space=pltpu.SMEM)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), scalars()]
    operands = [jnp.reshape(n_live, (1,)).astype(jnp.int32),
                token_of.astype(jnp.int32).reshape(cells, 1, block)]
    if weight is not None:
        in_specs.append(scalars())
        operands.append(weight.astype(jnp.float32).reshape(cells, 1, block))
    in_specs.append(pl.BlockSpec((block, W), lambda c, i: (i, c)))
    operands.append(rows)
    need = 2 * tokens * W * 4 + 2 * block * W * rows.dtype.itemsize + \
        block * W * 4
    return _pallas_call(
        functools.partial(_row_sum_kernel, block=block,
                          weighted=weight is not None),
        "moe_row_sum", vmem_limit_bytes=_vmem_limit(need),
        cache_key=(block, weight is not None, tokens, W),
        grid=(H // W, cells), in_specs=in_specs,
        out_specs=pl.BlockSpec((tokens, W), lambda c, i: (0, c)),
        out_shape=jax.ShapeDtypeStruct((tokens, H), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, W), jnp.float32)],
    )(*operands)


# ---------------------------------------------------------------------------
# fused layer norm
# ---------------------------------------------------------------------------

def _layer_norm_kernel(x_ref, scale_ref, bias_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)              # [bm, D]
    mean = x.mean(axis=-1, keepdims=True)
    xc = x - mean
    var = (xc * xc).mean(axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y = y * scale_ref[:].astype(jnp.float32) + bias_ref[:] \
        .astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _pallas_layer_norm(x2d, scale, bias, eps):
    """x2d [M, D] → normalized rows, one VMEM pass (mean/var/affine fused;
    XLA usually emits the same fusion — the kernel guarantees it and is
    the template for deeper fusions like norm+matmul)."""
    M, D = x2d.shape
    block_m = 128
    while M % block_m and block_m > 1:
        block_m //= 2
    return _pallas_call(
        functools.partial(_layer_norm_kernel, eps=eps), "fused_layer_norm",
        grid=(M // block_m,),
        in_specs=[pl.BlockSpec((block_m, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_m, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, D), x2d.dtype),
    )(x2d, scale, bias)


def _reference_layer_norm(x2d, scale, bias, eps):
    xm = x2d.astype(jnp.float32)
    mean = xm.mean(axis=-1, keepdims=True)
    var = xm.var(axis=-1, keepdims=True)
    y = (xm - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) +
            bias.astype(jnp.float32)).astype(x2d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x2d, scale, bias, eps):
    return _pallas_layer_norm(x2d, scale, bias, eps)


def _ln_fwd(x2d, scale, bias, eps):
    return _pallas_layer_norm(x2d, scale, bias, eps), (x2d, scale, bias)


def _ln_bwd(eps, res, g):
    x2d, scale, bias = res
    _, vjp = jax.vjp(
        lambda a, s, b: _reference_layer_norm(a, s, b, eps), x2d, scale,
        bias)
    return vjp(g)


fused_layer_norm.defvjp(_ln_fwd, _ln_bwd)


@register_op("fused_layer_norm")
def _fused_layer_norm_op(ctx, op):
    """Pallas layer norm over the last axis (begin_norm_axis folds leading
    dims); same contract as the layer_norm op."""
    x = ctx.i("X")
    scale = ctx.i_opt("Scale")
    bias = ctx.i_opt("Bias")
    eps = ctx.attr("epsilon", 1e-5)
    bna = ctx.attr("begin_norm_axis", 1)
    lead = x.shape[:bna]
    D = int(np.prod(x.shape[bna:]))
    x2d = x.reshape((-1, D))
    if scale is None:
        scale = jnp.ones((D,), x.dtype)
    if bias is None:
        bias = jnp.zeros((D,), x.dtype)
    y = fused_layer_norm(x2d, scale.reshape(-1), bias.reshape(-1),
                         float(eps))
    ctx.set("Y", y.reshape(x.shape))
    xm = x2d.astype(jnp.float32)
    ctx.set("Mean", xm.mean(axis=-1).reshape(lead))
    ctx.set("Variance", xm.var(axis=-1).reshape(lead))
