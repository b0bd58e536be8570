"""Ops of decoder-only language models (``models/deepseek_v3.py`` and the
models after it): RMS norm, rotary embedding on a head's slice, the gated
short convolution, the routed-expert layer and the rule that moves its
selection bias.  The reference predates all of them.

``routed_experts`` is an expert layer that is TOLD which experts it holds
(attributes ``first_expert`` and the leading dimension of its weights): it
routes over all ``E`` experts of the model — float32 sigmoid scores, the
``top_k`` largest of score + selection bias, weights from the scores alone,
normalised and scaled; or, with ``scoring_func="softmax"``, the softmax
over the chosen logits; from the experts' input or from a second one, the
op's ``RouterX`` — and computes the part of ``sum_i w_i E_i(x)`` that
the experts held here give.  What the absent experts would add is left out:
in an expert-parallel deployment it arrives through the exchange this
single-chip lowering does not have, and nothing stands in for it.  There is
no capacity and no dropped token: the assignments are sorted by expert, the
rows of the held ones gathered, and three grouped matmuls (a gated unit:
SwiGLU, or ReGLU with ``hidden_act="relu"``) run over
the rows actually present — ``jax.lax.ragged_dot``, which XLA:TPU compiles
to its own Mosaic grouped-matmul kernel that skips the tiles past the last
group, and whose transpose rules give both backward products.  (On the
chip a hand-written Pallas kernel over tile-aligned groups ran the forward
product in 0.25 ms against ``ragged_dot``'s 0.57 at 3072 live rows in a
worst-case buffer of 24576 when a whole expert matrix was one block, and in
1.8 ms with 128-wide column blocks; it has no backward yet.  PERF.md
section 6, PR 28.)

The row buffers follow the rows that came.  Their size is a RUNG, and the
ladder comes from shapes alone (``_rungs``): twice the rows an even router
sends to the experts held here, rounded up to 512, then every assignment
(``T * top_k``) — 6144 | 24576 rows for 4096 tokens choosing 6 of 64 with
8 held.  Each step, each layer, a ``lax.cond`` on the step's own
``group_sizes`` takes the first rung that holds the rows (``_first_rung``:
gather, SwiGLU and the token-side sums over 6144 rows) and the last one
otherwise (``_every_row``: the layer as it was before it had rungs), so
nothing is ever dropped, capped or approximated.  The conditional lives
inside one ``custom_vjp`` (``_ladder``) whose forward keeps the first
rung's rows alone and whose last rung computes its rows again in the
backward: the rung that runs every step pays nothing for the other.  The
Fluid op hands those rows to its grad op through the ``Kept`` outputs (XLA
merges a replayed forward's plain HLO with the forward op's, never two
conditionals).  A layer whose first rung would be half the last or more —
every layer that holds all ``E`` experts, and small shapes — has one rung
and traces no conditional.  PERF.md section 6, PR 31.

The token side's sums (the weighted sum back to the tokens, and the
dispatch's backward) are ``pallas_ops.row_sum``: the row buffer read once
up to its last live row, each row added into its token's float32 sum;
the gathers of rows stay XLA's.  PERF.md section 6.

The rotary embedding (``rotary``) is one pass over its operand each way:
the rotate-half and the de-interleave are products by constant signed
permutations, exact on the MXU, and the backward (a ``custom_vjp``) is the
same pass with the matrices transposed.  The slices it replaced,
``[-x[D/2:], x[:D/2]]`` of a float32 copy, cut a 128-lane tile at lane 64,
which XLA:TPU does not fuse: it wrote each float32 half to HBM at 128
lanes, 17.8 ms of a SmallThinker step and 18.7 of Ouro's on the chip
where reading Q and K and writing them once costs about 3 and 6.  PERF.md
section 6.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..lowering import amp_operands
from . import pallas_ops
from ..registry import register_grad_lower, register_op

_HIGHEST = jax.lax.Precision.HIGHEST

_m_experts_lowered = telemetry.counter(
    "moe_experts_lowered_total",
    "routed_experts lowerings traced, by the path of its grouped matmuls "
    "(a training step traces each op twice: the forward op and its replay "
    "inside the grad op)")
_m_token_rows = telemetry.counter(
    "moe_token_rows_lowered_total",
    "routed_experts sums by token traced (Pallas call moe_row_sum), by form "
    "(combine: the weighted sum of the rows back to the tokens; dx: the "
    "dispatch's backward) and the row buffer's rows")


@register_op("rms_norm")
def _rms_norm(ctx, op):
    """Y = Scale * X / sqrt(mean(X^2, last axis) + epsilon), statistics in
    float32 whatever X's dtype."""
    x = ctx.i("X")
    xm = x.astype(jnp.float32)
    y = xm * jax.lax.rsqrt(jnp.mean(xm * xm, axis=-1, keepdims=True)
                           + ctx.attr("epsilon", 1e-5))
    scale = ctx.i_opt("Scale")
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    ctx.set("Y", y.astype(x.dtype))


_m_rotary_lowered = telemetry.counter(
    "rotary_lowered_total",
    "rotary_embedding lowerings traced, by the lanes' pairing "
    "(interleaved) and head_dim (a training step traces each op twice: "
    "the forward op and its replay inside the grad op)")


def _lane_matrices(D, interleaved):
    """``(A, AR)``, numpy ``[D, D]``: ``x @ A`` is ``x`` de-interleaved
    (evens first, then odds; None where the lanes pair as they are) and
    ``x @ AR`` the rotate-half of that, ``[-x_a[D/2:], x_a[:D/2]]``.  One
    entry of +-1 a column: a product by either is exact."""
    h = D // 2
    rot = np.zeros((D, D), np.float32)
    rot[np.arange(h) + h, np.arange(h)] = -1.0
    rot[np.arange(h), np.arange(h) + h] = 1.0
    if not interleaved:
        return None, rot
    deint = np.zeros((D, D), np.float32)
    deint[2 * np.arange(h), np.arange(h)] = 1.0
    deint[2 * np.arange(h) + 1, np.arange(h) + h] = 1.0
    return deint, deint @ rot


def _turn(x, first, second, angles):
    """``(x @ first) * cos + (x @ second) * sin`` in float32, cast to
    ``x``'s dtype: each element of ``x`` read once, each of the result
    written once.  ``first`` None is the identity; the products are
    exact (bf16 operands on the MXU with a float32 sum; float32 at
    ``HIGHEST``); ``angles`` [S, D] are broadcast over batch and heads."""
    def lanes(m):
        return jnp.einsum(
            "bsnd,de->bsne", x, jnp.asarray(m, x.dtype),
            preferred_element_type=jnp.float32,
            precision=_HIGHEST if x.dtype == jnp.float32 else None)

    a = x.astype(jnp.float32) if first is None else lanes(first)
    angles = angles[None, :, None, :]
    return (a * jnp.cos(angles) + lanes(second) * jnp.sin(angles)) \
        .astype(x.dtype)


def _angles(S, D, theta, adjacent):
    """Position times frequency, [S, D] float32: frequency ``i`` on lanes
    ``i`` and ``i + D/2`` (the rotate-half's order), or with ``adjacent``
    on lanes ``2i`` and ``2i + 1`` (an interleaved input's own order)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    if adjacent:
        inv_freq = jnp.repeat(inv_freq, 2)
    else:
        inv_freq = jnp.concatenate([inv_freq, inv_freq])
    return jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def rotary(x, theta, interleaved=True):
    """Rotary position embedding over the last axis of ``x`` [B, S, heads,
    D], positions 0..S-1 (full sequences, packed from 0).

    ``interleaved`` (the ``deepseek_v3`` layout): the published weights
    pair lane 2i with lane 2i+1, and the source (``modeling_deepseek.py:
    apply_rotary_pos_emb``) de-interleaves the lanes (evens first, then
    odds) before the usual rotate-half; the result stays in that
    de-interleaved order, for Q and K alike, so the scores are unchanged.
    Not ``interleaved`` (the Llama layout): the weights pair lane i with
    lane i + D/2 already, and the rotate-half runs on the lanes as they
    are.

    ONE pass over ``x`` each way: the de-interleave and the rotate-half
    are products by constant ``[D, D]`` signed permutations
    (``_lane_matrices``), and the backward is the same pass with the
    matrices transposed and the angles in the input's lane order, ``dx =
    (g A^T) cos + (g (AR)^T) sin``: ``sin`` is equal on the two lanes of
    a pair, so ``R^T (g sin) = (R^T g) sin``, and the backward forms the
    products differentiation forms, in float32, and sums them once.
    Written as slices, ``[-x[D/2:], x[:D/2]]`` of a float32 copy, the
    half at lane 64 of a 128-lane tile is no fusion XLA:TPU makes: it
    wrote each float32 half to HBM at 128 lanes, half of them padding,
    17.8 ms of a SmallThinker step and 18.7 of Ouro's on the chip against
    a 3.0 ms HBM floor for reading Q and K and writing them once in each
    of SmallThinker's 9 passes (PERF.md section 6)."""
    B, S, N, D = x.shape
    first, second = _lane_matrices(D, interleaved)
    return _turn(x, first, second,
                 _angles(S, D, theta, adjacent=False))


def _rotary_fwd(x, theta, interleaved):
    return rotary(x, theta, interleaved), None


def _rotary_bwd(theta, interleaved, _, g):
    B, S, N, D = g.shape
    first, second = _lane_matrices(D, interleaved)
    return (_turn(g, None if first is None else first.T, second.T,
                  _angles(S, D, theta, adjacent=interleaved)),)


rotary.defvjp(_rotary_fwd, _rotary_bwd)


@register_op("rotary_embedding")
def _rotary_embedding(ctx, op):
    """X [B, S, heads, D] -> Out, the same shape: rotary embedding with
    base ``theta`` on the whole last axis (the caller hands over the
    rotary slice of the head); ``interleaved`` says how the lanes pair."""
    x, interleaved = ctx.i("X"), bool(ctx.attr("interleaved", True))
    _m_rotary_lowered.inc(interleaved=interleaved, head_dim=int(x.shape[-1]))
    ctx.set("Out", rotary(x, float(ctx.attr("theta", 10000.0)),
                          interleaved))


# -- the gated short convolution -------------------------------------------------

_m_short_conv_lowered = telemetry.counter(
    "gated_short_conv_lowered_total",
    "gated_short_conv lowerings traced, by kernel_size (a training step "
    "traces each op twice: the forward op and its replay inside the grad "
    "op)")


def gated_short_conv(bcx, w):
    """``bcx`` [B, S, 3C] (the gates B and C and the signal x, in that
    order along the last axis), ``w`` [C, L] float32 -> [B, S, C]:

        z_t = B_t * x_t
        c_t = sum_{j < L} w[:, j] * z_{t - (L - 1) + j}     (z = 0 before 0)
        out_t = C_t * c_t

    a causal depthwise cross-correlation of length L, padded on the left,
    between two gates.  L shifted multiply-adds: the products ``B * x`` and
    their shifts in the activations' dtype, the taps and the sum over them
    in float32.  No ``conv_general_dilated`` (a C-group convolution for L
    multiply-adds a channel) and nothing but elementwise work, pads and
    slices, so the ``jax.vjp`` of this body is elementwise too; all of it
    under ONE ``short_conv`` scope, which XLA's fusions carry forward and
    backward.

    The body is a ``jax.checkpoint``: what goes from forward to backward is
    ``bcx`` and the taps alone.  A shift by one or two rows is no view of a
    tiled array, so XLA materialises each shifted product, and left to
    itself keeps them from the forward for the taps' gradient: L - 1
    ``[B, S, C]`` arrays a layer (537 MB over four layers at S=8192,
    C=2048, compiled for a v5e; PERF.md section 6, PR 34)."""
    @jax.checkpoint
    def body(bcx, w):
        C, L = w.shape
        S = bcx.shape[1]
        gate_b, gate_c, x = (bcx[..., i * C:(i + 1) * C] for i in range(3))
        z = gate_b * x
        taps = w.astype(jnp.float32)
        conv = z.astype(jnp.float32) * taps[:, L - 1]
        for j in range(L - 1):
            back = L - 1 - j                 # tap j reads z_{t - back}
            if back < S:
                shifted = jnp.pad(z[:, :S - back],
                                  ((0, 0), (back, 0), (0, 0)))
                conv = conv + taps[:, j] * shifted.astype(jnp.float32)
        return (gate_c.astype(jnp.float32) * conv).astype(bcx.dtype)

    with jax.named_scope("short_conv"):
        return body(bcx, w)


@register_op("gated_short_conv")
def _gated_short_conv(ctx, op):
    """X [B, S, 3C] (B, C, x along the last axis); W [C, kernel_size]
    float32 -> Out [B, S, C] (``gated_short_conv``).  The grad op replays
    this body under ``jax.vjp``: elementwise work, which XLA merges with the
    forward op's."""
    w = ctx.i("W")
    _m_short_conv_lowered.inc(kernel_size=int(w.shape[1]))
    ctx.set("Out", gated_short_conv(ctx.i("X"), w))


# -- the routed-expert layer ---------------------------------------------------

_SCORES = {"sigmoid": jax.nn.sigmoid, "softmax": lambda logits: logits}


def route(x, router_w, select_bias, top_k, scale, scoring_func="sigmoid"):
    """``(idx [T, k] int32, weight [T, k] float32, load [E] float32)`` from
    the router's input ``x`` [T, H] (the experts' own input, or another:
    the op's ``RouterX``): float32 scores over all E experts; the ``top_k``
    largest of score + bias are chosen (ties: the lower index), the bias
    chooses and does not weigh; ``load`` counts the tokens that chose each
    expert.  ``scoring_func`` ``sigmoid``: the scores are the logits'
    sigmoids and the weights the chosen scores over their sum (+1e-20),
    times ``scale``.  ``softmax``: the scores are the logits themselves and
    the weights the softmax over the CHOSEN logits, times ``scale``, which
    is the softmax over all E, chosen and renormalised."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=_HIGHEST)
    scores = _SCORES[scoring_func](logits)
    _, idx = jax.lax.top_k(
        jax.lax.stop_gradient(scores) + select_bias.astype(jnp.float32),
        top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    if scoring_func == "sigmoid":
        weight = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20) * scale
    else:
        weight = jax.nn.softmax(chosen, axis=-1) * scale
    E = router_w.shape[-1]
    load = (idx[..., None] == jnp.arange(E)).sum(axis=(0, 1))
    return idx, weight, load.astype(jnp.float32)


def _narrowed(rows, dtype):
    """``rows`` as the grouped matmul gave them, or in ``dtype`` where that
    is narrower: the token side sums in float32 either way, and a row
    widened only to be summed is the same numbers in twice the bytes."""
    if jnp.promote_types(rows.dtype, dtype) != dtype:
        return rows.astype(dtype)
    return rows


def _sum_rows(rows, token_of, n_live, tokens, dtype, w_row=None):
    """``pallas_ops.row_sum`` in ``dtype``: each token's float32 sum of
    its live rows (times ``w_row``), a row wider than ``dtype`` rounded to
    it first (counted: one lowering traced)."""
    _m_token_rows.inc(form="dx" if w_row is None else "combine",
                      rows=str(rows.shape[0]))
    return pallas_ops.row_sum(_narrowed(rows, dtype), token_of, n_live,
                              tokens, w_row).astype(dtype)


def _row_weights(weight, order, held):
    """``[R]`` float32: the weight of each sorted row's assignment, zero
    for the assignments held elsewhere."""
    return jnp.where(held, weight, 0).reshape(-1)[order]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(dtypes, x, token_of, slot_of, n_live):
    """Rows of ``x`` [T, H] in sorted-assignment order, [T * k, H] in the
    rows' dtype (``dtypes`` = (rows', x's)): row r is token
    ``token_of[r]``.  Backward: each token sums its live rows
    (``_sum_rows``), never a scatter-add; ``slot_of`` [T, k] gives the
    tokens' count."""
    return x[token_of].astype(dtypes[0])


def _dispatch_fwd(dtypes, x, token_of, slot_of, n_live):
    return _dispatch(dtypes, x, token_of, slot_of, n_live), \
        (token_of, slot_of, n_live)


def _dispatch_bwd(dtypes, res, g):
    token_of, slot_of, n_live = res
    return _sum_rows(g, token_of, n_live, slot_of.shape[0], dtypes[1]), \
        None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _combine_backward(ys, weight, order, token_of, slot_of, held, g):
    """``(dys [R, H], dweight [T, k])`` of the weighted sum by token for
    the cotangent ``g`` [T, H]: ``g`` gathered by row as the forward
    gathered ``x``, and the weights' gradient formed on the row side,
    ``<ys[r], g[token_of[r]]>`` over the rows and then a gather of
    scalars.  A row past the live ones has a zero weight; its ``ys`` may
    be anything a grouped matmul left there, and its product is read
    nowhere."""
    g_rows = g[token_of].astype(jnp.float32)                  # [R, H]
    dys = (g_rows * _row_weights(weight, order, held)[:, None]) \
        .astype(ys.dtype)
    dw_row = (ys.astype(jnp.float32) * g_rows).sum(axis=-1)
    return dys, jnp.where(held, dw_row[slot_of], 0).astype(weight.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _combine(y, weight, order, token_of, slot_of, held, n_live, dtype):
    """out[t] = sum_k weight[t, k] * y[slot_of[t, k]] over the held
    assignments (float32 sum), [T, H] in ``dtype``; the backward gathers
    ``d out`` by row, as the forward gathered ``x``."""
    return _sum_rows(y, token_of, n_live, weight.shape[0], dtype,
                     _row_weights(weight, order, held))


def _combine_fwd(y, weight, order, token_of, slot_of, held, n_live, dtype):
    return _combine(y, weight, order, token_of, slot_of, held, n_live,
                    dtype), (y, weight, order, token_of, slot_of, held)


def _combine_bwd(dtype, res, g):
    return _combine_backward(*res, g) + (None,) * 5


_combine.defvjp(_combine_fwd, _combine_bwd)


def _plan(idx, first_expert, n_held):
    """The step's assignments ``idx`` [T, k] sorted by held expert:
    ``order`` [T * k] (sorted row -> flat assignment; assignments to
    absent experts sort behind every held group, so the live rows are a
    prefix), ``token_of`` [T * k] (the token of each sorted row),
    ``slot_of`` [T, k] (the row of each assignment), ``held`` [T, k] and
    ``group_sizes`` [n_held] (the rows of each held expert)."""
    T, top_k = idx.shape
    local = idx - first_expert
    held = (local >= 0) & (local < n_held)               # [T, k]
    key = jnp.where(held, local, n_held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    slot_of = jnp.argsort(order).astype(jnp.int32).reshape(T, top_k)
    group_sizes = (key[:, None] == jnp.arange(n_held)).sum(axis=0) \
        .astype(jnp.int32)
    return order, order // top_k, slot_of, held, group_sizes


# the first rung: this many times the rows an even router sends here,
# rounded up to a multiple of _RUNG_MULTIPLE
_RUNG_FACTOR = 2
_RUNG_MULTIPLE = 512


def _rungs(T, top_k, n_held, E):
    """The row counts the layer's buffers may take, from shapes alone.  An
    even router sends ``T * top_k * n_held / E`` rows to the experts held
    here; the first rung is ``_RUNG_FACTOR`` times that, the last is every
    assignment (``T * top_k``: nothing is ever dropped).  A first rung of
    half the last or more is not worth a conditional: ONE rung, the worst
    case — every uncut layer, and small shapes."""
    full = T * top_k
    first = -(-_RUNG_FACTOR * full * n_held // (E * _RUNG_MULTIPLE)) \
        * _RUNG_MULTIPLE
    return (full,) if 2 * first >= full else (first, full)


@contextlib.contextmanager
def _rows_scope(stage, R):
    """``moe_dispatch`` / ``moe_experts`` / ``moe_combine`` and, inside
    it, the rung whose rows the operations work on: under
    ``moe_rows_<R>`` a device trace shows which rung a step took."""
    with jax.named_scope(stage), jax.named_scope("moe_rows_%d" % R):
        yield


def _grouped(a, w, group_sizes, acc):
    return jax.lax.ragged_dot(
        a, w, group_sizes, preferred_element_type=acc,
        precision=_HIGHEST if a.dtype == jnp.float32 else None)


def _gate_up(xs, wg, wu, group_sizes, acc):
    return _grouped(xs, wg, group_sizes, acc), \
        _grouped(xs, wu, group_sizes, acc)


_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _gated(act, gate, up, dtype):
    """The gated unit's hidden rows, ``act(gate) * up``: SwiGLU with
    ``silu``, ReGLU with ``relu``."""
    return (_ACTIVATIONS[act](gate) * up).astype(dtype)


def _every_row(dtypes, x, weight, wg, wu, wd, plan):
    """The held experts' part of the routed sum through row buffers of
    every assignment (``T * k`` rows, whatever came): the last rung, and
    the whole layer where it has one.  Gather, grouped SwiGLU, weighted
    sum by token; differentiable as it stands (``_dispatch`` and
    ``_combine`` carry their backward).  ``dtypes``: the one the rows are
    computed in, the matmuls' accumulator (``amp_operands``) and the
    gate's activation (``_gated``)."""
    order, token_of, slot_of, held, group_sizes = plan
    rows_dtype, acc, act = dtypes
    R = order.shape[0]
    n_live = group_sizes.sum()
    with _rows_scope("moe_dispatch", R):
        xs = _dispatch((rows_dtype, x.dtype), x, token_of, slot_of,
                       n_live)                               # [T * k, H]
    with _rows_scope("moe_experts", R):
        gate, up = _gate_up(xs, wg, wu, group_sizes, acc)
        ys = _narrowed(_grouped(_gated(act, gate, up, rows_dtype), wd,
                                group_sizes, acc), x.dtype)
    with _rows_scope("moe_combine", R):
        return _combine(ys, weight, order, token_of, slot_of, held, n_live,
                        x.dtype)


def _first_rows(plan, R):
    """``plan`` for row buffers of ``R`` rows: the held assignments sort
    first, so the live rows are the prefix ``[:R]`` of the sorted order;
    the slot of an assignment that is not held is read nowhere and only
    has to lie inside the buffer."""
    order, token_of, slot_of, held, group_sizes = plan
    return order[:R], token_of[:R], jnp.minimum(slot_of, R - 1), held, \
        group_sizes


def _first_rung(R, dtypes, x, weight, wg, wu, wd, plan):
    """``(out [T, H], kept)``: what ``_every_row`` gives, through row
    buffers of ``R`` rows, for a step whose rows fit (``group_sizes.sum()
    <= R``), and the rows its backward reads: ``(xs [R, H], gate, up
    [R, I], ys [R, H])``.  The same rows, the same float32 sums;
    ``ys`` stays in the dtype the matmul gives where widening it to
    ``x``'s is exact (the sum is float32 either way: half the bytes for
    the token side to read)."""
    order, token_of, slot_of, held, group_sizes = _first_rows(plan, R)
    rows_dtype, acc, act = dtypes
    with _rows_scope("moe_dispatch", R):
        xs = x[token_of].astype(rows_dtype)                  # [R, H]
    with _rows_scope("moe_experts", R):
        gate, up = _gate_up(xs, wg, wu, group_sizes, acc)
        ys = _narrowed(_grouped(_gated(act, gate, up, rows_dtype), wd,
                                group_sizes, acc), x.dtype)
    with _rows_scope("moe_combine", R):
        out = _sum_rows(ys, token_of, group_sizes.sum(), x.shape[0],
                        x.dtype, _row_weights(weight, order, held))
    return out, (xs, gate, up, ys)


def _first_rung_backward(R, dtypes, x, weight, wg, wu, wd, plan, kept, g):
    """``(dx, dweight, dwg, dwu, dwd)`` of ``_first_rung(R, ...)[0]`` for
    the cotangent ``g`` [T, H], from the rows its forward ``kept``.  No
    matmul of the forward runs again: the grouped products are linear, and
    the primal side of their ``jax.vjp`` is dead code.  The combine's
    backward is ``_combine``'s (``_combine_backward``)."""
    order, token_of, slot_of, held, group_sizes = _first_rows(plan, R)
    rows_dtype, acc, act = dtypes
    xs, gate, up, ys = kept
    with _rows_scope("moe_combine", R):
        dy, dweight = _combine_backward(ys, weight, order, token_of, slot_of,
                                        held, g)
    with _rows_scope("moe_experts", R):
        hidden, gated_vjp = jax.vjp(
            lambda a, b: _gated(act, a, b, rows_dtype), gate, up)
        dhidden, dwd = jax.vjp(
            lambda h, w: _grouped(h, w, group_sizes, acc).astype(ys.dtype),
            hidden, wd)[1](dy)
        dxs, dwg, dwu = jax.vjp(
            lambda a, b, c: _gate_up(a, b, c, group_sizes, acc),
            xs, wg, wu)[1](gated_vjp(dhidden))
    with _rows_scope("moe_dispatch", R):
        dx = _sum_rows(dxs, token_of, group_sizes.sum(), x.shape[0],
                       x.dtype)
    return dx, dweight, dwg, dwu, dwd


def _cond_on_rows(rungs, plan, first_rung, last_rung, operands):
    """``first_rung(*operands)`` where the step's rows fit the first rung,
    else ``last_rung(*operands)``: a ``jax.lax.cond`` on the step's own
    ``group_sizes`` whose branches hold exactly what was traced into them.
    XLA's conditional code motion otherwise moves work across the
    boundary: out of it, the tail both branches share (the weighted sum
    over ``k`` left the conditional and the gathered ``[T, k, H]`` float32
    rows became its output, 201 MB a layer at the Moonlight cell's
    shapes); into it, the producers and users next to it (casts of the
    weights' gradients, twice).  Barriers on the operands, on each
    branch's result and on the conditional's pin all three."""
    def pinned(branch):
        return lambda *args: jax.lax.optimization_barrier(branch(*args))
    return jax.lax.optimization_barrier(jax.lax.cond(
        plan[-1].sum() <= rungs[0], pinned(first_rung), pinned(last_rung),
        *jax.lax.optimization_barrier(operands)))


def _forward_by_rung(rungs, dtypes, x, weight, wg, wu, wd, plan):
    """``(out, kept)`` through the first rung that holds the step's rows,
    chosen on the device.  The last rung keeps nothing (zeros in the first
    rung's shapes) and its backward computes its rows again: the rung that
    runs every step pays nothing for the one that almost never does."""
    first_rung = functools.partial(_first_rung, rungs[0], dtypes)

    def last_rung(*args):
        return _every_row(dtypes, *args), jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(first_rung, *args)[1])
    return _cond_on_rows(rungs, plan, first_rung, last_rung,
                         (x, weight, wg, wu, wd, plan))


def _backward_by_rung(rungs, dtypes, x, weight, wg, wu, wd, plan, kept, g):
    """The backward of ``_forward_by_rung`` on the same predicate: from
    ``kept`` in the first rung; in the last, ``_every_row`` again under
    ``jax.vjp`` (dearer than the layer without rungs by one forward: it
    is the rung that is not expected to run)."""
    def last_rung(x, weight, wg, wu, wd, plan, kept, g):
        return jax.vjp(lambda *a: _every_row(dtypes, *a, plan),
                       x, weight, wg, wu, wd)[1](g)
    return _cond_on_rows(
        rungs, plan, functools.partial(_first_rung_backward, rungs[0], dtypes),
        last_rung, (x, weight, wg, wu, wd, plan, kept, g))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ladder(rungs, dtypes, x, weight, wg, wu, wd, plan):
    """``_forward_by_rung`` as ONE differentiable function; ``kept`` is
    handed out beside ``out`` and has no gradient.  Left to ``jax.vjp``, a
    ``lax.cond`` makes the residuals of every branch outputs of the
    forward conditional and fills the untaken branch's with zeros: the
    worst case's rows would be written on every step.  Here the forward
    conditional keeps the first rung's rows alone."""
    return _forward_by_rung(rungs, dtypes, x, weight, wg, wu, wd, plan)


def _ladder_fwd(rungs, dtypes, x, weight, wg, wu, wd, plan):
    out, kept = _forward_by_rung(rungs, dtypes, x, weight, wg, wu, wd, plan)
    return (out, kept), (x, weight, wg, wu, wd, plan, kept)


def _ladder_bwd(rungs, dtypes, res, cotangents):
    return _backward_by_rung(rungs, dtypes, *res, cotangents[0]) + (None,)


_ladder.defvjp(_ladder_fwd, _ladder_bwd)


def _route_and_plan(x, router_w, select_bias, *, top_k, scale, first_expert,
                    n_held, scoring_func="sigmoid"):
    """``weight`` [T, k] and ``(plan, load)`` from the router's input
    ``x``: everything of the layer that does not depend on a rung."""
    with jax.named_scope("moe_route"):
        idx, weight, load = route(x, router_w, select_bias, top_k, scale,
                                  scoring_func)
    with jax.named_scope("moe_dispatch"):
        plan = _plan(idx, first_expert, n_held)
    return weight, (plan, load)


def _held_part(x, router_w, select_bias, w_gate, w_up, w_down, state, *,
               top_k, scale, first_expert, router_x=None,
               scoring_func="sigmoid", hidden_act="silu"):
    """``(out, load, kept)``; ``kept`` is None where the layer has one
    rung, whose backward needs nothing handed over.  ``router_x`` [T, H]:
    what the router reads where that is not the experts' input ``x``."""
    weight, (plan, load) = _route_and_plan(
        x if router_x is None else router_x, router_w, select_bias,
        top_k=top_k, scale=scale, first_expert=first_expert,
        n_held=w_gate.shape[0], scoring_func=scoring_func)
    operands, dtypes, rungs = _rows_operands(
        state, x, router_w, w_gate, w_up, w_down, top_k, scoring_func,
        hidden_act)
    if len(rungs) == 1:
        return _every_row(dtypes, x, weight, *operands, plan), load, None
    out, kept = _ladder(rungs, dtypes, x, weight, *operands, plan)
    return out, load, kept


def _rows_operands(state, x, router_w, w_gate, w_up, w_down, top_k,
                   scoring_func="sigmoid", hidden_act="silu"):
    """The expert weights in the compute dtype, the rungs' ``dtypes`` (with
    the gate's activation) and the layer's rungs (counted: one lowering
    traced)."""
    xc, wg, wu, wd, acc = amp_operands(state, x, w_gate, w_up, w_down)
    rungs = _rungs(x.shape[0], top_k, w_gate.shape[0], router_w.shape[-1])
    _m_experts_lowered.inc(path="ragged_dot",
                           rows="|".join(str(R) for R in rungs),
                           score=scoring_func, act=hidden_act)
    return (wg, wu, wd), (xc.dtype, acc, hidden_act), rungs


def routed_experts(x, router_w, select_bias, w_gate, w_up, w_down, *,
                   top_k, scale, first_expert, state=None, router_x=None,
                   scoring_func="sigmoid", hidden_act="silu"):
    """x [T, H]; router_w [H, E]; select_bias [E]; w_gate / w_up
    [held, H, I]; w_down [held, I, H] -> (out [T, H], load [E]): the part
    of ``sum_i w_i E_i(x)`` given by the experts ``first_expert ..
    first_expert + held - 1``, ``E_i`` a gated unit (``hidden_act``), the
    ``w_i`` from ``route`` on ``router_x`` (default: ``x``)."""
    return _held_part(x, router_w, select_bias, w_gate, w_up, w_down, state,
                      top_k=top_k, scale=scale, first_expert=first_expert,
                      router_x=router_x, scoring_func=scoring_func,
                      hidden_act=hidden_act)[:2]


def _op_operands(ctx):
    """``(operands, keywords)`` of ``_held_part`` from the op (or its grad
    op); ``router_x`` among the keywords where the op has a ``RouterX``."""
    x = ctx.i("X")
    attrs = dict(
        top_k=int(ctx.attr("top_k")),
        scale=float(ctx.attr("routed_scaling_factor", 1.0)),
        first_expert=int(ctx.attr("first_expert", 0)),
        scoring_func=ctx.attr("scoring_func", "sigmoid") or "sigmoid",
        hidden_act=ctx.attr("hidden_act", "silu") or "silu")
    if attrs["scoring_func"] not in _SCORES or \
            attrs["hidden_act"] not in _ACTIVATIONS:
        raise ValueError("routed_experts: scoring_func %r, hidden_act %r"
                         % (attrs["scoring_func"], attrs["hidden_act"]))
    router_x = ctx.i_opt("RouterX")
    if router_x is not None:
        if router_x.shape != x.shape:
            raise ValueError("routed_experts: RouterX %s beside X %s"
                             % (router_x.shape, x.shape))
        attrs["router_x"] = router_x.reshape(-1, x.shape[-1])
    return (x.reshape(-1, x.shape[-1]), ctx.i("RouterW"),
            ctx.i("SelectBias"), ctx.i("WGate"), ctx.i("WUp"),
            ctx.i("WDown")), attrs


@register_op("routed_experts", nondiff_inputs=("SelectBias",))
def _routed_experts(ctx, op):
    """X [..., H]; RouterW [H, E]; SelectBias [E] (no gradient: it is moved
    by ``moe_bias_update``); WGate / WUp [held, H, I]; WDown [held, I, H]
    -> Out [..., H] (the held experts' part of the routed sum) and
    ExpertLoad [E] float32 (tokens that chose each expert, over all E).

    ``RouterX`` [..., H] (optional): what the router reads, where that is
    not the experts' input (a router placed before the attention of its
    layer); its gradient comes through the weights alone and is written
    apart from X's.  Attributes ``scoring_func`` (``sigmoid``, the default,
    or ``softmax``: ``route``) and ``hidden_act`` (``silu`` or ``relu``:
    ``_gated``).

    ``Kept`` (four variables, optional): where the layer has two rungs,
    the first rung's rows ``(xs, gate, up, ys)`` as the forward
    conditional left them, for ``routed_experts_grad``: XLA merges a
    replay of plain HLO with the forward op's, never two conditionals.  A
    layer of one rung leaves them unwritten; its grad op replays."""
    operands, attrs = _op_operands(ctx)
    out, load, kept = _held_part(*operands, ctx.state, **attrs)
    if kept is not None and len(op.output("Kept")) == len(kept):
        ctx.set_all("Kept", kept)
    ctx.set("Out", out.reshape(ctx.i("X").shape))
    ctx.set("ExpertLoad", load)


@register_grad_lower("routed_experts")
def _routed_experts_grad(ctx, op):
    """Where the forward op left its first rung's rows in ``Kept``, the
    backward conditional reads them; the route, the sort and the casts are
    traced again here as plain HLO, which XLA merges with the forward
    op's.  Everywhere else (one rung; a program built without the slot)
    ``generic_grad_lower`` replays the forward."""
    from ..lowering import generic_grad_lower

    kept = tuple(ctx.env.get(name) for name in op.input("Kept"))
    g = ctx.i_opt("Out@GRAD")
    if len(kept) != 4 or g is None or any(v is None for v in kept):
        generic_grad_lower(ctx, op, residual_slots=("Kept",))
        return
    (x, router_w, select_bias, w_gate, w_up, w_down), attrs = \
        _op_operands(ctx)
    router_x = attrs.pop("router_x", None)
    hidden_act = attrs.pop("hidden_act")
    weight, route_vjp, (plan, _) = jax.vjp(
        lambda a, b: _route_and_plan(a, b, select_bias,
                                     n_held=w_gate.shape[0], **attrs),
        x if router_x is None else router_x, router_w, has_aux=True)
    (wg, wu, wd), dtypes, rungs = _rows_operands(
        ctx.state, x, router_w, w_gate, w_up, w_down, attrs["top_k"],
        attrs["scoring_func"], hidden_act)
    dx, dweight, dwg, dwu, dwd = _backward_by_rung(
        rungs, dtypes, x, weight, wg, wu, wd, plan, kept,
        g.reshape(x.shape).astype(x.dtype))
    dx_route, drouter = route_vjp(dweight)
    shape = ctx.i("X").shape
    if router_x is None:
        grads = {"X": (dx + dx_route).reshape(shape)}
    else:
        grads = {"X": dx.reshape(shape), "RouterX": dx_route.reshape(shape)}
    grads.update(RouterW=drouter, WGate=dwg.astype(w_gate.dtype),
                 WUp=dwu.astype(w_up.dtype), WDown=dwd.astype(w_down.dtype))
    for slot, grad in grads.items():
        name = (op.output(slot + "@GRAD") or [""])[0]
        if name:
            ctx.env[name] = grad


@register_op("moe_bias_update", stop_gradient=True)
def _moe_bias_update(ctx, op):
    """The auxiliary-loss-free balancing rule (DeepSeek-V3 report, section
    2.1.2): Bias += gamma * sign(mean(load) - load), an expert chosen less
    than the mean is lifted, one chosen more is lowered.  State moved in the
    optimizer role; no gradient is involved."""
    bias, load = ctx.i("Bias"), ctx.i("ExpertLoad")
    gamma = jnp.asarray(ctx.attr("gamma", 0.001), bias.dtype)
    ctx.set("BiasOut", bias + gamma * jnp.sign(load.mean() - load)
            .astype(bias.dtype))
