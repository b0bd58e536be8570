"""Ops of a decoder-only language model with latent attention and routed
experts (the ``deepseek_v3`` family, ``models/deepseek_v3.py``): RMS norm,
rotary embedding on a head's slice, the routed-expert layer and the rule
that moves its selection bias.  The reference predates all of them.

``routed_experts`` is an expert layer that is TOLD which experts it holds
(attributes ``first_expert`` and the leading dimension of its weights): it
routes over all ``E`` experts of the model — float32 sigmoid scores, the
``top_k`` largest of score + selection bias, weights from the scores alone,
normalised and scaled — and computes the part of ``sum_i w_i E_i(x)`` that
the experts held here give.  What the absent experts would add is left out:
in an expert-parallel deployment it arrives through the exchange this
single-chip lowering does not have, and nothing stands in for it.  There is
no capacity and no dropped token: the assignments are sorted by expert, the
rows of the held ones gathered, and three grouped matmuls (SwiGLU) run over
the rows actually present — ``jax.lax.ragged_dot``, which XLA:TPU compiles
to its own Mosaic grouped-matmul kernel that skips the tiles past the last
group, and whose transpose rules give both backward products.  (On the
chip a hand-written Pallas kernel over tile-aligned groups ran the forward
product in 0.25 ms against ``ragged_dot``'s 0.57 at the cell's 3072 live
rows when a whole expert matrix was one block, and in 1.8 ms with 128-wide
column blocks; it has no backward yet.  PERF.md section 6, PR 28.)  The
buffers are sized for the worst case (every assignment held here); the
matmul work follows the rows that came.
"""

import jax
import jax.numpy as jnp

from .. import telemetry
from ..lowering import amp_operands
from ..registry import register_op

_HIGHEST = jax.lax.Precision.HIGHEST

_m_experts_lowered = telemetry.counter(
    "moe_experts_lowered_total",
    "routed_experts lowerings traced, by the path of its grouped matmuls "
    "(a training step traces each op twice: the forward op and its replay "
    "inside the grad op)")


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


def rotary(x, theta):
    """Rotary position embedding over the last axis of ``x`` [B, S, heads,
    D], positions 0..S-1 (full sequences, packed from 0).  The published
    weights pair lane 2i with lane 2i+1, and the source
    (``modeling_deepseek.py: apply_rotary_pos_emb``) de-interleaves the
    lanes (evens first, then odds) before the usual rotate-half; the result
    stays in that de-interleaved order, for Q and K alike, so the scores are
    unchanged."""
    B, S, N, D = x.shape
    xf = x.astype(jnp.float32).reshape(B, S, N, D // 2, 2).swapaxes(-1, -2) \
        .reshape(B, S, N, D)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    half = jnp.concatenate([-xf[..., D // 2:], xf[..., :D // 2]], axis=-1)
    return (xf * jnp.cos(angles) + half * jnp.sin(angles)).astype(x.dtype)


@register_op("rotary_embedding")
def _rotary_embedding(ctx, op):
    """X [B, S, heads, D] -> Out, the same shape: rotary embedding with
    base ``theta`` on the whole last axis (the caller hands over the
    rotary slice of the head)."""
    ctx.set("Out", rotary(ctx.i("X"), float(ctx.attr("theta", 10000.0))))


# -- the routed-expert layer ---------------------------------------------------

def route(x, router_w, select_bias, top_k, scale):
    """``(idx [T, k] int32, weight [T, k] float32, load [E] float32)``:
    float32 sigmoid scores over all E experts; the ``top_k`` largest of
    score + bias are chosen (ties: the lower index), the bias chooses and
    does not weigh; weights are the chosen scores over their sum (+1e-20),
    times ``scale``; ``load`` counts the tokens that chose each expert."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=_HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(
        jax.lax.stop_gradient(scores) + select_bias.astype(jnp.float32),
        top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weight = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20) * scale
    E = router_w.shape[-1]
    load = (idx[..., None] == jnp.arange(E)).sum(axis=(0, 1))
    return idx, weight, load.astype(jnp.float32)


@jax.custom_vjp
def _dispatch(x, token_of, slot_of, held):
    """Rows of ``x`` [T, H] in sorted-assignment order, [T * k, H]:
    row r is token ``token_of[r]``.  Backward is a gather too (each token
    sums the rows of its own held assignments, found through ``slot_of``
    [T, k], the row of each assignment), never a scatter-add."""
    return x[token_of]


def _dispatch_fwd(x, token_of, slot_of, held):
    return x[token_of], (slot_of, held)


def _dispatch_bwd(res, g):
    slot_of, held = res
    rows = jnp.where(held[..., None], g[slot_of], 0)        # [T, k, H]
    return rows.astype(jnp.float32).sum(axis=1).astype(g.dtype), \
        None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _held_rows(y, slot_of, held):
    """[T, k, H] float32: the row of each held assignment, zero for the
    others (their slots lie past the last group, where nothing is
    computed)."""
    return jnp.where(held[..., None], y[slot_of], 0).astype(jnp.float32)


@jax.custom_vjp
def _combine(y, weight, order, token_of, slot_of, held):
    """out[t] = sum_k weight[t, k] * y[slot_of[t, k]] over the held
    assignments (float32 sum), [T, H] in ``y``'s dtype; the backward
    gathers ``d out`` by row, as the forward gathered ``x``."""
    return (_held_rows(y, slot_of, held) * weight[..., None]).sum(axis=1) \
        .astype(y.dtype)


def _combine_fwd(y, weight, order, token_of, slot_of, held):
    return _combine(y, weight, order, token_of, slot_of, held), \
        (y, weight, order, token_of, slot_of, held)


def _combine_bwd(res, g):
    y, weight, order, token_of, slot_of, held = res
    w_row = jnp.where(held, weight, 0).reshape(-1)[order]   # [T * k]
    dy = (g[token_of].astype(jnp.float32) * w_row[:, None]).astype(y.dtype)
    dw = (_held_rows(y, slot_of, held) *
          g[:, None, :].astype(jnp.float32)).sum(axis=-1)
    return dy, dw.astype(weight.dtype), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(x, router_w, select_bias, w_gate, w_up, w_down, *,
                   top_k, scale, first_expert, state=None):
    """x [T, H]; router_w [H, E]; select_bias [E]; w_gate / w_up
    [held, H, I]; w_down [held, I, H] -> (out [T, H], load [E]): the part
    of ``sum_i w_i E_i(x)`` given by the experts ``first_expert ..
    first_expert + held - 1``, ``E_i`` a SwiGLU."""
    T, H = x.shape
    n_held = w_gate.shape[0]
    with jax.named_scope("moe_route"):
        idx, weight, load = route(x, router_w, select_bias, top_k, scale)
    with jax.named_scope("moe_dispatch"):
        local = idx - first_expert
        held = (local >= 0) & (local < n_held)               # [T, k]
        # assignments to absent experts sort behind every held group
        key = jnp.where(held, local, n_held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        slot_of = jnp.argsort(order).astype(jnp.int32).reshape(T, top_k)
        group_sizes = (key[:, None] == jnp.arange(n_held)).sum(axis=0) \
            .astype(jnp.int32)
        token_of = order // top_k
        xs = _dispatch(x, token_of, slot_of, held)           # [T * k, H]
    with jax.named_scope("moe_experts"):
        _m_experts_lowered.inc(path="ragged_dot")
        xs, wg, wu, wd, acc = amp_operands(state, xs, w_gate, w_up, w_down)
        prec = _HIGHEST if xs.dtype == jnp.float32 else None

        def grouped(a, w):
            return jax.lax.ragged_dot(a, w, group_sizes, precision=prec,
                                      preferred_element_type=acc)
        hidden = jax.nn.silu(grouped(xs, wg)) * grouped(xs, wu)
        ys = grouped(hidden.astype(xs.dtype), wd).astype(x.dtype)
    with jax.named_scope("moe_combine"):
        out = _combine(ys, weight, order, token_of, slot_of, held)
    return out, load


@register_op("routed_experts", nondiff_inputs=("SelectBias",))
def _routed_experts(ctx, op):
    """X [..., H]; RouterW [H, E]; SelectBias [E] (no gradient: it is moved
    by ``moe_bias_update``); WGate / WUp [held, H, I]; WDown [held, I, H]
    -> Out [..., H] (the held experts' part of the routed sum) and
    ExpertLoad [E] float32 (tokens that chose each expert, over all E)."""
    x = ctx.i("X")
    out, load = routed_experts(
        x.reshape(-1, x.shape[-1]), ctx.i("RouterW"), ctx.i("SelectBias"),
        ctx.i("WGate"), ctx.i("WUp"), ctx.i("WDown"),
        top_k=int(ctx.attr("top_k")),
        scale=float(ctx.attr("routed_scaling_factor", 1.0)),
        first_expert=int(ctx.attr("first_expert", 0)), state=ctx.state)
    ctx.set("Out", out.reshape(x.shape))
    ctx.set("ExpertLoad", load)


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
