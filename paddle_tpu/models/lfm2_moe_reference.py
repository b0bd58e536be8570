"""Plain reference of the ``lfm2_moe`` decoder (``models/lfm2_moe.py`` is
the program under test): forward, loss and gradients in ``jax.numpy``,
float32, every matmul at ``jax.default_matmul_precision("highest")``; no
kernel, no sort, no cache, no batching tricks.  It imports nothing of
``paddle_tpu`` and is copied verbatim to ``benchmarks/configs/`` (a test
holds the two copies equal).

It follows the published description (``config.json`` of
``LiquidAI/LFM2-8B-A1B``, ``model_type: lfm2_moe``, and
``modeling_lfm2_moe.py`` of the transformers library).  Departures, each
also marked where it happens:

1. **The chip's share.**  ``cfg["num_experts_held"]`` /
   ``cfg["first_expert_held"]``: the router scores all ``num_experts``;
   only the experts held add to the output, and what the absent ones would
   add is LEFT OUT (the guide's cut: one chip of an expert-parallel
   deployment).  With all experts held it is the published layer.
   ``vocab_size`` is whatever ``embed_tokens`` holds.
2. **Block by block.**  ``loss_and_grads`` runs one block at a time, keeps
   each block's input and differentiates the blocks in reverse, so that the
   float32 model fits beside the program's own state on one chip; the
   numbers are those of differentiating the whole.  The tied embedding's
   gradient is the sum of the head's and the look-up's.
3. Attention runs head by head and ``QUERY_ROWS`` query rows at a time, and
   the expert layer expert by expert, each piece under ``jax.checkpoint``
   (memory only: at 8192 tokens one head's float32 scores are 268 MB).
4. **Assumed, not published** (the catalog's row lacks them; the
   configuration file lists each): the head tied to the embedding
   (``tie_embedding``), no auxiliary loss, full sequences packed from
   position 0 with no document mask (the convolution and attention cross
   document boundaries).
5. ``conv_bias`` false, as published: the convolution has no bias.
6. **The program's, not this file's.**  The router's weights are the chosen
   scores over their sum + 1e-6 here, as the family's code has it;
   ``fluid.layers.routed_experts`` adds 1e-20 (the ``deepseek_v3`` family's
   value, and its only one): 5e-7 relative on a sum of about 2, a thousandth
   of the smallest tolerance anything is held to.

``params``: a dict of float32 arrays under the program's parameter names
(``layers.<i>.conv.in_proj`` ...; matrices are ``[in, out]``, as Fluid's
``mul`` takes them, the transpose of the published ``[out, in]``; the
convolution's taps ``layers.<i>.conv.conv`` are ``[channels, L]``, the
published ``[channels, 1, L]`` without its middle axis), plus
``select_bias.<i>`` per expert layer.  ``cfg``: the published keys, with
``layer_types`` a list of ``"conv"`` / ``"full_attention"``.  ``dtype``:
the precision everything is computed in; anything but float32 exists for
one purpose, to show that the comparison's limits refuse it.
"""

import functools

import jax
import jax.numpy as jnp


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (w * y).astype(x.dtype)


def rotate_half(x):
    d = x.shape[-1] // 2
    return jnp.concatenate([-x[..., d:], x[..., :d]], axis=-1)


def rotary(x, theta):
    """x [B, S, heads, D], positions 0..S-1: ``x * cos + rotate_half(x) *
    sin`` with the D/2 frequencies repeated twice (lane i pairs with lane
    i + D/2, as the published weights are laid out)."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    freqs = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    return (x32 * jnp.cos(emb) + rotate_half(x32) * jnp.sin(emb)) \
        .astype(x.dtype)


QUERY_ROWS = 2048


def _attend(q, k, v, first_row, scale):
    """q [B, rows, D] (query rows ``first_row`` onward), k, v [B, S, D]:
    causal softmax attention of these rows over the whole sequence."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    seen = (first_row + jnp.arange(q.shape[1]))[:, None] >= \
        jnp.arange(k.shape[1])[None, :]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def _one_head(q, k, v, scale):
    """q, k, v [B, S, D]: causal softmax attention, ``QUERY_ROWS`` query
    rows at a time (departure 3)."""
    piece = jax.checkpoint(functools.partial(_attend, scale=scale),
                           static_argnums=3)
    return jnp.concatenate(
        [piece(q[:, i:i + QUERY_ROWS], k, v, i)
         for i in range(0, q.shape[1], QUERY_ROWS)], axis=1)


def attention(x, p, cfg, prefix):
    """Grouped-query attention, x [B, S, hidden]: an RMS norm over the
    lanes of every Q and K head, rotary embedding on the whole head, K and V
    REPEATED to the query heads (head ``h`` reads key/value head ``h //
    (n / n_kv)``)."""
    B, S, H = x.shape
    n, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = H // n
    w = lambda name: p[prefix + "." + name].astype(x.dtype)   # noqa: E731
    q = (x @ w("q_proj")).reshape(B, S, n, d)
    k = (x @ w("k_proj")).reshape(B, S, n_kv, d)
    v = (x @ w("v_proj")).reshape(B, S, n_kv, d)
    q = rotary(rms_norm(q, p[prefix + ".q_layernorm"], cfg["norm_eps"]),
               cfg["rope_theta"])
    k = rotary(rms_norm(k, p[prefix + ".k_layernorm"], cfg["norm_eps"]),
               cfg["rope_theta"])
    k, v = (jnp.repeat(t, n // n_kv, axis=2) for t in (k, v))
    # departure 3: one head at a time (``lax.map`` over the heads: one
    # traced body instead of n), recomputed in the backward
    ctx = jax.lax.map(
        lambda head: _one_head(*head, d ** -0.5),
        tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))    # [n, B, S, d]
    return jnp.moveaxis(ctx, 0, 2).reshape(B, S, n * d) @ w("out_proj")


def gated_conv(bcx, taps):
    """bcx [B, S, 3C] (B, C, x along the last axis), taps [C, L] ->
    ``C_t * sum_j taps[:, j] * (B * x)_{t - (L - 1) + j}``, zero before
    position 0: a plain sum over the taps of the left-padded product."""
    S, L = bcx.shape[1], taps.shape[1]
    gate_b, gate_c, x = jnp.split(bcx, 3, axis=-1)
    z = jnp.pad(gate_b * x, ((0, 0), (L - 1, 0), (0, 0)))
    conv = sum(taps[:, j].astype(bcx.dtype) * z[:, j:j + S]
               for j in range(L))
    return gate_c * conv


def short_conv(x, p, cfg, prefix):
    """The convolution mixer: ``W_out (C * conv(B * x'))``, ``(B, C, x') =
    split_3(W_in x)``; no bias (departure 5)."""
    w = lambda name: p[prefix + "." + name].astype(x.dtype)   # noqa: E731
    return gated_conv(x @ w("in_proj"), p[prefix + ".conv"]) @ w("out_proj")


def swiglu(x, p, prefix):
    w = lambda name: p[prefix + "." + name].astype(x.dtype)   # noqa: E731
    return (jax.nn.silu(x @ w("gate_proj")) * (x @ w("up_proj"))) \
        @ w("down_proj")


def router(x, router_w, select_bias, top_k, scale, dtype=jnp.float32):
    """``(mask [T, E] bool, weight [T, E])``: sigmoid scores; the ``top_k``
    largest of score + bias chosen (``use_expert_bias``; ties go to the
    lower index); the weights are the chosen scores (NOT score + bias) over
    their sum + 1e-6 (``norm_topk_prob``), times ``routed_scaling_factor``.
    The source computes this in float32; ``dtype`` lowers it for the
    refusal reading only."""
    scores = jax.nn.sigmoid(x.astype(dtype) @ router_w.astype(dtype))
    E = scores.shape[-1]
    choice = jnp.argsort(-(scores + select_bias.astype(dtype)), axis=-1,
                         stable=True)[:, :top_k]
    mask = (choice[..., None] == jnp.arange(E)).any(axis=1)
    chosen = jnp.where(mask, scores, 0).astype(jnp.float32)
    weight = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-6) * scale
    return mask, weight


def _one_expert(xt, weight, gate, up, down):
    """One expert's SwiGLU of every token under the token's weight for it,
    float32 [T, H]."""
    y = (jax.nn.silu(xt @ gate) * (xt @ up)) @ down
    return weight[:, None] * y.astype(jnp.float32)


def expert_ffn(x, p, cfg, prefix, select_bias, router_dtype=jnp.float32):
    """x [B, S, hidden] -> (routed part of the held experts, load [E]).  A
    loop over the experts held, each applied to every token under its
    weight (zero where it was not chosen); no shared expert."""
    B, S, H = x.shape
    xt = x.reshape(-1, H)
    e = prefix + ".experts"
    mask, weight = router(xt, p[e + ".router"], select_bias,
                          cfg["num_experts_per_tok"],
                          cfg["routed_scaling_factor"], router_dtype)
    first = cfg.get("first_expert_held", 0)
    held = cfg.get("num_experts_held", cfg["num_experts"])
    one = jax.checkpoint(_one_expert)          # departure 3
    out = jnp.zeros(xt.shape, jnp.float32)
    for j in range(held):          # departure 1: the absent experts add nothing
        out = out + one(xt, weight[:, first + j],
                        *(p[e + "." + n][j].astype(x.dtype)
                          for n in ("gate", "up", "down")))
    return out.astype(x.dtype).reshape(B, S, H), \
        mask.sum(axis=0).astype(jnp.float32)


def block(h, p, cfg, i, dtype=jnp.float32):
    """Pre-norm block ``i`` over its own parameters (names without the
    ``layers.<i>.`` prefix, and ``select_bias``): the mixer
    ``layer_types[i]`` names, then a dense SwiGLU where ``i <
    num_dense_layers`` and the experts after.  Returns (h, expert load or
    None)."""
    eps = cfg["norm_eps"]
    x = rms_norm(h, p["operator_norm"], eps)
    if cfg["layer_types"][i] == "conv":
        h = h + short_conv(x, p, cfg, "conv")
    else:
        h = h + attention(x, p, cfg, "self_attn")
    x = rms_norm(h, p["ffn_norm"], eps)
    if i < cfg["num_dense_layers"]:
        return h + swiglu(x, p, "feed_forward"), None
    y, load = expert_ffn(x, p, cfg, "feed_forward", p["select_bias"],
                         router_dtype=dtype)
    return h + y, load


def block_params(params, i):
    """Layer ``i``'s parameters under their local names."""
    pre = "layers.%d." % i
    out = {n[len(pre):]: v for n, v in params.items() if n.startswith(pre)}
    if "select_bias.%d" % i in params:
        out["select_bias"] = params["select_bias.%d" % i]
    return out


def embed(ids, p, dtype=jnp.float32):
    return p["embed_tokens"].astype(dtype)[ids]


def head_logits(h, p, cfg):
    """Final norm and the head, float32 [B, S, V] over the vocabulary held.
    Tied (departure 4): the logits are ``n(h) E^T`` with E the embedding; a
    ``lm_head`` among ``p`` is an untied head."""
    x = rms_norm(h, p["embedding_norm"], cfg["norm_eps"])
    head = p["lm_head"] if "lm_head" in p else p["embed_tokens"].T
    return (x @ head.astype(x.dtype)).astype(jnp.float32)


def head_loss(h, p, cfg, labels):
    """``(mean loss, per-token loss [B, S])``: next-token cross-entropy of
    every position (``labels`` are the ids already shifted by one)."""
    logp = jax.nn.log_softmax(head_logits(h, p, cfg), axis=-1)
    per_token = -jnp.take_along_axis(logp, labels[..., None],
                                     axis=-1)[..., 0]
    return jnp.mean(per_token), per_token


def head_params(params):
    return {n: params[n] for n in ("embedding_norm", "lm_head", "embed_tokens")
            if n in params}


@_highest
def forward_loss(params, ids, labels, cfg, dtype=jnp.float32):
    """Loss of the whole model in one piece, and the expert loads."""
    h = embed(ids, params, dtype)
    loads = []
    for i in range(cfg["num_hidden_layers"]):
        h, load = block(h, block_params(params, i), cfg, i, dtype)
        if load is not None:
            loads.append(load)
    return head_loss(h, head_params(params), cfg, labels)[0], loads


@_highest
def logits(params, ids, cfg, dtype=jnp.float32):
    """The head's logits [B, S, V] of the whole model in one piece."""
    h = embed(ids, params, dtype)
    for i in range(cfg["num_hidden_layers"]):
        h = block(h, block_params(params, i), cfg, i, dtype)[0]
    return head_logits(h, head_params(params), cfg)


@_highest
def loss_and_grads(params, ids, labels, cfg, dtype=jnp.float32,
                   fetch=lambda tensors: tensors,
                   take=lambda name, grad: grad):
    """``(loss, per-token loss, grads, loads)``, block by block (departure
    2).  ``fetch`` moves one block's parameters to the device (a caller
    whose ``params`` live on the host hands over ``jax.device_put``);
    ``take(name, grad)`` gives what is held of each gradient as it comes
    (default: all of it; a caller that cannot hold a second model's worth
    reduces each to what it compares)."""
    def kept(found):
        return {n: take(n, g) for n, g in found.items()
                if "select_bias" not in n}

    n_layers = cfg["num_hidden_layers"]

    @functools.partial(jax.jit, static_argnums=2)
    def forward(h, p, i):
        return block(h, p, cfg, i, dtype)

    @functools.partial(jax.jit, static_argnums=3)
    def backward(h, p, dh, i):
        _, vjp = jax.vjp(lambda h_, p_: block(h_, p_, cfg, i, dtype)[0],
                         h, p)
        return vjp(dh)

    p_embed = fetch({"embed_tokens": params["embed_tokens"]})
    h = jax.jit(functools.partial(embed, dtype=dtype))(ids, p_embed)
    inputs, loads = [], []
    for i in range(n_layers):
        inputs.append(h)
        h, load = forward(h, fetch(block_params(params, i)), i)
        if load is not None:
            loads.append(load)

    p_head = dict(fetch({n: v for n, v in head_params(params).items()
                         if n != "embed_tokens"}), **(
        {} if "lm_head" in params else p_embed))
    (loss, per_token), (dh, head_grads) = jax.jit(jax.value_and_grad(
        functools.partial(head_loss, cfg=cfg, labels=labels),
        argnums=(0, 1), has_aux=True))(h, p_head)
    d_tied = head_grads.pop("embed_tokens", None)
    grads = kept(head_grads)
    del p_head, head_grads
    for i in reversed(range(n_layers)):
        dh, dp = backward(inputs.pop(), fetch(block_params(params, i)), dh,
                          i)
        grads.update(kept({"layers.%d.%s" % (i, n): g
                           for n, g in dp.items()}))
        del dp
    _, vjp = jax.vjp(lambda p_: embed(ids, p_, dtype), p_embed)
    d_embed = vjp(dh)[0]["embed_tokens"]
    if d_tied is not None:
        d_embed = d_embed + d_tied
    grads.update(kept({"embed_tokens": d_embed}))
    return loss, per_token, grads, loads
