"""Plain reference of the SmallThinker decoder (``models/smallthinker.py``
is the program under test): forward, loss and gradients in ``jax.numpy``,
float32, every matmul at ``jax.default_matmul_precision("highest")``; no
kernel, no sort, no cache, no batching tricks.  It imports nothing of
``paddle_tpu`` and is copied verbatim to ``benchmarks/configs/`` (a test
holds the two copies equal).

It follows the published description (``config.json`` of
``PowerInfer/SmallThinker-21BA3B-Instruct``; Song et al. 2025,
arXiv:2507.20984).  Departures, each also marked where it happens:

1. **The chip's share.**  ``cfg["moe_num_primary_experts_held"]`` /
   ``cfg["first_expert_held"]``: the router scores all
   ``moe_num_primary_experts``; only the experts held add to the output,
   and what the absent ones would add is LEFT OUT (the guide's cut: one
   chip of an expert-parallel deployment).  With all experts held it is the
   published layer.  ``vocab_size`` is whatever ``embed_tokens`` holds.
2. **Block by block.**  ``loss_and_grads`` runs one block at a time, keeps
   each block's input and differentiates the blocks in reverse, so that the
   float32 model fits beside the program's own state on one chip; the
   numbers are those of differentiating the whole.
3. Attention runs head by head and ``QUERY_ROWS`` query rows at a time, the
   expert layer expert by expert and the head ``HEAD_ROWS`` positions at a
   time, each piece under ``jax.checkpoint`` (memory only: at 16384 tokens
   one head's float32 scores are 1 GB, the logits 1.2 GB).
4. **Assumed, not published** (the catalog's row lacks them; the
   configuration file lists each): the router reads the normed input of
   the layer's ATTENTION (``moe_enable_early_router``), the experts gate
   with ReLU (``hidden_act``), no attention bias and no Q/K norm, no
   auxiliary loss, full sequences packed from position 0 with no document
   mask.
5. **Written the second way.**  The router's weights are the softmax over
   ALL experts, the chosen ones kept and renormalised; the program takes
   the softmax over the chosen logits.  The two are the same numbers (a
   test holds them equal).

``params``: a dict of float32 arrays under the program's parameter names
(``layers.<i>.self_attn.q_proj`` ...; matrices are ``[in, out]``, as
Fluid's ``mul`` takes them, the transpose of the published ``[out, in]``).
``cfg``: the published keys, with ``rope_layout`` and
``sliding_window_layout`` lists of 0 / 1.  ``dtype``: the precision
everything is computed in; anything but float32 exists for one purpose, to
show that the comparison's limits refuse it.  ``router_dtype`` (the router
alone in another precision) and ``window`` (False computes every layer with
the plain causal mask) exist for the same purpose.
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


def _attend(q, k, v, first_row, scale, window):
    """q [B, rows, D] (query rows ``first_row`` onward), k, v [B, S, D]:
    softmax attention of these rows over the keys a boolean mask allows:
    ``j <= i``, and under a ``window`` W (0: none) ``j > i - W``."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    i = (first_row + jnp.arange(q.shape[1]))[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window:
        seen = seen & (j > i - window)
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def _one_head(q, k, v, scale, window):
    """q, k, v [B, S, D]: masked softmax attention, ``QUERY_ROWS`` query
    rows at a time (departure 3)."""
    piece = jax.checkpoint(
        functools.partial(_attend, scale=scale, window=window),
        static_argnums=3)
    return jnp.concatenate(
        [piece(q[:, i:i + QUERY_ROWS], k, v, i)
         for i in range(0, q.shape[1], QUERY_ROWS)], axis=1)


def attention(x, p, cfg, prefix, rope, window):
    """Grouped-query attention, x [B, S, hidden]: rotary embedding on the
    whole head of Q and K where ``rope`` and no positional signal where
    not, K and V REPEATED to the query heads (head ``h`` reads key/value
    head ``h // (n / n_kv)``), the band of ``window`` keys (0: the whole
    causal prefix) as a boolean mask; no bias, no Q/K norm."""
    B, S, _ = x.shape
    n, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    w = lambda name: p[prefix + "." + name].astype(x.dtype)   # noqa: E731
    q = (x @ w("q_proj")).reshape(B, S, n, d)
    k = (x @ w("k_proj")).reshape(B, S, n_kv, d)
    v = (x @ w("v_proj")).reshape(B, S, n_kv, d)
    if rope:
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    k, v = (jnp.repeat(t, n // n_kv, axis=2) for t in (k, v))
    # departure 3: one head at a time (``lax.map`` over the heads: one
    # traced body instead of n), recomputed in the backward
    ctx = jax.lax.map(
        lambda head: _one_head(*head, d ** -0.5, window),
        tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))    # [n, B, S, d]
    return jnp.moveaxis(ctx, 0, 2).reshape(B, S, n * d) @ w("o_proj")


def router(r, router_w, top_k, dtype=jnp.float32):
    """``(mask [T, E] bool, weight [T, E])`` from the router's input ``r``:
    float32 logits over all E experts; the ``top_k`` largest chosen (ties
    go to the lower index); the weights are the softmax over ALL experts,
    the chosen kept and renormalised to sum to one (departure 5).  The
    source computes this in float32; ``dtype`` lowers it for the refusal
    reading only."""
    logits = r.astype(dtype) @ router_w.astype(dtype)
    E = logits.shape[-1]
    choice = jnp.argsort(-logits, axis=-1, stable=True)[:, :top_k]
    mask = (choice[..., None] == jnp.arange(E)).any(axis=1)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    chosen = jnp.where(mask, probs, 0)
    return mask, chosen / chosen.sum(axis=-1, keepdims=True)


def _one_expert(xt, weight, gate, up, down):
    """One expert's ReGLU of every token under the token's weight for it,
    float32 [T, H]."""
    y = (jax.nn.relu(xt @ gate) * (xt @ up)) @ down
    return weight[:, None] * y.astype(jnp.float32)


def expert_ffn(x, r, p, cfg, prefix, router_dtype=jnp.float32):
    """x, r [B, S, hidden] -> (routed part of the held experts, load [E]):
    the router reads ``r``, the experts ``x``.  A loop over the experts
    held, each applied to every token under its weight (zero where it was
    not chosen); no shared expert, no selection bias."""
    B, S, H = x.shape
    xt = x.reshape(-1, H)
    e = prefix + ".experts"
    mask, weight = router(r.reshape(-1, H), p[e + ".router"],
                          cfg["moe_num_active_primary_experts"],
                          router_dtype)
    first = cfg.get("first_expert_held", 0)
    held = cfg.get("moe_num_primary_experts_held",
                   cfg["moe_num_primary_experts"])
    one = jax.checkpoint(_one_expert)          # departure 3
    out = jnp.zeros(xt.shape, jnp.float32)
    for j in range(held):          # departure 1: the absent experts add nothing
        out = out + one(xt, weight[:, first + j],
                        *(p[e + "." + n][j].astype(x.dtype)
                          for n in ("gate", "up", "down")))
    return out.astype(x.dtype).reshape(B, S, H), \
        mask.sum(axis=0).astype(jnp.float32)


def block(h, p, cfg, i, dtype=jnp.float32, window=True, router_dtype=None):
    """Pre-norm block ``i`` over its own parameters (names without the
    ``layers.<i>.`` prefix): ``u = n_in(h)``; ``h' = h + attn_i(u)``; ``x =
    n_post(h')``; ``h'' = h' + moe(r = u, x)`` (departure 4: the router
    reads ``u``).  ``router_dtype``: the router's own precision where it is
    not ``dtype``.  Returns (h, expert load)."""
    eps = cfg["rms_norm_eps"]
    u = rms_norm(h, p["input_layernorm"], eps)
    band = cfg["sliding_window_size"] \
        if window and cfg["sliding_window_layout"][i] else 0
    h = h + attention(u, p, cfg, "self_attn", cfg["rope_layout"][i], band)
    x = rms_norm(h, p["post_attention_layernorm"], eps)
    r = u if cfg.get("moe_enable_early_router", True) else x
    y, load = expert_ffn(x, r, p, cfg, "block_sparse_moe",
                         router_dtype=router_dtype or dtype)
    return h + y, load


def block_params(params, i):
    """Layer ``i``'s parameters under their local names."""
    pre = "layers.%d." % i
    return {n[len(pre):]: v for n, v in params.items() if n.startswith(pre)}


def embed(ids, p, dtype=jnp.float32):
    return p["embed_tokens"].astype(dtype)[ids]


def head_logits(h, p, cfg):
    """Final norm and the untied head, float32 [B, S, V] over the
    vocabulary held."""
    x = rms_norm(h, p["norm"], cfg["rms_norm_eps"])
    return (x @ p["lm_head"].astype(x.dtype)).astype(jnp.float32)


HEAD_ROWS = 4096


def _positions_loss(h, labels, p, cfg):
    """Next-token cross-entropy of the positions ``h`` [B, rows, hidden]
    holds, [B, rows] float32."""
    logp = jax.nn.log_softmax(head_logits(h, p, cfg), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def head_loss(h, p, cfg, labels):
    """``(mean loss, per-token loss [B, S])``: next-token cross-entropy of
    every position (``labels`` are the ids already shifted by one),
    ``HEAD_ROWS`` positions at a time, each piece checkpointed (departure
    3: at 16384 tokens the float32 logits over 18992 rows are 1.2 GB, and
    their log-softmax and gradient as much again)."""
    piece = jax.checkpoint(functools.partial(_positions_loss, cfg=cfg))
    per_token = jnp.concatenate(
        [piece(h[:, i:i + HEAD_ROWS], labels[:, i:i + HEAD_ROWS], p)
         for i in range(0, h.shape[1], HEAD_ROWS)], axis=1)
    return jnp.mean(per_token), per_token


def head_params(params):
    return {n: params[n] for n in ("norm", "lm_head")}


@_highest
def forward_loss(params, ids, labels, cfg, dtype=jnp.float32, window=True,
                 router_dtype=None):
    """``(loss, per-token loss, loads)`` of the whole model in one piece."""
    h = embed(ids, params, dtype)
    loads = []
    for i in range(cfg["num_hidden_layers"]):
        h, load = block(h, block_params(params, i), cfg, i, dtype, window,
                        router_dtype)
        loads.append(load)
    loss, per_token = head_loss(h, head_params(params), cfg, labels)
    return loss, per_token, loads


@_highest
def loss_and_grads(params, ids, labels, cfg, dtype=jnp.float32,
                   fetch=lambda tensors: tensors,
                   take=lambda name, grad: grad, window=True,
                   router_dtype=None):
    """``(loss, per-token loss, grads, loads)``, block by block (departure
    2).  ``fetch`` moves one block's parameters to the device (a caller
    whose ``params`` live on the host hands over ``jax.device_put``);
    ``take(name, grad)`` gives what is held of each gradient as it comes
    (default: all of it; a caller that cannot hold a second model's worth
    reduces each to what it compares)."""
    def kept(found):
        return {n: take(n, g) for n, g in found.items()}

    n_layers = cfg["num_hidden_layers"]

    @functools.partial(jax.jit, static_argnums=2)
    def forward(h, p, i):
        return block(h, p, cfg, i, dtype, window, router_dtype)

    @functools.partial(jax.jit, static_argnums=3)
    def backward(h, p, dh, i):
        _, vjp = jax.vjp(
            lambda h_, p_: block(h_, p_, cfg, i, dtype, window,
                                 router_dtype)[0], h, p)
        return vjp(dh)

    p_embed = fetch({"embed_tokens": params["embed_tokens"]})
    h = jax.jit(functools.partial(embed, dtype=dtype))(ids, p_embed)
    inputs, loads = [], []
    for i in range(n_layers):
        inputs.append(h)
        h, load = forward(h, fetch(block_params(params, i)), i)
        loads.append(load)

    p_head = fetch(head_params(params))
    (loss, per_token), (dh, head_grads) = jax.jit(jax.value_and_grad(
        functools.partial(head_loss, cfg=cfg, labels=labels),
        argnums=(0, 1), has_aux=True))(h, p_head)
    grads = kept(head_grads)
    del p_head, head_grads
    for i in reversed(range(n_layers)):
        dh, dp = backward(inputs.pop(), fetch(block_params(params, i)), dh,
                          i)
        grads.update(kept({"layers.%d.%s" % (i, n): g
                           for n, g in dp.items()}))
        del dp
    _, vjp = jax.vjp(lambda p_: embed(ids, p_, dtype), p_embed)
    grads.update(kept({"embed_tokens": vjp(dh)[0]["embed_tokens"]}))
    return loss, per_token, grads, loads
