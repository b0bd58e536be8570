"""Plain reference of the ``deepseek_v3`` decoder (``models/deepseek_v3.py``
is the program under test): forward, loss and gradients in ``jax.numpy``,
float32, every matmul at ``jax.default_matmul_precision("highest")``; no
kernel, no sort, no cache, no batching tricks.  It imports nothing of
``paddle_tpu`` and is copied verbatim to ``benchmarks/configs/`` (a test
holds the two copies equal).

It follows the published description (``config.json`` and
``modeling_deepseek.py`` of ``moonshotai/Moonlight-16B-A3B``, DeepSeek-V3
technical report, arXiv:2412.19437).  Departures, each also marked where it
happens:

1. **The chip's share.**  ``cfg["n_routed_experts_held"]`` /
   ``cfg["first_expert_held"]``: the router scores all
   ``n_routed_experts``; only the experts held add to the output, and what
   the absent ones would add is LEFT OUT (the guide's cut: one chip of an
   expert-parallel deployment).  With all experts held it is the published
   layer.  ``vocab_size`` is whatever ``embed_tokens`` / ``lm_head`` hold.
2. **Block by block.**  ``loss_and_grads`` runs one block at a time, keeps
   each block's input and differentiates the blocks in reverse, so that the
   float32 model fits beside the program's own state on one chip; the
   numbers are those of differentiating the whole.
3. **No auxiliary loss, no multi-token prediction** (the published config
   sets ``num_nextn_predict_layers`` 0; ``seq_aux`` has no weight given).
4. Attention runs head by head under ``jax.checkpoint`` (memory only).

``params``: a dict of float32 arrays under the program's parameter names
(``layers.<i>.self_attn.q_proj`` ...; matrices are ``[in, out]``, as Fluid's
``mul`` takes them, the transpose of the published ``[out, in]``), plus
``select_bias.<i>`` per expert layer.  ``dtype``: the precision everything
is computed in; anything but float32 exists for one purpose, to show that
the comparison's limits refuse it.
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
    """x [B, S, heads, D], positions 0..S-1.  As the source's
    ``apply_rotary_pos_emb``: de-interleave the lanes (view as [D/2, 2],
    transpose), then x * cos + rotate_half(x) * sin with the D/2
    frequencies repeated twice."""
    B, S, N, D = x.shape
    x = x.reshape(B, S, N, D // 2, 2).transpose(0, 1, 2, 4, 3) \
        .reshape(B, S, N, D)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    freqs = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    return (x32 * jnp.cos(emb) + rotate_half(x32) * jnp.sin(emb)) \
        .astype(x.dtype)


def _one_head(q, k, v, scale):
    """q, k [B, S, Dqk], v [B, S, Dv]: causal softmax attention."""
    S = q.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def latent_attention(x, p, cfg, prefix):
    """MLA, ``q_lora_rank`` null.  x [B, S, hidden]."""
    B, S, _ = x.shape
    n, nope, rope, dv = cfg["num_attention_heads"], \
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    dt = x.dtype
    w = lambda name: p[prefix + "." + name].astype(dt)   # noqa: E731
    q = (x @ w("q_proj")).reshape(B, S, n, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kva = x @ w("kv_a_proj_with_mqa")
    c_kv, k_pe = kva[..., :cfg["kv_lora_rank"]], kva[..., cfg["kv_lora_rank"]:]
    kv = (rms_norm(c_kv, p[prefix + ".kv_a_layernorm"], cfg["rms_norm_eps"])
          @ w("kv_b_proj")).reshape(B, S, n, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = rotary(q_pe, cfg["rope_theta"])
    k_pe = rotary(k_pe.reshape(B, S, 1, rope), cfg["rope_theta"])
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, n, rope))],
                        axis=-1)
    scale = (nope + rope) ** -0.5          # no YaRN mscale: no rope_scaling
    # departure 4: one head at a time, recomputed in the backward
    head = jax.checkpoint(functools.partial(_one_head, scale=scale))
    ctx = jnp.stack([head(q[:, :, i], k[:, :, i], v[:, :, i])
                     for i in range(n)], axis=2)
    return ctx.reshape(B, S, n * dv) @ w("o_proj")


def swiglu(x, p, prefix):
    w = lambda name: p[prefix + "." + name].astype(x.dtype)   # noqa: E731
    return (jax.nn.silu(x @ w("gate_proj")) * (x @ w("up_proj"))) \
        @ w("down_proj")


def router(x, router_w, select_bias, top_k, scale, dtype=jnp.float32):
    """``(mask [T, E] bool, weight [T, E])``: sigmoid scores; the ``top_k``
    largest of score + bias chosen (``noaux_tc`` with one group: group
    limiting is the identity; ties go to the lower index); the weights are
    the chosen scores (NOT score + bias) over their sum + 1e-20, times
    ``routed_scaling_factor``.  The source computes this in float32;
    ``dtype`` lowers it for the refusal reading only."""
    scores = jax.nn.sigmoid(x.astype(dtype) @ router_w.astype(dtype))
    E = scores.shape[-1]
    choice = jnp.argsort(-(scores + select_bias.astype(dtype)), axis=-1,
                         stable=True)[:, :top_k]
    mask = (choice[..., None] == jnp.arange(E)).any(axis=1)
    chosen = jnp.where(mask, scores, 0).astype(jnp.float32)
    weight = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20) * scale
    return mask, weight


def expert_ffn(x, p, cfg, prefix, select_bias, router_dtype=jnp.float32):
    """x [B, S, hidden] -> (routed part of the held experts + shared
    experts, load [E]).  A loop over the experts held, each applied to
    every token under its weight (zero where it was not chosen)."""
    B, S, H = x.shape
    xt = x.reshape(-1, H)
    e = prefix + ".experts"
    mask, weight = router(xt, p[e + ".router"], select_bias,
                          cfg["num_experts_per_tok"],
                          cfg["routed_scaling_factor"], router_dtype)
    first = cfg.get("first_expert_held", 0)
    held = cfg.get("n_routed_experts_held", cfg["n_routed_experts"])
    out = jnp.zeros(xt.shape, jnp.float32)
    for j in range(held):          # departure 1: the absent experts add nothing
        gate, up, down = (p[e + "." + n][j].astype(x.dtype)
                          for n in ("gate", "up", "down"))
        y = (jax.nn.silu(xt @ gate) * (xt @ up)) @ down
        out = out + weight[:, first + j, None] * y.astype(jnp.float32)
    out = out.astype(x.dtype) + swiglu(xt, p, prefix + ".shared_experts")
    return out.reshape(B, S, H), mask.sum(axis=0).astype(jnp.float32)


def block(h, p, cfg, dense, dtype=jnp.float32):
    """One pre-norm block over its own parameters (names without the
    ``layers.<i>.`` prefix, and ``select_bias``); ``dense``: a SwiGLU of
    ``intermediate_size`` instead of the experts.  Returns (h, expert load
    or None)."""
    eps = cfg["rms_norm_eps"]
    h = h + latent_attention(
        rms_norm(h, p["input_layernorm"], eps), p, cfg, "self_attn")
    x = rms_norm(h, p["post_attention_layernorm"], eps)
    if dense:
        return h + swiglu(x, p, "mlp"), None
    y, load = expert_ffn(x, p, cfg, "mlp", p["select_bias"],
                         router_dtype=dtype)
    return h + y, load


def block_params(params, i):
    """Layer ``i``'s parameters under their local names."""
    pre = "layers.%d." % i
    out = {n[len(pre):]: v for n, v in params.items() if n.startswith(pre)}
    if "select_bias.%d" % i in params:
        out["select_bias"] = params["select_bias.%d" % i]
    return out


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def embed(ids, p, dtype=jnp.float32):
    return p["embed_tokens"].astype(dtype)[ids]


def head_loss(h, p, cfg, labels):
    """Final norm, untied head, mean next-token cross-entropy over the
    vocabulary held (``labels`` are the ids already shifted by one)."""
    x = rms_norm(h, p["norm"], cfg["rms_norm_eps"])
    logits = (x @ p["lm_head"].astype(x.dtype)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


@_highest
def forward_loss(params, ids, labels, cfg, dtype=jnp.float32):
    """Loss of the whole model in one piece, and the expert loads."""
    h = embed(ids, params, dtype)
    loads = []
    for i in range(cfg["num_hidden_layers"]):
        h, load = block(h, block_params(params, i), cfg, is_dense(cfg, i),
                        dtype)
        if load is not None:
            loads.append(load)
    return head_loss(h, params, cfg, labels), loads


@_highest
def loss_and_grads(params, ids, labels, cfg, dtype=jnp.float32,
                   fetch=lambda tensors: tensors,
                   take=lambda name, grad: grad):
    """``(loss, grads, loads)``, block by block (departure 2).  ``fetch``
    moves one block's parameters to the device (a caller whose ``params``
    live on the host hands over ``jax.device_put``); ``take(name, grad)``
    gives what is held of each gradient as it comes (default: all of it; a
    caller that cannot hold a second model's worth reduces each to what it
    compares)."""
    def kept(found):
        return {n: take(n, g) for n, g in found.items()
                if "select_bias" not in n}

    n_layers = cfg["num_hidden_layers"]

    @functools.partial(jax.jit, static_argnums=2)
    def forward(h, p, dense):
        return block(h, p, cfg, dense, dtype)

    @functools.partial(jax.jit, static_argnums=3)
    def backward(h, p, dh, dense):
        _, vjp = jax.vjp(lambda h_, p_: block(h_, p_, cfg, dense, dtype)[0],
                         h, p)
        return vjp(dh)

    p_embed = fetch({"embed_tokens": params["embed_tokens"]})
    h = jax.jit(functools.partial(embed, dtype=dtype))(ids, p_embed)
    inputs, loads = [], []
    for i in range(n_layers):
        inputs.append(h)
        h, load = forward(h, fetch(block_params(params, i)),
                          is_dense(cfg, i))
        if load is not None:
            loads.append(load)

    p_head = fetch({n: params[n] for n in ("norm", "lm_head")})
    loss, (dh, grads) = jax.jit(jax.value_and_grad(
        functools.partial(head_loss, cfg=cfg, labels=labels),
        argnums=(0, 1)))(h, p_head)
    grads = kept(grads)
    del p_head
    for i in reversed(range(n_layers)):
        dh, dp = backward(inputs.pop(), fetch(block_params(params, i)), dh,
                          is_dense(cfg, i))
        grads.update(kept({"layers.%d.%s" % (i, n): g
                           for n, g in dp.items()}))
        del dp
    _, vjp = jax.vjp(lambda p_: embed(ids, p_, dtype), p_embed)
    grads.update(kept(vjp(dh)[0]))
    return loss, grads, loads
