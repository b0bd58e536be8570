"""Plain reference of the looped ``ouro`` decoder (``models/ouro.py`` is the
program under test): forward, loss and gradients in ``jax.numpy``, float32,
every matmul at ``jax.default_matmul_precision("highest")``; the passes are
a Python loop over the layer list, attention is softmax(QK^T) under a mask:
no kernel, no scan, no rematerialisation of a pass.  It imports nothing of
``paddle_tpu`` and is copied verbatim to ``benchmarks/configs/`` (a test
holds the two copies equal).

It follows the published description (``config.json`` of
``ByteDance/Ouro-2.6B``; Zhu et al. 2025, arXiv:2510.25741, and the
published ``modeling_ouro.py`` as remembered).  What the config cannot
settle, each also marked where it happens:

1. **The state a pass hands on** is the final norm's output ``z_t`` (the
   published forward overwrites the hidden state with its norm before the
   next pass).
2. **The loss** is the paper's stage-I objective: per token ``sum_t p_t
   CE_t - beta H(p)`` with the exit distribution ``p_t = lambda_t
   prod_{j<t}(1 - lambda_j)``, ``p_T = prod_{j<T}(1 - lambda_j)``,
   ``lambda_t = sigmoid(w_g . z_t + b_g)``; ``beta`` is
   ``cfg["entropy_beta"]``.
3. **Block by block.**  ``loss_and_grads`` runs one block at a time, keeps
   each block application's input and differentiates the applications in
   reverse, so that the float32 model fits beside the program's own state
   on one chip; a tied parameter's gradient is the SUM over the passes of
   what each application gives, and the numbers are those of
   differentiating the whole.
4. Attention runs head by head under ``jax.checkpoint`` (memory only).

``params``: a dict of float32 arrays under the program's parameter names
(``layers.<i>.self_attn.q_proj`` ...; matrices are ``[in, out]``, as Fluid's
``mul`` takes them, the transpose of the published ``[out, in]``).
``dtype``: the precision everything but the gate, the exit distribution and
the loss is computed in; anything but float32 exists for one purpose, to
show that the comparison's limits refuse it.  ``untied=True`` in
``loss_and_grads`` returns every pass's gradient apart (``<name>@<t>``): the
tie test's reading that the program's gradient is their sum.
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
    """x [B, S, heads, D], positions 0..S-1: x * cos + rotate_half(x) * sin
    with the D/2 frequencies repeated twice (lane i pairs with lane i +
    D/2, the layout of the published weights)."""
    S, D = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    freqs = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    return (x32 * jnp.cos(emb) + rotate_half(x32) * jnp.sin(emb)) \
        .astype(x.dtype)


def _one_head(q, k, v, scale):
    """q, k, v [B, S, D]: causal softmax attention."""
    S = q.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def attention(x, p, cfg, prefix):
    B, S, _ = x.shape
    n, d = cfg["num_attention_heads"], cfg["head_dim"]
    w = lambda name: p[prefix + "." + name].astype(x.dtype)   # noqa: E731
    q = rotary((x @ w("q_proj")).reshape(B, S, n, d), cfg["rope_theta"])
    k = rotary((x @ w("k_proj")).reshape(B, S, n, d), cfg["rope_theta"])
    v = (x @ w("v_proj")).reshape(B, S, n, d)
    # departure 4: one head at a time, recomputed in the backward
    head = jax.checkpoint(functools.partial(_one_head, scale=d ** -0.5))
    ctx = jnp.stack([head(q[:, :, i], k[:, :, i], v[:, :, i])
                     for i in range(n)], axis=2)
    return ctx.reshape(B, S, n * d) @ w("o_proj")


def swiglu(x, p, prefix):
    w = lambda name: p[prefix + "." + name].astype(x.dtype)   # noqa: E731
    return (jax.nn.silu(x @ w("gate_proj")) * (x @ w("up_proj"))) \
        @ w("down_proj")


def block(h, p, cfg):
    """One sandwich-norm layer over its own parameters (names without the
    ``layers.<i>.`` prefix)."""
    eps = cfg["rms_norm_eps"]
    a = attention(rms_norm(h, p["input_layernorm"], eps), p, cfg,
                  "self_attn")
    h = h + rms_norm(a, p["input_layernorm_2"], eps)
    m = swiglu(rms_norm(h, p["post_attention_layernorm"], eps), p, "mlp")
    return h + rms_norm(m, p["post_attention_layernorm_2"], eps)


def block_params(params, i):
    """Layer ``i``'s parameters under their local names."""
    pre = "layers.%d." % i
    return {n[len(pre):]: v for n, v in params.items() if n.startswith(pre)}


HEAD = ("norm", "lm_head", "early_exit_gate.w", "early_exit_gate.b")


def embed(ids, p, dtype=jnp.float32):
    return p["embed_tokens"].astype(dtype)[ids]


def exit_head(h, p, cfg, labels):
    """The end of a pass: ``(z, ce, gate)``, the normed state (what the next
    pass starts from: departure 1), the next-token cross-entropy of this
    pass's logits and the exit gate's logit, float32 [B, S] both."""
    z = rms_norm(h, p["norm"], cfg["rms_norm_eps"])
    logits = (z @ p["lm_head"].astype(z.dtype)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    gate = z.astype(jnp.float32) @ p["early_exit_gate.w"] \
        + p["early_exit_gate.b"][0]
    return z, ce, gate


def exit_distribution(gate):
    """gate [T, B, S] -> ``log p`` [T, B, S] (departure 2), in log space:
    ``log p_t = log sigmoid(g_t) + sum_{j<t} log(1 - sigmoid(g_j))`` for
    ``t < T``, and the last pass takes what is left."""
    log_stay = jax.nn.log_sigmoid(-gate)
    survived = jnp.cumsum(log_stay, axis=0) - log_stay
    return survived.at[:-1].add(jax.nn.log_sigmoid(gate[:-1]))


def exit_loss(ce, gate, beta):
    """Mean over tokens of ``sum_t p_t ce_t - beta H(p)``."""
    log_p = exit_distribution(gate)
    p = jnp.exp(log_p)
    return jnp.mean(jnp.sum(p * ce + beta * p * log_p, axis=0))


@_highest
def forward_loss(params, ids, labels, cfg, dtype=jnp.float32):
    """``(loss, ce, gate, p)`` ([T, B, S] each: the passes' cross-entropies,
    the exit gate's logits, the exit distribution) of the whole model in
    one piece: the passes as a Python loop over the layer list."""
    h = embed(ids, params, dtype)
    ces, gates = [], []
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            h = block(h, block_params(params, i), cfg)
        h, ce, gate = exit_head(h, params, cfg, labels)
        ces.append(ce)
        gates.append(gate)
    ce, gate = jnp.stack(ces), jnp.stack(gates)
    return exit_loss(ce, gate, cfg["entropy_beta"]), ce, gate, \
        jnp.exp(exit_distribution(gate))


def _head_spans(S, head_rows):
    """The exit head runs ``head_rows`` positions at a time (its logits are
    the largest tensor; every position is on its own there, so the numbers
    are the same): the ``(from, to)`` spans of a sequence of ``S``."""
    rows = head_rows or S
    return [(a, min(a + rows, S)) for a in range(0, S, rows)]


@_highest
def forward_by_blocks(params, ids, labels, cfg, dtype=jnp.float32,
                      fetch=lambda tensors: tensors, head_rows=None,
                      keep=False, control=None):
    """``(ce, gate)`` [T, B, S] each, one block application at a time: what
    ``forward_loss`` gives, in pieces that fit beside a program's own
    state (``head_rows``: ``_head_spans``).  ``keep``: also every block
    application's input and every pass's last hidden state, for
    ``loss_and_grads``.  ``control``: a second precision whose stream runs
    beside the first through the same fetched weights (ONE pass over the
    parameters); its ``(ce, gate)`` come last, as one tuple."""
    spans = _head_spans(ids.shape[1], head_rows)
    forward = jax.jit(lambda h, p: block(h, p, cfg))
    head_of = jax.jit(lambda h, p, y: exit_head(h, p, cfg, y))

    def head(h, p):
        parts = [head_of(h[:, a:b], p, labels[:, a:b]) for a, b in spans]
        return tuple(jnp.concatenate(part, axis=1) for part in zip(*parts))

    dtypes = [dtype] + ([] if control is None else [control])
    table = fetch({"embed_tokens": params["embed_tokens"]})
    hs = [jax.jit(functools.partial(embed, dtype=d))(ids, table)
          for d in dtypes]
    del table
    inputs, ends = [], []
    ces, gates = [[] for _ in dtypes], [[] for _ in dtypes]
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            if keep:
                inputs.append(hs[0])
            p = fetch(block_params(params, i))
            hs = [forward(h, p) for h in hs]
        if keep:
            ends.append(hs[0])
        p = fetch({n: params[n] for n in HEAD})
        for k, (z, ce, gate) in enumerate([head(h, p) for h in hs]):
            hs[k] = z
            ces[k].append(ce)
            gates[k].append(gate)
    out = jnp.stack(ces[0]), jnp.stack(gates[0])
    if keep:
        out += (inputs, ends)
    if control is not None:
        out += ((jnp.stack(ces[1]), jnp.stack(gates[1])),)
    return out


@_highest
def loss_and_grads(params, ids, labels, cfg, dtype=jnp.float32,
                   fetch=lambda tensors: tensors,
                   take=lambda name, grad: grad, untied=False,
                   head_rows=None, control=None):
    """``(loss, grads, ce, gate, p)``, one block application at a time
    (departure 3).  ``fetch`` moves one block's parameters to the device (a
    caller whose ``params`` live on the host hands over
    ``jax.device_put``); ``take(name, grad)`` gives what is held of each
    leaf's SUMMED gradient when its last contribution has come (default:
    all of it).  ``untied``: every pass's contribution apart, as
    ``<name>@<t>``.  ``head_rows``: as in ``_head_spans``.  ``control``: as in
    ``forward_by_blocks`` (forward only); its ``(ce, gate)`` come last."""
    T, n_layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    spans = _head_spans(ids.shape[1], head_rows)

    @jax.jit
    def backward(h, p, dh):
        return jax.vjp(lambda h_, p_: block(h_, p_, cfg), h, p)[1](dh)

    @jax.jit
    def head_backward_of(h, p, y, dz, dce, dgate):
        return jax.vjp(lambda h_, p_: exit_head(h_, p_, cfg, y),
                       h, p)[1]((dz, dce, dgate))

    def head_backward(h, p, dz, dce, dgate):
        dhs, dp = [], None
        for a, b in spans:
            dh, d = head_backward_of(h[:, a:b], p, labels[:, a:b],
                                     dz[:, a:b], dce[:, a:b], dgate[:, a:b])
            dhs.append(dh)
            dp = d if dp is None else jax.tree.map(jnp.add, dp, d)
        return jnp.concatenate(dhs, axis=1), dp

    sums, grads = {}, {}

    def add(found, t, last):
        """One application's gradients: apart under ``untied``, else into
        the leaf's sum, which ``take`` gets with its last contribution
        (the passes are differentiated last to first)."""
        for n, g in found.items():
            if untied:
                grads["%s@%d" % (n, t)] = take("%s@%d" % (n, t), g)
                continue
            sums[n] = sums[n] + g if n in sums else g
            if last:
                grads[n] = take(n, sums.pop(n))

    ce, gate, inputs, ends, *controlled = forward_by_blocks(
        params, ids, labels, cfg, dtype, fetch, head_rows, keep=True,
        control=control)
    loss, (dce, dgate) = jax.jit(jax.value_and_grad(
        functools.partial(exit_loss, beta=cfg["entropy_beta"]),
        argnums=(0, 1)))(ce, gate)
    p = jnp.exp(exit_distribution(gate))

    dh = jnp.zeros_like(inputs[0])     # nothing reads the last pass's z
    for t in reversed(range(T)):
        dh, dp = head_backward(ends.pop(),
                               fetch({n: params[n] for n in HEAD}), dh,
                               dce[t], dgate[t])
        add(dp, t, last=t == 0)
        del dp
        for i in reversed(range(n_layers)):
            dh, dp = backward(inputs.pop(), fetch(block_params(params, i)),
                              dh)
            add({"layers.%d.%s" % (i, n): g for n, g in dp.items()}, t,
                last=t == 0)
            del dp
    p_embed = fetch({"embed_tokens": params["embed_tokens"]})
    _, vjp = jax.vjp(lambda p_: embed(ids, p_, dtype), p_embed)
    grads.update({n: take(n, g) for n, g in vjp(dh)[0].items()})
    return (loss, grads, ce, gate, p, *controlled)
