"""Looped decoder-only language model of the ``ouro`` family (the public
``config.json`` of ByteDance/Ouro-2.6B carries ``model_type: ouro``; Zhu et
al. 2025, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): ONE stack of ``num_hidden_layers`` sandwich-norm blocks
(multi-head attention with rotary embedding on the whole head in the
rotate-half layout, SwiGLU feed-forward, four RMS norms a block) applied
``total_ut_steps`` times to the residual stream with the SAME parameters.
Every pass ends in the final norm, an untied LM head and a one-unit exit
gate; the training loss weighs the passes' next-token losses by the gate's
exit distribution and rewards that distribution's entropy.

Built from ``fluid.layers`` calls only and run by ``fluid.Executor`` like
every other model here.  The passes are ONE loop in the program: a
``StaticRNN`` with a step count of its own whose memory is the residual
stream and whose sub-block holds the layers and the exit head, so the
``ProgramDesc`` has one ``recurrent`` op over ``total_ut_steps`` and the
block's ops once.  ``build_train(unrolled=True)`` writes the same model
as ``total_ut_steps`` copies of the block over shared parameter names
(gradients joined by ``sum`` ops): the control the loop form is tested and
measured against.  The plain float32 reference of the same equations is
``models/ouro_reference.py``.

Not built: the early exit at inference (``early_exit_threshold``: a
data-dependent ``while``, which carries no gradient and would need a KV
cache a pass), the KV caches.
"""

import math

from .. import fluid


class OuroConfig:
    def __init__(self, vocab_size=49152, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=16,
                 num_key_value_heads=16, head_dim=128,
                 intermediate_size=5632, total_ut_steps=4,
                 rope_theta=1000000.0, rms_norm_eps=1e-6,
                 initializer_range=0.02, entropy_beta=0.05,
                 max_seq_len=4096):
        if num_key_value_heads != num_attention_heads:
            raise NotImplementedError("ouro: grouped-query attention "
                                      "(num_key_value_heads < heads)")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.total_ut_steps = total_ut_steps
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.initializer_range = float(initializer_range)
        self.entropy_beta = float(entropy_beta)
        self.max_seq_len = max_seq_len


def tiny_config(**kw):
    """Small config for tests: 2 layers of 2 heads of 16, 3 passes."""
    kw.setdefault("vocab_size", 128)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 2)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("intermediate_size", 48)
    kw.setdefault("total_ut_steps", 3)
    kw.setdefault("max_seq_len", 16)
    return OuroConfig(**kw)


def _w(cfg, name):
    return fluid.ParamAttr(name=name, initializer=fluid.initializer.Normal(
        loc=0.0, scale=cfg.initializer_range))


def _linear(x, size, cfg, name):
    return fluid.layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                           param_attr=_w(cfg, name))


def _norm(x, cfg, name):
    return fluid.layers.rms_norm(x, epsilon=cfg.rms_norm_eps,
                                 param_attr=fluid.ParamAttr(name=name))


def attention(x, cfg, prefix):
    """Causal multi-head attention, rotary embedding on the whole head
    (rotate-half pairing).  x [B, S, hidden] -> the same shape."""
    B, S = 0, cfg.max_seq_len
    n, d = cfg.num_attention_heads, cfg.head_dim
    L = fluid.layers

    def heads(name, rotate):                  # -> [B, n, S, d]
        t = L.reshape(_linear(x, n * d, cfg, prefix + "." + name),
                      [B, S, n, d])
        if rotate:
            t = L.rotary_embedding(t, theta=cfg.rope_theta,
                                   interleaved=False)
        return L.transpose(t, [0, 2, 1, 3])

    ctx = L.fused_attention(heads("q_proj", True), heads("k_proj", True),
                            heads("v_proj", False),
                            scale=1.0 / math.sqrt(d), causal=True)
    ctx = L.reshape(L.transpose(ctx, [0, 2, 1, 3]), [B, S, n * d])
    return _linear(ctx, cfg.hidden_size, cfg, prefix + ".o_proj")


def swiglu(x, cfg, prefix):
    """W_down(silu(W_gate x) * W_up x)."""
    L = fluid.layers
    width = cfg.intermediate_size
    hidden = L.swish(_linear(x, width, cfg, prefix + ".gate_proj")) * \
        _linear(x, width, cfg, prefix + ".up_proj")
    return _linear(hidden, cfg.hidden_size, cfg, prefix + ".down_proj")


def block(h, cfg, p):
    """One sandwich-norm layer: a norm before AND after each of attention
    and feed-forward, the second inside the residual branch."""
    a = attention(_norm(h, cfg, p + ".input_layernorm"), cfg,
                  p + ".self_attn")
    h = h + _norm(a, cfg, p + ".input_layernorm_2")
    m = swiglu(_norm(h, cfg, p + ".post_attention_layernorm"), cfg,
               p + ".mlp")
    return h + _norm(m, cfg, p + ".post_attention_layernorm_2")


def one_pass(h, labels, cfg):
    """The ``num_hidden_layers`` blocks, then the exit head.  Returns
    ``(z, ce, gate)``: the normed state the next pass starts from, the
    next-token cross-entropy of this pass's logits and the exit gate's
    logit, both float32 [B, S]."""
    L = fluid.layers
    for i in range(cfg.num_hidden_layers):
        h = block(h, cfg, "layers.%d" % i)
    with fluid.name_scope("exit_head"):
        z = _norm(h, cfg, "norm")
        logits = _linear(z, cfg.vocab_size, cfg, "lm_head")
        # the loss is float32 whatever the logits are (models/deepseek_v3)
        ce = L.softmax_with_cross_entropy(L.cast(logits, "float32"), labels)
        # a dot product a token, written elementwise so that it stays
        # float32 under AMP (a ``mul`` would take bf16 operands)
        w_gate = L.create_parameter(
            [cfg.hidden_size], "float32", attr=_w(cfg, "early_exit_gate.w"))
        b_gate = L.create_parameter(
            [1], "float32", attr=fluid.ParamAttr(name="early_exit_gate.b"),
            is_bias=True)
        gate = L.reduce_sum(L.cast(z, "float32") * w_gate, dim=-1) + b_gate
        ce = L.reshape(ce, [0, cfg.max_seq_len])
    return z, ce, gate


def exit_loss(ce, gate, cfg):
    """``ce``, ``gate`` [T, B, S] float32 -> (loss [1], exit distribution
    ``p`` [T, B, S]).  ``lambda_t = sigmoid(gate_t)``; ``p_t = lambda_t
    prod_{j<t} (1 - lambda_j)`` for ``t < T`` and ``p_T = prod_{j<T} (1 -
    lambda_j)`` (the last pass takes what is left; its own gate is not
    read); loss = mean over tokens of ``sum_t p_t ce_t - beta H(p)``, in
    log space so that a saturated gate stays finite."""
    L = fluid.layers
    T = cfg.total_ut_steps
    log_lam = L.logsigmoid(gate)
    log_stay = log_lam - gate                       # log(1 - lambda)
    survived = L.cumsum(log_stay, axis=0, exclusive=True)
    if T > 1:
        log_p = L.concat(
            [L.slice(survived + log_lam, axes=[0], starts=[0], ends=[T - 1]),
             L.slice(survived, axes=[0], starts=[T - 1], ends=[T])], axis=0)
    else:
        log_p = survived
    p = L.exp(log_p)
    per_token = L.reduce_sum(p * ce + cfg.entropy_beta * (p * log_p), dim=0)
    return L.mean(per_token), p


def build_train(cfg=None, lr=1e-4, optimizer=None, unrolled=False):
    """The training program: ``ids`` and ``labels`` (int64 [B, S, 1]; the
    labels are the ids shifted by one, the caller's business) -> the
    exit-weighted loss, minimised by Adam or the caller's ``optimizer``.
    ``unrolled``: the passes as copies of the block instead of one loop."""
    cfg = cfg or OuroConfig()
    L = fluid.layers
    S, T = cfg.max_seq_len, cfg.total_ut_steps
    ids = L.data(name="ids", shape=[S, 1], dtype="int64")
    labels = L.data(name="labels", shape=[S, 1], dtype="int64")
    h = L.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                    param_attr=_w(cfg, "embed_tokens"))
    if unrolled:
        ces, gates = [], []
        for _ in range(T):
            h, ce_t, gate_t = one_pass(h, labels, cfg)
            ces.append(ce_t)
            gates.append(gate_t)
        ce, gate = L.stack(ces, axis=0), L.stack(gates, axis=0)
    else:
        loop = L.StaticRNN(name="ut_passes", steps=T)
        with loop.step():
            state = loop.memory(init=h)
            z, ce_t, gate_t = one_pass(state, labels, cfg)
            loop.update_memory(state, z)
            loop.step_output(ce_t)
            loop.step_output(gate_t)
        ce, gate = loop()
    loss, p = exit_loss(ce, gate, cfg)
    # a step leaves what the passes gave in the scope (float32 [T, B, S]
    # each, written and never read), as ``routed_experts`` leaves its load:
    # what a caller holds against a reference is the training step's own
    ce.persistable = p.persistable = True
    opt = optimizer or fluid.optimizer.AdamOptimizer(learning_rate=lr)
    opt.minimize(loss)
    return {"loss": loss, "ce": ce, "gate": gate, "exit_distribution": p,
            "feeds": [ids, labels], "optimizer": opt, "config": cfg}
