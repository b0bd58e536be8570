"""Decoder-only language model of the ``deepseek_v3`` family (the public
``config.json`` of DeepSeek-V3 and of Moonlight-16B-A3B carries
``model_type: deepseek_v3``): pre-norm blocks of multi-head LATENT
attention (MLA) and a SwiGLU feed-forward that is dense in the first
``first_k_dense_replace`` layers and a mixture of routed experts plus shared
experts after them; RMS norm, rotary embedding on a slice of each head, an
untied output head, next-token cross-entropy.

Built from ``fluid.layers`` calls only and run by ``fluid.Executor`` like
every other model here.  The config's keys are the published ones.  Two
more say what ONE chip of an expert-parallel deployment holds:
``n_routed_experts_held`` / ``first_expert_held`` (the experts of each
layer that live here; the router still scores all ``n_routed_experts``, and
what the absent experts would add is left out — ``layers.routed_experts``)
and ``vocab_size`` itself (a sliced vocabulary is a smaller vocabulary: ids,
logits and loss are over the rows held).  The plain float32 reference of
the same equations is ``models/deepseek_v3_reference.py``.

Not built (the keys exist in the family, Moonlight sets them off):
``q_lora_rank`` (the low-rank Q projection), group-limited routing
(``n_group`` / ``topk_group`` > 1), YaRN rope scaling, multi-token
prediction, the sequence-wise auxiliary loss.
"""

import math

from .. import fluid


class DeepseekV3Config:
    def __init__(self, vocab_size=163840, hidden_size=2048,
                 num_hidden_layers=27, num_attention_heads=16,
                 kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=50000.0,
                 intermediate_size=11264, moe_intermediate_size=1408,
                 n_routed_experts=64, num_experts_per_tok=6,
                 n_shared_experts=2, first_k_dense_replace=1,
                 routed_scaling_factor=2.446, rms_norm_eps=1e-5,
                 n_group=1, topk_group=1, initializer_range=0.02,
                 n_routed_experts_held=None, first_expert_held=0,
                 bias_update_speed=0.001, max_seq_len=4096):
        if q_lora_rank is not None:
            raise NotImplementedError("deepseek_v3: q_lora_rank (the "
                                      "low-rank Q projection) is not built")
        if n_group != 1 or topk_group != 1:
            raise NotImplementedError("deepseek_v3: group-limited routing "
                                      "(n_group / topk_group > 1)")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = float(rope_theta)
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.first_k_dense_replace = first_k_dense_replace
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = float(rms_norm_eps)
        self.initializer_range = float(initializer_range)
        self.n_routed_experts_held = n_routed_experts \
            if n_routed_experts_held is None else n_routed_experts_held
        self.first_expert_held = first_expert_held
        self.bias_update_speed = float(bias_update_speed)
        self.max_seq_len = max_seq_len


def tiny_config(**kw):
    """Small config for tests: 2 heads of nope 16 / rope 8 / v 16, latent
    32, 8 experts top-2 with 2 shared, one dense and one expert layer."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 2)
    kw.setdefault("kv_lora_rank", 32)
    kw.setdefault("qk_nope_head_dim", 16)
    kw.setdefault("qk_rope_head_dim", 8)
    kw.setdefault("v_head_dim", 16)
    kw.setdefault("intermediate_size", 64)
    kw.setdefault("moe_intermediate_size", 24)
    kw.setdefault("n_routed_experts", 8)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("max_seq_len", 16)
    return DeepseekV3Config(**kw)


def _w(cfg, name):
    return fluid.ParamAttr(name=name, initializer=fluid.initializer.Normal(
        loc=0.0, scale=cfg.initializer_range))


def _linear(x, size, cfg, name):
    return fluid.layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                           param_attr=_w(cfg, name))


def _norm(x, cfg, name):
    return fluid.layers.rms_norm(x, epsilon=cfg.rms_norm_eps,
                                 param_attr=fluid.ParamAttr(name=name))


def latent_attention(x, cfg, prefix):
    """MLA with ``q_lora_rank`` null.  x [B, S, hidden] -> the same shape.
    K and V come out of a shared latent ``c_kv`` (RMS-normed, then
    up-projected per head); the rotary part of K is ONE head shared by all
    query heads and is handed to ``fused_attention`` as it is."""
    B, S = 0, cfg.max_seq_len
    n, nope, rope, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim, cfg.v_head_dim
    L = fluid.layers

    q = L.reshape(_linear(x, n * (nope + rope), cfg, prefix + ".q_proj"),
                  [B, S, n, nope + rope])
    q_nope, q_pe = L.split(q, [nope, rope], dim=3)
    kva = _linear(x, cfg.kv_lora_rank + rope, cfg,
                  prefix + ".kv_a_proj_with_mqa")
    c_kv, k_pe = L.split(kva, [cfg.kv_lora_rank, rope], dim=2)
    kv = L.reshape(
        _linear(_norm(c_kv, cfg, prefix + ".kv_a_layernorm"),
                n * (nope + dv), cfg, prefix + ".kv_b_proj"),
        [B, S, n, nope + dv])
    k_nope, v = L.split(kv, [nope, dv], dim=3)
    q_pe = L.rotary_embedding(q_pe, theta=cfg.rope_theta)
    k_pe = L.rotary_embedding(L.reshape(k_pe, [B, S, 1, rope]),
                              theta=cfg.rope_theta)

    def heads_first(t):                       # [B, S, n, d] -> [B, n, S, d]
        return L.transpose(t, [0, 2, 1, 3])

    ctx = L.fused_attention(
        heads_first(q_nope), heads_first(k_nope), heads_first(v),
        scale=1.0 / math.sqrt(nope + rope), causal=True,
        q_rope=heads_first(q_pe), k_rope=heads_first(k_pe))
    ctx = L.reshape(L.transpose(ctx, [0, 2, 1, 3]), [B, S, n * dv])
    return _linear(ctx, cfg.hidden_size, cfg, prefix + ".o_proj")


def swiglu(x, width, cfg, prefix):
    """W_down(silu(W_gate x) * W_up x)."""
    L = fluid.layers
    hidden = L.swish(_linear(x, width, cfg, prefix + ".gate_proj")) * \
        _linear(x, width, cfg, prefix + ".up_proj")
    return _linear(hidden, cfg.hidden_size, cfg, prefix + ".down_proj")


def expert_ffn(x, cfg, prefix, loads):
    """Routed experts (the part the held ones give) + the shared experts,
    which every chip computes whole: one SwiGLU of ``n_shared_experts``
    times the expert width."""
    routed, load, bias = fluid.layers.routed_experts(
        x, cfg.n_routed_experts, cfg.num_experts_per_tok,
        cfg.moe_intermediate_size, num_held=cfg.n_routed_experts_held,
        first_expert=cfg.first_expert_held,
        routed_scaling_factor=cfg.routed_scaling_factor,
        param_attr=_w(cfg, prefix + ".experts"))
    loads.append((bias, load))
    shared = swiglu(x, cfg.n_shared_experts * cfg.moe_intermediate_size,
                    cfg, prefix + ".shared_experts")
    return routed + shared


def decoder(ids, cfg, loads):
    """ids int64 [B, S, 1] -> final hidden states [B, S, hidden]."""
    h = fluid.layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden_size],
        param_attr=_w(cfg, "embed_tokens"))
    for i in range(cfg.num_hidden_layers):
        p = "layers.%d" % i
        h = h + latent_attention(_norm(h, cfg, p + ".input_layernorm"), cfg,
                                 p + ".self_attn")
        x = _norm(h, cfg, p + ".post_attention_layernorm")
        if i < cfg.first_k_dense_replace:
            h = h + swiglu(x, cfg.intermediate_size, cfg, p + ".mlp")
        else:
            h = h + expert_ffn(x, cfg, p + ".mlp", loads)
    return _norm(h, cfg, "norm")


def token_feeds(cfg):
    """``ids`` and ``labels``, int64 [B, S, 1]; the labels are the ids
    shifted by one, the caller's business."""
    S = cfg.max_seq_len
    return (fluid.layers.data(name="ids", shape=[S, 1], dtype="int64"),
            fluid.layers.data(name="labels", shape=[S, 1], dtype="int64"))


def train_on_next_token(ids, labels, logits, loads, cfg, lr, optimizer,
                        keep_token_loss=False):
    """Mean next-token cross-entropy of ``logits`` over the vocabulary
    held, minimised by Adam or the caller's ``optimizer``; then one
    ``moe_bias_update`` per expert layer (``loads``: ``(selection bias,
    expert load)`` pairs) in the optimizer role: the bias has no gradient.
    Returns ``build_train``'s handles.  ``keep_token_loss``: a step leaves
    every position's loss in the scope (float32 [B, S, 1], written and
    never read, as ``routed_experts`` leaves its load)."""
    # the loss is float32 whatever the logits are: under pure-bf16 AMP a
    # per-token loss in bf16 has steps of 0.03 at ln(vocabulary)
    token_loss = fluid.layers.softmax_with_cross_entropy(
        fluid.layers.cast(logits, "float32"), labels)
    token_loss.persistable = bool(keep_token_loss)
    loss = fluid.layers.mean(token_loss)
    opt = optimizer or fluid.optimizer.AdamOptimizer(learning_rate=lr)
    opt.minimize(loss)
    program = fluid.default_main_program()
    with program._optimized_guard([]):
        for bias, load in loads:
            fluid.layers.moe_bias_update(bias, load,
                                         gamma=cfg.bias_update_speed)
    return {"loss": loss, "logits": logits, "token_loss": token_loss,
            "feeds": [ids, labels],
            "expert_loads": [load for _, load in loads],
            "select_biases": [bias for bias, _ in loads],
            "optimizer": opt, "config": cfg}


def build_train(cfg=None, lr=1e-4, optimizer=None):
    """The training program: ``ids`` and ``labels`` -> mean next-token
    cross-entropy over the vocabulary held (``train_on_next_token``)."""
    cfg = cfg or DeepseekV3Config()
    ids, labels = token_feeds(cfg)
    loads = []
    hidden = decoder(ids, cfg, loads)
    logits = _linear(hidden, cfg.vocab_size, cfg, "lm_head")
    return train_on_next_token(ids, labels, logits, loads, cfg, lr, optimizer)
