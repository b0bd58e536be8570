"""Decoder-only language model of the ``lfm2_moe`` family (the public
``config.json`` of LiquidAI/LFM2-8B-A1B carries ``model_type: lfm2_moe``;
``modeling_lfm2_moe.py`` of the transformers library): pre-norm residual
blocks whose MIXER differs in kind by position (``layer_types``) — a gated
causal depthwise convolution of length ``conv_L_cache`` between two linear
projections, or grouped-query attention (``num_key_value_heads`` <
``num_attention_heads``) with an RMS norm over every Q and K head and
rotary embedding on the whole head — and whose feed-forward is a dense
SwiGLU in the first ``num_dense_layers`` layers and routed SwiGLU experts
after them (sigmoid scores, a selection bias, normalised weights, no shared
expert); RMS norm, an output head tied to the embedding, next-token
cross-entropy.

Built from ``fluid.layers`` calls only and run by ``fluid.Executor`` like
every other model here.  The config's keys are the published ones.  Two
more say what ONE chip of an expert-parallel deployment holds:
``num_experts_held`` / ``first_expert_held`` (the experts of each layer
that live here; the router still scores all ``num_experts``, and what the
absent experts would add is left out — ``layers.routed_experts``) and
``vocab_size`` itself (a sliced vocabulary is a smaller vocabulary).  The
plain float32 reference of the same equations is
``models/lfm2_moe_reference.py``.

Not built: the convolution's bias (``conv_bias``, off in the published
config), routing without the selection bias or without normalised weights,
the caches of inference (a K/V cache beside a rolling state of
``conv_L_cache - 1`` positions a convolution layer).
"""

import math

from .. import fluid
from .deepseek_v3 import (_linear, _norm, _w, swiglu, token_feeds,
                          train_on_next_token)

CONV, ATTENTION = "conv", "full_attention"


class Lfm2MoeConfig:
    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=7168, moe_intermediate_size=1792,
                 num_hidden_layers=24, layer_types=None, num_dense_layers=2,
                 num_attention_heads=32, num_key_value_heads=8,
                 conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
                 rope_theta=1000000.0, num_experts=32, num_experts_per_tok=4,
                 norm_topk_prob=True, use_expert_bias=True,
                 routed_scaling_factor=1.0, tie_embedding=True,
                 initializer_range=0.02, num_experts_held=None,
                 first_expert_held=0, bias_update_speed=0.001,
                 max_seq_len=4096):
        if conv_bias:
            raise NotImplementedError("lfm2_moe: conv_bias")
        if not (norm_topk_prob and use_expert_bias):
            raise NotImplementedError(
                "lfm2_moe: routing without normalised weights or without "
                "the selection bias")
        if layer_types is None:       # the published pattern of 24 layers
            layer_types = [ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV
                           for i in range(num_hidden_layers)]
        layer_types = list(layer_types)
        if len(layer_types) != num_hidden_layers or \
                set(layer_types) - {CONV, ATTENTION}:
            raise ValueError("lfm2_moe: layer_types names %d layers of %d as "
                             "%s" % (len(layer_types), num_hidden_layers,
                                     sorted(set(layer_types))))
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = layer_types
        self.num_dense_layers = num_dense_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.conv_L_cache = conv_L_cache
        # ``norm_eps`` is the published key; the shared ``_norm`` reads the
        # ``deepseek_v3`` family's name for it
        self.norm_eps = self.rms_norm_eps = float(norm_eps)
        self.rope_theta = float(rope_theta)
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.tie_embedding = bool(tie_embedding)
        self.initializer_range = float(initializer_range)
        self.num_experts_held = num_experts if num_experts_held is None \
            else num_experts_held
        self.first_expert_held = first_expert_held
        self.bias_update_speed = float(bias_update_speed)
        self.max_seq_len = max_seq_len


def tiny_config(**kw):
    """Small config for tests: hidden 32, 4 query heads over 2 key/value
    heads of 8, 8 experts top-2; a dense convolution layer, an attention
    layer and a convolution layer with experts."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("intermediate_size", 64)
    kw.setdefault("moe_intermediate_size", 24)
    kw.setdefault("num_hidden_layers", 3)
    kw.setdefault("layer_types", [CONV, ATTENTION, CONV])
    kw.setdefault("num_dense_layers", 1)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("num_experts", 8)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("max_seq_len", 16)
    return Lfm2MoeConfig(**kw)


def short_conv(x, cfg, prefix):
    """The convolution mixer: ``W_out (C * conv(B * x'))`` with ``(B, C,
    x') = split_3(W_in x)``.  x [B, S, hidden] -> the same shape."""
    bcx = _linear(x, 3 * cfg.hidden_size, cfg, prefix + ".in_proj")
    y = fluid.layers.gated_short_conv(
        bcx, kernel_size=cfg.conv_L_cache,
        param_attr=_w(cfg, prefix + ".conv"))
    return _linear(y, cfg.hidden_size, cfg, prefix + ".out_proj")


def attention(x, cfg, prefix):
    """Causal grouped-query attention: an RMS norm over the lanes of every
    Q and K head (one scale of ``head_dim`` each), rotary embedding on the
    whole head (rotate-half pairing); K and V keep their
    ``num_key_value_heads`` all the way into ``fused_attention``."""
    B, S = 0, cfg.max_seq_len
    d = cfg.head_dim
    L = fluid.layers

    def heads(name, n, norm=None):            # -> [B, n, S, d]
        t = L.reshape(_linear(x, n * d, cfg, prefix + "." + name),
                      [B, S, n, d])
        if norm:
            t = L.rotary_embedding(_norm(t, cfg, prefix + "." + norm),
                                   theta=cfg.rope_theta, interleaved=False)
        return L.transpose(t, [0, 2, 1, 3])

    n, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
    ctx = L.fused_attention(heads("q_proj", n, "q_layernorm"),
                            heads("k_proj", n_kv, "k_layernorm"),
                            heads("v_proj", n_kv),
                            scale=1.0 / math.sqrt(d), causal=True)
    ctx = L.reshape(L.transpose(ctx, [0, 2, 1, 3]), [B, S, n * d])
    return _linear(ctx, cfg.hidden_size, cfg, prefix + ".out_proj")


def expert_ffn(x, cfg, prefix, loads):
    """The part of the routed sum the held experts give; no shared expert.
    (The family's code adds 1e-6 to the sum of the chosen scores where
    ``routed_experts`` adds 1e-20: 5e-7 relative on a sum of about 2, noted
    in the reference's docstring.)"""
    routed, load, bias = fluid.layers.routed_experts(
        x, cfg.num_experts, cfg.num_experts_per_tok,
        cfg.moe_intermediate_size, num_held=cfg.num_experts_held,
        first_expert=cfg.first_expert_held,
        routed_scaling_factor=cfg.routed_scaling_factor,
        param_attr=_w(cfg, prefix + ".experts"))
    loads.append((bias, load))
    return routed


def decoder(ids, cfg, loads):
    """ids int64 [B, S, 1] -> final hidden states [B, S, hidden]."""
    h = fluid.layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden_size],
        param_attr=_w(cfg, "embed_tokens"))
    for i, kind in enumerate(cfg.layer_types):
        p = "layers.%d" % i
        x = _norm(h, cfg, p + ".operator_norm")
        if kind == CONV:
            h = h + short_conv(x, cfg, p + ".conv")
        else:
            h = h + attention(x, cfg, p + ".self_attn")
        x = _norm(h, cfg, p + ".ffn_norm")
        if i < cfg.num_dense_layers:
            h = h + swiglu(x, cfg.intermediate_size, cfg, p + ".feed_forward")
        else:
            h = h + expert_ffn(x, cfg, p + ".feed_forward", loads)
    return _norm(h, cfg, "embedding_norm")


def build_train(cfg=None, lr=1e-4, optimizer=None):
    """The training program: ``ids`` and ``labels`` -> mean next-token
    cross-entropy over the vocabulary held, the logits ``n(h) E^T`` with E
    the embedding itself where ``tie_embedding`` (its gradient is then the
    sum of the look-up's and the head's).  A step leaves every position's
    loss in the scope (the handle ``token_loss``)."""
    cfg = cfg or Lfm2MoeConfig()
    ids, labels = token_feeds(cfg)
    loads = []
    hidden = decoder(ids, cfg, loads)
    if cfg.tie_embedding:
        embedding = fluid.default_main_program().global_block() \
            .var("embed_tokens")
        logits = fluid.layers.matmul(hidden, embedding, transpose_y=True)
    else:
        logits = _linear(hidden, cfg.vocab_size, cfg, "lm_head")
    return train_on_next_token(ids, labels, logits, loads, cfg, lr, optimizer,
                               keep_token_loss=True)
