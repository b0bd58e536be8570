"""Decoder-only language model of the SmallThinker family (the public
``config.json`` of PowerInfer/SmallThinker-21BA3B-Instruct; Song et al.
2025, arXiv:2507.20984): pre-norm residual blocks whose attention differs
by position in two ways at once — ``sliding_window_layout[l]``: a sliding
window of ``sliding_window_size`` keys or the whole causal prefix;
``rope_layout[l]``: rotary embedding on the whole head or NO positional
signal at all — grouped-query attention (``num_key_value_heads`` <
``num_attention_heads``, no bias, no Q/K norm), and in EVERY layer routed
ReGLU experts whose router reads the normed input of the layer's ATTENTION
while the experts read the normed input of the feed-forward (softmax over
the chosen logits, no selection bias, no shared expert); RMS norm, an
untied head, next-token cross-entropy.

    u = n_in(h);  h' = h + attn_l(u);  x = n_post(h');  h'' = h' + moe(r=u, x)

Built from ``fluid.layers`` calls only and run by ``fluid.Executor`` like
every other model here.  The config's keys are the published ones.  Two
more say what ONE chip of an expert-parallel deployment holds:
``moe_num_primary_experts_held`` / ``first_expert_held`` (the experts of
each layer that live here; the router still scores all
``moe_num_primary_experts``, and what the absent experts would add is left
out — ``layers.routed_experts``) and ``vocab_size`` itself.  ``recompute``:
the training program keeps the residual stream entering each layer and the
final norm's input and replays each layer in the backward
(``RecomputeOptimizer``), the way a 16k-token step fits one chip.
``embedding_initializer_range``: the deviation the embedding table alone is
drawn at (absent: ``initializer_range``, like every matrix).  The plain
float32 reference of the same equations is
``models/smallthinker_reference.py``.

Not built: ``moe_primary_router_apply_softmax`` false (sigmoid weights) or
``norm_topk_prob`` false, the secondary experts the report mentions (the
published config has none), the caches of inference.
"""

import math

from .. import fluid
from .deepseek_v3 import _linear, _norm, _w, token_feeds, train_on_next_token


class SmallThinkerConfig:
    def __init__(self, vocab_size=151936, hidden_size=2560,
                 num_hidden_layers=52, num_attention_heads=28,
                 num_key_value_heads=4, head_dim=128,
                 max_position_embeddings=16384, rms_norm_eps=1e-6,
                 rope_theta=1500000.0, rope_layout=None, rope_scaling=None,
                 sliding_window_layout=None, sliding_window_size=4096,
                 moe_ffn_hidden_size=768, moe_num_primary_experts=64,
                 moe_num_active_primary_experts=6,
                 moe_primary_router_apply_softmax=True, norm_topk_prob=True,
                 moe_enable_early_router=True, hidden_act="relu",
                 tie_word_embeddings=False, initializer_range=0.02,
                 embedding_initializer_range=None,
                 moe_num_primary_experts_held=None, first_expert_held=0,
                 recompute=True, max_seq_len=None):
        if not (moe_primary_router_apply_softmax and norm_topk_prob):
            raise NotImplementedError(
                "smallthinker: routing without the softmax over the chosen "
                "logits")
        if rope_scaling is not None or tie_word_embeddings:
            raise NotImplementedError(
                "smallthinker: rope_scaling or a tied head")

        def layout(given, name):      # the published period: 0 1 1 1
            given = [int(i % 4 != 0) for i in range(num_hidden_layers)] \
                if given is None else [int(v) for v in given]
            if len(given) != num_hidden_layers or set(given) - {0, 1}:
                raise ValueError("smallthinker: %s names %d layers of %d"
                                 % (name, len(given), num_hidden_layers))
            return given
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.rope_layout = layout(rope_layout, "rope_layout")
        self.sliding_window_layout = layout(sliding_window_layout,
                                            "sliding_window_layout")
        self.sliding_window_size = int(sliding_window_size)
        self.moe_ffn_hidden_size = moe_ffn_hidden_size
        self.moe_num_primary_experts = moe_num_primary_experts
        self.moe_num_active_primary_experts = moe_num_active_primary_experts
        self.moe_enable_early_router = bool(moe_enable_early_router)
        self.hidden_act = hidden_act
        self.initializer_range = float(initializer_range)
        # the embedding table's own deviation where it is not the matrices'
        self.embedding_initializer_range = self.initializer_range \
            if embedding_initializer_range is None \
            else float(embedding_initializer_range)
        self.moe_num_primary_experts_held = moe_num_primary_experts \
            if moe_num_primary_experts_held is None \
            else moe_num_primary_experts_held
        self.first_expert_held = first_expert_held
        self.recompute = bool(recompute)
        self.max_seq_len = max_seq_len or max_position_embeddings


def tiny_config(**kw):
    """Small config for tests: hidden 32, 6 query heads over 2 key/value
    heads of 8 (a group of 3), a window of 8 at 32 tokens, one full layer
    without positions and three windowed with rotary, 8 experts of 24 with
    2 a token."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("num_hidden_layers", 4)
    kw.setdefault("num_attention_heads", 6)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("head_dim", 8)
    kw.setdefault("rope_layout", [0, 1, 1, 1])
    kw.setdefault("sliding_window_layout", [0, 1, 1, 1])
    kw.setdefault("sliding_window_size", 8)
    kw.setdefault("moe_ffn_hidden_size", 24)
    kw.setdefault("moe_num_primary_experts", 8)
    kw.setdefault("moe_num_active_primary_experts", 2)
    kw.setdefault("max_seq_len", 32)
    return SmallThinkerConfig(**kw)


def attention(x, cfg, prefix, layer):
    """Causal grouped-query attention of layer ``layer``: rotary embedding
    on the whole head of Q and K (rotate-half pairing) where ``rope_layout``
    says so and no positional signal where not; a sliding window inside
    ``fused_attention`` where ``sliding_window_layout`` says so; K and V
    keep their ``num_key_value_heads`` all the way into the op."""
    B, S = 0, cfg.max_seq_len
    d = cfg.head_dim
    L = fluid.layers

    def heads(name, n, positions):            # -> [B, n, S, d]
        t = L.reshape(_linear(x, n * d, cfg, prefix + "." + name),
                      [B, S, n, d])
        if positions:
            t = L.rotary_embedding(t, theta=cfg.rope_theta,
                                   interleaved=False)
        return L.transpose(t, [0, 2, 1, 3])

    n, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
    rope = cfg.rope_layout[layer]
    window = cfg.sliding_window_size if cfg.sliding_window_layout[layer] \
        else 0
    ctx = L.fused_attention(heads("q_proj", n, rope),
                            heads("k_proj", n_kv, rope),
                            heads("v_proj", n_kv, False),
                            scale=1.0 / math.sqrt(d), causal=True,
                            window=window)
    ctx = L.reshape(L.transpose(ctx, [0, 2, 1, 3]), [B, S, n * d])
    return _linear(ctx, cfg.hidden_size, cfg, prefix + ".o_proj")


def expert_ffn(x, router_input, cfg, prefix, loads):
    """The part of the routed sum the held experts give: ReGLU experts, the
    router fed from ``router_input`` (None: from ``x``), the softmax over
    the chosen logits; no shared expert, and a selection bias that stays
    zero (nothing moves it)."""
    routed, load, _ = fluid.layers.routed_experts(
        x, cfg.moe_num_primary_experts, cfg.moe_num_active_primary_experts,
        cfg.moe_ffn_hidden_size, num_held=cfg.moe_num_primary_experts_held,
        first_expert=cfg.first_expert_held,
        param_attr=_w(cfg, prefix + ".experts"), router_input=router_input,
        scoring_func="softmax", hidden_act=cfg.hidden_act)
    loads.append(load)
    return routed


def decoder(ids, cfg, loads, kept):
    """ids int64 [B, S, 1] -> final hidden states [B, S, hidden].  ``kept``
    collects the residual stream entering each layer and the final norm's
    input: ``RecomputeOptimizer``'s checkpoints."""
    h = fluid.layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden_size],
        param_attr=fluid.ParamAttr(
            name="embed_tokens", initializer=fluid.initializer.Normal(
                loc=0.0, scale=cfg.embedding_initializer_range)))
    for i in range(cfg.num_hidden_layers):
        kept.append(h)
        p = "layers.%d" % i
        u = _norm(h, cfg, p + ".input_layernorm")
        h = h + attention(u, cfg, p + ".self_attn", i)
        x = _norm(h, cfg, p + ".post_attention_layernorm")
        h = h + expert_ffn(x, u if cfg.moe_enable_early_router else None,
                           cfg, p + ".block_sparse_moe", loads)
    kept.append(h)
    return _norm(h, cfg, "norm")


def build_train(cfg=None, lr=1e-4, optimizer=None):
    """The training program: ``ids`` and ``labels`` -> mean next-token
    cross-entropy over the vocabulary held, through an untied head.  With
    ``cfg.recompute`` the optimizer (Adam, or the caller's, e.g. one that
    ``mixed_precision.decorate`` wrapped) runs under ``RecomputeOptimizer``
    with each layer's input and the final norm's input as its checkpoints.
    A step leaves every position's loss in the scope (``token_loss``)."""
    cfg = cfg or SmallThinkerConfig()
    ids, labels = token_feeds(cfg)
    loads, kept = [], []
    hidden = decoder(ids, cfg, loads, kept)
    logits = _linear(hidden, cfg.vocab_size, cfg, "lm_head")
    opt = optimizer or fluid.optimizer.AdamOptimizer(learning_rate=lr)
    if cfg.recompute:
        opt = fluid.optimizer.RecomputeOptimizer(opt)._set_checkpoints(kept)
    handles = train_on_next_token(ids, labels, logits, [], cfg, lr, opt,
                                  keep_token_loss=True)
    handles["expert_loads"] = loads
    return handles
