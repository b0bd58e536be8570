"""BERT pretraining (Devlin et al. 2018, arXiv:1810.04805: masked LM +
next-sentence) and its arithmetic.  ``build`` and ``make_batch`` are
chip_smoke.py's ``build_bert`` / ``bert_batch`` (they ran on the v5e in PR
21); the model is the program under test, ``paddle_tpu.models.bert``.

``params`` is the configuration's JSON file (keys as in the published
``bert_config.json``) merged with the cell's traffic file (``batch``,
``seq_len``).
"""

import math

import numpy as np

FEEDS = ("src_ids", "pos_ids", "sent_ids", "input_mask", "mask_pos",
         "mask_label", "nsp_label")


def model_config(params):
    from paddle_tpu import models

    if params["hidden_act"] != "gelu":
        raise ValueError("models.bert has gelu only")
    return models.bert.BertConfig(
        vocab_size=params["vocab_size"], hidden_size=params["hidden_size"],
        num_layers=params["num_hidden_layers"],
        num_heads=params["num_attention_heads"],
        ffn_size=params["intermediate_size"],
        max_position=params["max_position_embeddings"],
        type_vocab_size=params["type_vocab_size"],
        hidden_dropout=params["hidden_dropout_prob"],
        attn_dropout=params["attention_probs_dropout_prob"],
        max_seq_len=params["seq_len"])


def flash_on_path(params):
    """models/bert.py routes attention through the Pallas flash kernels
    when the fused op is on (its default) and attention dropout is off;
    with dropout the same op runs the XLA composition."""
    return params["attention_probs_dropout_prob"] == 0.0


def build(params):
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    opt = fluid.contrib.mixed_precision.decorate(
        fluid.optimizer.AdamOptimizer(learning_rate=params["learning_rate"]),
        use_pure_bf16=True)
    handles = models.bert.build_pretrain(
        model_config(params), optimizer=opt,
        max_pred_per_seq=params["max_predictions_per_seq"])
    block = fluid.default_main_program().global_block()
    return [block.var(n) for n in FEEDS], handles["loss"]


def make_batch(rng, params):
    """One host batch: uniform token ids, full-length sequences (no
    padding), uniform masked positions and labels."""
    n, s, p = params["batch"], params["seq_len"], \
        params["max_predictions_per_seq"]
    vocab = params["vocab_size"]
    mask_pos = rng.integers(0, s, (n, p)) + np.arange(n)[:, None] * s
    return {
        "src_ids": rng.integers(0, vocab, (n, s, 1), dtype=np.int64),
        "pos_ids": np.tile(np.arange(s, dtype=np.int64)[None, :, None],
                           (n, 1, 1)),
        "sent_ids": np.zeros((n, s, 1), np.int64),
        "input_mask": np.ones((n, s, 1), np.float32),
        "mask_pos": mask_pos.reshape(-1, 1).astype(np.int32),
        "mask_label": rng.integers(0, vocab, (n * p, 1), dtype=np.int64),
        "nsp_label": rng.integers(0, 2, (n, 1), dtype=np.int64),
    }


def first_loss(params):
    """Untrained model, uniform labels: a uniform guess over the vocabulary
    plus one over the two next-sentence classes."""
    return math.log(params["vocab_size"]) + math.log(2)


def expects_in_hlo(params):
    # compiled by Mosaic, not interpreted and not replaced by the reference
    return ["tpu_custom_call"] if flash_on_path(params) else []


def forward_macs(params):
    """Multiply-accumulates of one sequence's forward pass, from shapes.
    Per token and layer: Q, K, V and output projections 4*H*H, the FFN
    2*H*F, attention scores and context 2*S*H.  Heads: the MLM transform
    H*H and the tied decoder H*V on the P predicted positions only, the
    pooler H*H and the NSP classifier 2*H once.  Embedding look-ups, layer
    norm, softmax, gelu and dropout are not matmul work and are left out."""
    h, f = params["hidden_size"], params["intermediate_size"]
    s, v = params["seq_len"], params["vocab_size"]
    layer = 4 * h * h + 2 * h * f + 2 * s * h
    heads = params["max_predictions_per_seq"] * (h * h + h * v) \
        + h * h + 2 * h
    return params["num_hidden_layers"] * s * layer + heads


def flops_per_sample(params):
    """Training FLOPs of one sequence: 2 per multiply-accumulate, backward
    = twice the forward, so 3 x 2 x forward_macs.  The flash backward's
    recomputation of the scores is not counted."""
    return 3 * 2 * forward_macs(params)


def kernel_costs(params):
    """What the flash-attention kernels of ONE training step need, from
    shapes, or None where they are not on the path.  Per (sequence, head),
    S = seq_len, D = head size:

    FLOPs: forward QK^T and PV, 2*S*S*D each = 4*S*S*D; backward the five
    products of the algorithm (scores again, dV, dP, dQ, dK) = 10*S*S*D.
    The repo's backward is two kernels (dQ; dK/dV) that each form the
    scores and dP, so it executes 18*S*S*D; the needed 14 is counted.

    Bytes (bf16 = 2): forward reads Q, K, V and writes O (4*S*D*2);
    backward reads Q, K, V, O, dO and writes dQ, dK, dV (8*S*D*2); the
    float32 log-sum-exp / delta rows are written once and read once each
    (4*S*4).  The additive mask [S, S] is shared by a sequence's heads and
    is needed once per sequence in each pass (2*S*S*4, float32)."""
    if not flash_on_path(params):
        return None
    s, heads = params["seq_len"], params["num_attention_heads"]
    d = params["hidden_size"] // heads
    calls = params["batch"] * heads * params["num_hidden_layers"]
    flops = calls * 14 * s * s * d
    per_head = 12 * s * d * 2 + 4 * s * 4
    bytes_ = calls * per_head + \
        params["batch"] * params["num_hidden_layers"] * 2 * s * s * 4
    return {"flops": flops, "bytes": bytes_}
