"""Recurrent / structured-prediction / generation layer builders.

Reference: ``python/paddle/fluid/layers/nn.py`` — ``dynamic_lstm`` (:423),
``dynamic_gru`` (:975), ``linear_chain_crf``, ``crf_decoding``, ``nce``,
``hsigmoid``, ``cos_sim``, ``beam_search``, ``beam_search_decode``.  The
reference reads sequence structure from LoD; here every sequence layer takes
an explicit ``length`` Variable ([batch]) over padded [batch, time, ...]
data, the same convention as ``layers/sequence.py``.
"""

from .. import unique_name
from ..layer_helper import LayerHelper

__all__ = [
    "dynamic_lstm", "dynamic_gru", "linear_chain_crf", "crf_decoding",
    "nce", "hsigmoid", "cos_sim", "beam_search", "beam_search_decode",
    "fused_attention", "switch_moe", "rms_norm", "rotary_embedding",
    "gated_short_conv", "routed_experts", "moe_bias_update",
]


def dynamic_lstm(input, size, length=None, h_0=None, c_0=None,
                 param_attr=None, bias_attr=None, use_peepholes=True,
                 is_reverse=False, gate_activation="sigmoid",
                 cell_activation="tanh", candidate_activation="tanh",
                 dtype="float32", name=None):
    """LSTM over a pre-projected input [B, T, 4*D]; size = 4*D.

    Returns (hidden, cell), both [B, T, D].
    """
    assert length is not None, \
        "TPU dynamic_lstm needs an explicit length tensor (no LoD)"
    helper = LayerHelper("lstm", param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    D = size // 4
    weight = helper.create_parameter(helper.param_attr, [D, 4 * D], dtype)
    bias_size = [1, 7 * D] if use_peepholes else [1, 4 * D]
    bias = helper.create_parameter(helper.bias_attr, bias_size, dtype,
                                   is_bias=True)
    hidden = helper.create_variable_for_type_inference(dtype)
    cell = helper.create_variable_for_type_inference(dtype)
    if input.shape:
        hidden.shape = tuple(input.shape[:2]) + (D,)
        cell.shape = hidden.shape
    inputs = {"Input": [input], "Weight": [weight], "Length": [length]}
    if bias is not None:
        inputs["Bias"] = [bias]
    if h_0 is not None:
        inputs["H0"] = [h_0]
    if c_0 is not None:
        inputs["C0"] = [c_0]
    helper.append_op("lstm", inputs=inputs,
                     outputs={"Hidden": [hidden], "Cell": [cell]},
                     attrs={"use_peepholes": use_peepholes,
                            "is_reverse": is_reverse,
                            "gate_activation": gate_activation,
                            "cell_activation": cell_activation,
                            "candidate_activation": candidate_activation})
    return hidden, cell


def dynamic_gru(input, size, length=None, h_0=None, param_attr=None,
                bias_attr=None, is_reverse=False, gate_activation="sigmoid",
                candidate_activation="tanh", origin_mode=False,
                dtype="float32", name=None):
    """GRU over a pre-projected input [B, T, 3*D]; size = D.

    Returns hidden [B, T, D].
    """
    assert length is not None, \
        "TPU dynamic_gru needs an explicit length tensor (no LoD)"
    helper = LayerHelper("gru", param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    D = size
    weight = helper.create_parameter(helper.param_attr, [D, 3 * D], dtype)
    bias = helper.create_parameter(helper.bias_attr, [1, 3 * D], dtype,
                                   is_bias=True)
    hidden = helper.create_variable_for_type_inference(dtype)
    if input.shape:
        hidden.shape = tuple(input.shape[:2]) + (D,)
    inputs = {"Input": [input], "Weight": [weight], "Length": [length]}
    if bias is not None:
        inputs["Bias"] = [bias]
    if h_0 is not None:
        inputs["H0"] = [h_0]
    helper.append_op("gru", inputs=inputs, outputs={"Hidden": [hidden]},
                     attrs={"is_reverse": is_reverse,
                            "gate_activation": gate_activation,
                            "activation": candidate_activation,
                            "origin_mode": origin_mode})
    return hidden


def linear_chain_crf(input, label, length=None, param_attr=None):
    """CRF negative log-likelihood; input [B, T, C], label [B, T] int.

    The transition parameter is [C+2, C] (row 0 start, row 1 stop), the
    reference's exact layout, so a trained ``crfw`` feeds crf_decoding.
    """
    assert length is not None, \
        "TPU linear_chain_crf needs an explicit length tensor (no LoD)"
    helper = LayerHelper("linear_chain_crf", param_attr=param_attr)
    num_tags = input.shape[-1]
    transition = helper.create_parameter(helper.param_attr,
                                         [num_tags + 2, num_tags],
                                         input.dtype)
    nll = helper.create_variable_for_type_inference(input.dtype)
    alpha = helper.create_variable_for_type_inference(input.dtype)
    nll.shape = (input.shape[0], 1)
    helper.append_op("linear_chain_crf",
                     inputs={"Emission": [input], "Transition": [transition],
                             "Label": [label], "Length": [length]},
                     outputs={"LogLikelihood": [nll], "Alpha": [alpha]})
    return nll


def crf_decoding(input, length=None, param_attr=None, label=None):
    """Viterbi decode; returns [B, T, 1] int64 path (or 0/1 correctness
    indicators when ``label`` is given, the chunk_eval contract)."""
    assert length is not None
    helper = LayerHelper("crf_decoding", param_attr=param_attr)
    num_tags = input.shape[-1]
    transition = helper.create_parameter(helper.param_attr,
                                         [num_tags + 2, num_tags],
                                         input.dtype)
    path = helper.create_variable_for_type_inference("int64",
                                                     stop_gradient=True)
    path.shape = tuple(input.shape[:2]) + (1,)
    inputs = {"Emission": [input], "Transition": [transition],
              "Length": [length]}
    if label is not None:
        inputs["Label"] = [label]
    helper.append_op("crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": [path]})
    return path


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim")
    out = helper.create_variable_for_type_inference(X.dtype)
    xn = helper.create_variable_for_type_inference(X.dtype)
    yn = helper.create_variable_for_type_inference(X.dtype)
    if X.shape:
        out.shape = tuple(X.shape[:-1]) + (1,)
    helper.append_op("cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]})
    return out


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=None, name=None, sampler="uniform",
        custom_dist=None, seed=0, is_sparse=False):
    """NCE loss layer (reference nn.py nce → nce op); returns [B, 1] cost."""
    helper = LayerHelper("nce", param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    dim = input.shape[-1]
    weight = helper.create_parameter(helper.param_attr,
                                     [num_total_classes, dim], input.dtype)
    bias = helper.create_parameter(helper.bias_attr,
                                   [num_total_classes, 1], input.dtype,
                                   is_bias=True)
    cost = helper.create_variable_for_type_inference(input.dtype)
    sample_logits = helper.create_variable_for_type_inference(input.dtype)
    sample_labels = helper.create_variable_for_type_inference("int64",
                                                              stop_gradient=True)
    cost.shape = (input.shape[0], 1)
    sampler_id = {"uniform": 0, "log_uniform": 1, "custom_dist": 2}[sampler]
    inputs = {"Input": [input], "Label": [label], "Weight": [weight]}
    if bias is not None:
        inputs["Bias"] = [bias]
    if sample_weight is not None:
        inputs["SampleWeight"] = [sample_weight]
    if custom_dist is not None:
        inputs["CustomDistProbs"] = [custom_dist]
        sampler_id = 2
    helper.append_op("nce", inputs=inputs,
                     outputs={"Cost": [cost],
                              "SampleLogits": [sample_logits],
                              "SampleLabels": [sample_labels]},
                     attrs={"num_total_classes": int(num_total_classes),
                            "num_neg_samples": int(num_neg_samples or 10),
                            "sampler": sampler_id, "seed": seed,
                            "is_sparse": is_sparse,
                            "__op_seed__":
                                helper.main_program.next_op_seed()})
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None, path_table=None, path_code=None,
             is_custom=False, is_sparse=False):
    """Hierarchical sigmoid (reference nn.py hsigmoid); returns [B, 1]."""
    helper = LayerHelper("hierarchical_sigmoid", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dim = input.shape[-1]
    if is_custom:
        assert path_table is not None and path_code is not None
        num_nodes = num_classes  # custom tree: caller-sized node table
    else:
        num_nodes = num_classes - 1
    weight = helper.create_parameter(helper.param_attr, [num_nodes, dim],
                                     input.dtype)
    bias = helper.create_parameter(helper.bias_attr, [1, num_nodes],
                                   input.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    pre_out = helper.create_variable_for_type_inference(input.dtype)
    out.shape = (input.shape[0], 1)
    inputs = {"X": [input], "Label": [label], "W": [weight]}
    if bias is not None:
        inputs["Bias"] = [bias]
    if path_table is not None:
        inputs["PathTable"] = [path_table]
        inputs["PathCode"] = [path_code]
    helper.append_op("hierarchical_sigmoid", inputs=inputs,
                     outputs={"Out": [out], "PreOut": [pre_out]},
                     attrs={"num_classes": int(num_classes),
                            "is_sparse": is_sparse})
    return out


def beam_search(pre_ids, pre_scores, ids, scores, beam_size, end_id,
                level=0, is_accumulated=True, name=None):
    """One beam-search step on static [B, K] beams.

    ``ids``/``scores``: [B, K, C] per-beam candidate ids and *accumulated*
    log-probs (typically from topk over log-softmax + pre_scores).
    Returns (selected_ids, selected_scores, parent_idx), all [B, beam_size].
    """
    helper = LayerHelper("beam_search", name=name)
    sel_ids = helper.create_variable_for_type_inference("int64",
                                                        stop_gradient=True)
    sel_scores = helper.create_variable_for_type_inference(
        scores.dtype, stop_gradient=True)
    parent = helper.create_variable_for_type_inference("int64",
                                                       stop_gradient=True)
    if scores.shape:
        sel_ids.shape = (scores.shape[0], int(beam_size))
        sel_scores.shape = sel_ids.shape
        parent.shape = sel_ids.shape
    helper.append_op("beam_search",
                     inputs={"pre_ids": [pre_ids],
                             "pre_scores": [pre_scores],
                             "ids": [ids], "scores": [scores]},
                     outputs={"selected_ids": [sel_ids],
                              "selected_scores": [sel_scores],
                              "parent_idx": [parent]},
                     attrs={"beam_size": int(beam_size),
                            "end_id": int(end_id), "level": int(level),
                            "is_accumulated": bool(is_accumulated)})
    return sel_ids, sel_scores, parent


def beam_search_decode(ids, scores, parent_idx, beam_size, end_id,
                       name=None):
    """Backtrack stacked per-step beams [T, B, K] into sentences.

    Returns (sentence_ids [B, K, T], sentence_scores [B, K]).
    """
    helper = LayerHelper("beam_search_decode", name=name)
    sent_ids = helper.create_variable_for_type_inference("int64",
                                                         stop_gradient=True)
    sent_scores = helper.create_variable_for_type_inference(
        scores.dtype, stop_gradient=True)
    helper.append_op("beam_search_decode",
                     inputs={"Ids": [ids], "Scores": [scores],
                             "ParentIdx": [parent_idx]},
                     outputs={"SentenceIds": [sent_ids],
                              "SentenceScores": [sent_scores]},
                     attrs={"beam_size": int(beam_size),
                            "end_id": int(end_id)})
    return sent_ids, sent_scores


def fused_attention(q, k, v, attn_bias=None, scale=1.0, causal=False,
                    dropout_prob=0.0, is_test=False, name=None,
                    q_rope=None, k_rope=None, num_heads=None, window=0):
    """Fused attention core (ops/pallas_ops.py flash-attention kernel):
    q [B, H, S_q, D], k/v [B, H, S_kv, D] (cross-attention supported),
    optional additive bias [B, 1|H, S_q, S_kv].  k and v may carry fewer
    heads, [B, H_kv, S_kv, D] with ``H % H_kv == 0`` (grouped-query
    attention): query head ``h`` reads key/value head ``h // (H / H_kv)``,
    the flash kernels read K and V where they lie, and no other path
    refuses them (they repeat K and V).
    ``causal=True`` applies the decoder triangular mask inside the kernel
    (static block indices — no [S, S] mask tensor).  ``dropout_prob``
    applies upscale_in_train dropout to the attention probabilities
    (routes through the exact composition — flash has no in-kernel
    RNG; clone(for_test=True) flips ``is_test`` and disables it).

    The op's second output ``LSE`` (float32 [B, H, S_q], the softmax's
    logsumexp rows; lane-dense, never [..., 1]) is the flash kernels'
    residual: written by the forward kernel of a training program and read
    by ``fused_attention_grad`` in place of a second forward run, as
    ``batch_norm`` hands on ``SavedMean`` and ``dropout`` its ``Mask``.

    ``num_heads=H``: the heads lie in the MINOR dimension, as a
    projection's matmul leaves them and the next one reads them: q
    [B, S_q, H * D], k [B, S_kv, H_kv * D], v [B, S_kv, H_kv * D_v]
    (q_rope [B, S_q, H * R], k_rope [B, S_kv, R]), and the output is
    [B, S_q, H * D_v]; the bias and ``LSE`` are what they are without it.
    No reshape or transpose before the op and none after it.  Where a head
    is one tile of the flash kernels in forward and backward (S <= 512 at
    the BERT widths), H_kv = H, D_v = D and the heads pack whole into 128
    lanes (D = 64: a pair of heads a grid cell, because a block's minor
    dimension must be a multiple of 128; D = 128: one), the kernels read
    Q, K, V and the output's gradient in place and write the output and
    the three gradients in place (``ops/pallas_ops._in_place``): the head
    split and merge, which XLA runs as copies that pad 64 numbers to 128
    lanes and keeps for the backward, do not exist.  Every other op in
    this layout (dropout, sequence parallelism, longer sequences, grouped
    heads, a rotary pair, a bias whose gradient is wanted) is split and
    merged inside the lowering and computes what the 4-D op computes.

    ``window=W`` (with ``causal=True``): a sliding window, query ``i`` sees
    keys ``j`` with ``i - W < j <= i`` (W keys, itself among them).  The
    flash kernels visit the tiles of that band alone; 0 is no window, and
    ``W >= S_kv`` is the causal op."""
    if window and (window < 0 or not causal):
        raise ValueError("fused_attention: window=%d needs causal=True and "
                         "a positive size" % window)
    helper = LayerHelper("fused_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    lse = helper.create_variable_for_type_inference("float32",
                                                    stop_gradient=True)
    if num_heads:
        if q.shape and k.shape and v.shape:
            heads_kv = int(k.shape[2]) // (int(q.shape[2]) // num_heads)
            out.shape = tuple(q.shape[:2]) + \
                (int(v.shape[2]) // heads_kv * num_heads,)
            lse.shape = (q.shape[0], num_heads, q.shape[1])
    else:
        out.shape = tuple(q.shape[:3]) + tuple(v.shape[3:]) if q.shape \
            and v.shape else q.shape
        if q.shape:
            lse.shape = tuple(q.shape[:3])
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if attn_bias is not None:
        inputs["BiasQK"] = [attn_bias]
    if (q_rope is None) != (k_rope is None):
        raise ValueError("fused_attention: q_rope and k_rope come together")
    if q_rope is not None:
        inputs["QRope"], inputs["KRope"] = [q_rope], [k_rope]
    helper.append_op("fused_attention", inputs=inputs,
                     outputs={"Out": [out], "LSE": [lse]},
                     attrs={"scale": float(scale),
                            "causal": bool(causal),
                            "attn_dropout": float(dropout_prob),
                            "is_test": bool(is_test),
                            **({"num_heads": int(num_heads)} if num_heads
                               else {}),
                            **({"window": int(window)} if window else {}),
                            "__op_seed__":
                                helper.main_program.next_op_seed()})
    return out


def _suffixed_attr(param_attr, suffix):
    """A layer's several parameters from ONE user attr: a NAMED ParamAttr
    must not collapse them onto one variable, so suffix a COPY's name
    (copy.copy keeps subclass fields like WeightNormParamAttr.dim;
    rebuilding via ParamAttr(**__dict__) would TypeError on them)."""
    import copy
    from ..param_attr import ParamAttr
    attr = copy.copy(ParamAttr._to_attr(param_attr))
    if getattr(attr, "name", None):
        attr.name = attr.name + "." + suffix
    return attr


def switch_moe(x, num_experts, ffn_dim, capacity_factor=1.25, act="relu",
               param_attr=None, with_aux_loss=True, name=None):
    """Switch-routed mixture-of-experts FFN block (ops/moe_ops.py).

    x [..., D] → (out [..., D], aux_loss [1]) — ``aux_loss`` is the
    switch load-balance term (add a small multiple to the training
    loss), or None when ``with_aux_loss=False``.  Beyond-reference
    feature (the reference predates MoE); expert-parallel execution via
    ``fluid.transpiler.ExpertParallelTranspiler`` or fleet
    ``DistributedStrategy(ep_degree=N)``.
    """
    helper = LayerHelper("switch_moe", param_attr=param_attr, name=name)
    D = int(x.shape[-1])
    E, F = int(num_experts), int(ffn_dim)

    if param_attr is False:
        raise ValueError("switch_moe requires parameters; param_attr=False "
                         "is not supported")

    def attr_for(suffix):
        return _suffixed_attr(param_attr, suffix)

    router_w = helper.create_parameter(attr_for("router"), [D, E], x.dtype)
    w1 = helper.create_parameter(attr_for("w1"), [E, D, F], x.dtype)
    w2 = helper.create_parameter(attr_for("w2"), [E, F, D], x.dtype)
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    outputs = {"Out": [out]}
    aux = None
    if with_aux_loss:
        aux = helper.create_variable_for_type_inference("float32")
        aux.shape = (1,)
        outputs["AuxLoss"] = [aux]
    helper.append_op("switch_moe",
                     inputs={"X": [x], "RouterW": [router_w],
                             "W1": [w1], "W2": [w2]},
                     outputs=outputs,
                     attrs={"capacity_factor": float(capacity_factor),
                            "act": act})
    return out, aux


def rms_norm(input, epsilon=1e-5, param_attr=None, name=None):
    """RMS norm over the last axis (ops/decoder_ops.py): ``scale * x /
    sqrt(mean(x^2) + epsilon)``, statistics in float32; the scale is a
    float32 parameter that starts at 1."""
    from ..initializer import ConstantInitializer
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    scale = helper.create_parameter(
        helper.param_attr, [int(input.shape[-1])], "float32",
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    out.shape = input.shape
    helper.append_op("rms_norm", inputs={"X": [input], "Scale": [scale]},
                     outputs={"Y": [out]}, attrs={"epsilon": float(epsilon)})
    return out


def rotary_embedding(x, theta=10000.0, interleaved=True, name=None):
    """Rotary position embedding on the last axis of ``x`` [B, S, heads, D]
    at positions 0..S-1 (the caller slices the rotary part of a head off
    first).  ``interleaved=True``: lanes are paired (2i, 2i+1) as in the
    published ``deepseek_v3`` weights and de-interleaved before the
    rotate-half.  ``interleaved=False``: lanes are paired (i, i + D/2), the
    rotate-half layout of the Llama family's published weights."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op("rotary_embedding", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"theta": float(theta),
                            "interleaved": bool(interleaved)})
    return out


def gated_short_conv(bcx, kernel_size=3, param_attr=None, name=None):
    """Gated causal depthwise convolution of length ``kernel_size``
    (ops/decoder_ops.py).  ``bcx`` [B, S, 3C] holds the gates B and C and
    the signal x in that order along the last axis (one projection's
    output); the result [B, S, C] is ``C_t * sum_j w[:, j] * (B * x)_{t -
    (kernel_size - 1) + j}``, zero before position 0.  The taps ``w`` [C,
    kernel_size] are a float32 parameter."""
    helper = LayerHelper("gated_short_conv", param_attr=param_attr, name=name)
    if int(bcx.shape[-1]) % 3:
        raise ValueError("gated_short_conv: the last axis (%d) holds B, C "
                         "and x, so it is a multiple of 3" % bcx.shape[-1])
    C = int(bcx.shape[-1]) // 3
    w = helper.create_parameter(helper.param_attr, [C, int(kernel_size)],
                                "float32")
    out = helper.create_variable_for_type_inference(bcx.dtype)
    out.shape = tuple(bcx.shape[:-1]) + (C,)
    helper.append_op("gated_short_conv", inputs={"X": [bcx], "W": [w]},
                     outputs={"Out": [out]},
                     attrs={"kernel_size": int(kernel_size)})
    return out


def routed_experts(x, num_experts, top_k, ffn_dim, num_held=None,
                   first_expert=0, routed_scaling_factor=1.0,
                   param_attr=None, name=None, router_input=None,
                   scoring_func="sigmoid", hidden_act="silu"):
    """Routed gated experts without capacity or drops (ops/decoder_ops.py).

    x [..., H] -> (out [..., H], expert_load [num_experts], select_bias
    [num_experts]).  The layer routes over all ``num_experts`` (float32
    sigmoid scores, the ``top_k`` largest of score + ``select_bias``,
    weights normalised over the chosen and scaled) and holds ``num_held`` of
    them, ``first_expert`` onward (default: all): ``out`` is the part of the
    routed sum these give — one chip's share under expert parallelism.
    ``select_bias`` is persistable state without a gradient; move it with
    ``moe_bias_update`` ops after ``minimize`` (``models/deepseek_v3.py``),
    which read ``expert_load`` (tokens that chose each expert).  The load is
    persistable too: the last step's count stays in the scope, where a
    monitor reads the balance without a fetch of its own.

    ``router_input`` (the shape of ``x``): what the router reads where that
    is not the experts' input, e.g. the normed input of the same layer's
    attention; its gradient comes through the weights alone.
    ``scoring_func="softmax"``: the ``top_k`` largest LOGITS (plus the
    bias) are chosen and weighed by the softmax over the chosen logits.
    ``hidden_act="relu"``: the experts gate with ReLU (ReGLU), not SiLU.
    (The lowering refuses any other value of either.)

    The op's ``Kept`` outputs (four variables without a gradient) are the
    rows its backward reads, handed from the forward op to
    ``routed_experts_grad`` where the lowering sizes its row buffers by a
    rung chosen on the device (a layer that holds a small share of the
    experts; ops/decoder_ops.py), as ``fused_attention`` hands on its
    ``LSE``; elsewhere they stay unwritten."""
    from ..initializer import ConstantInitializer
    helper = LayerHelper("routed_experts", param_attr=param_attr, name=name)
    H, E, I = int(x.shape[-1]), int(num_experts), int(ffn_dim)
    n_held = E if num_held is None else int(num_held)
    if not 0 <= first_expert <= E - n_held:
        raise ValueError("routed_experts: experts %d..%d are not among %d"
                         % (first_expert, first_expert + n_held - 1, E))

    def param(suffix, shape):
        return helper.create_parameter(_suffixed_attr(param_attr, suffix),
                                       shape, "float32")

    router_w = param("router", [H, E])
    w_gate = param("gate", [n_held, H, I])
    w_up = param("up", [n_held, H, I])
    w_down = param("down", [n_held, I, H])
    def state(suffix):
        var = helper.create_global_variable(
            name=unique_name.generate(helper.name + suffix),
            shape=(E,), dtype="float32", persistable=True)
        var.stop_gradient = True
        helper.set_variable_initializer(var, ConstantInitializer(0.0))
        return var

    bias, load = state(".select_bias"), state(".expert_load")
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    kept = [helper.create_variable_for_type_inference(x.dtype,
                                                      stop_gradient=True)
            for _ in range(4)]
    attrs = {"top_k": int(top_k), "first_expert": int(first_expert),
             "routed_scaling_factor": float(routed_scaling_factor)}
    if scoring_func != "sigmoid":
        attrs["scoring_func"] = scoring_func
    if hidden_act != "silu":
        attrs["hidden_act"] = hidden_act
    helper.append_op("routed_experts",
                     inputs={"X": [x], "RouterW": [router_w],
                             "SelectBias": [bias], "WGate": [w_gate],
                             "WUp": [w_up], "WDown": [w_down],
                             **({} if router_input is None
                                else {"RouterX": [router_input]})},
                     outputs={"Out": [out], "ExpertLoad": [load],
                              "Kept": kept},
                     attrs=attrs)
    return out, load, bias


def moe_bias_update(select_bias, expert_load, gamma=0.001):
    """Move a ``routed_experts`` layer's selection bias in place:
    ``bias += gamma * sign(mean(load) - load)`` (the auxiliary-loss-free
    balancing of the DeepSeek-V3 report).  Call it after ``minimize``, under
    ``program._optimized_guard([])``: it is optimizer-role state motion."""
    helper = LayerHelper("moe_bias_update")
    helper.append_op("moe_bias_update",
                     inputs={"Bias": [select_bias],
                             "ExpertLoad": [expert_load]},
                     outputs={"BiasOut": [select_bias]},
                     attrs={"gamma": float(gamma)})
    return select_bias
