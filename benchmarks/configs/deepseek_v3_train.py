"""Training a ``deepseek_v3``-family decoder (latent attention, routed and
shared SwiGLU experts) on next-token prediction, and its arithmetic.  The
model is the program under test, ``paddle_tpu.models.deepseek_v3``; the
plain float32 reference it is held to is ``deepseek_v3_reference.py`` beside
this file (a verbatim copy of ``paddle_tpu/models/deepseek_v3_reference.py``).

``params`` is the configuration's JSON file (keys as in the published
``config.json``, plus ``n_routed_experts_held`` / ``first_expert_held``: the
experts of each layer this chip holds) merged with the cell's traffic file
(``batch``, ``seq_len``).
"""

import functools
import importlib.util
import math
import os

import numpy as np

# a program without the model cannot run this configuration: fail at once
from paddle_tpu.models import deepseek_v3 as model

# -- the comparison that decides ``correct`` ----------------------------------
# ONE step of the timed program (the window's program, feed signature and
# fetch list, so the window's executable) on the pool's first batch; what it
# leaves in the scope — every parameter, both Adam moments of each, every
# selection bias and expert load — against the float32 reference's gradient
# put through Adam by hand from a host copy of the state before the step.
# The limits are readings of the chip at the published widths (PERF.md
# section 6, PR 28).
#
# 1. Share of the expert layers' T * k assignments that went to another
#    expert than in the reference (half the L1 distance of the two loads,
#    summed over the layers: one layer alone reads 0.0017 to 0.0039, the
#    deeper the higher).  The program's float32 router sees bf16 activations
#    and moved 0.00245 to 0.00302 of them (27 logged runs); the reference with
#    everything, the router too, in bfloat16 — the nearest precision below
#    the configuration's — moved 0.00583 to 0.00675 (9 runs), and the
#    program itself with a bfloat16 router, through the harness, 0.00633.
#    This is the limit that refuses a lower precision.
LOAD_LIMIT = 0.0042
# 2. Every leaf's change over the step, relative: ||after - expected|| /
#    ||expected - before||, for the parameter and both moments.  A state
#    left unchanged reads exactly 1; a precision moves it little (the
#    program and the bfloat16 reference both multiply bf16 operands), so
#    each limit lies between the program's largest reading and 1, with the
#    more room, as a ratio, above the reading.  The routed experts and their
#    router carry the moved assignments (a moved row is a row in another
#    expert's sum: sqrt(2 * 0.003) = 0.08 of a gradient before any
#    rounding) and read up to 0.112 / 0.165 / 0.182 (parameter / first /
#    second moment; 10 runs, 69 leaves each); every other tensor carries
#    bf16 rounding alone and reads up to 0.021 / 0.051 / 0.055.
CHANGE_LIMITS = {"routed": 0.45, "dense": 0.25}
# 3. |loss - reference| / reference.  The precision hardly moves it (5e-6
#    to 3.9e-4 over 19 logged runs; the bfloat16 reference's 5e-5 to 9e-4
#    overlap that: signed noise around zero), so it gets near three times
#    the program's largest reading and refuses only a wrong loss; the
#    harness holds the first loss to 3% of its analytic value besides.
LOSS_LIMIT = 1e-3

_state = {}


@functools.lru_cache(maxsize=None)
def _reference():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "deepseek_v3_reference",
        os.path.join(here, "deepseek_v3_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def model_config(params):
    if params["hidden_act"] != "silu" or params["scoring_func"] != "sigmoid" \
            or params["topk_method"] != "noaux_tc" \
            or not params["norm_topk_prob"] \
            or params["tie_word_embeddings"] or params["attention_bias"] \
            or params["moe_layer_freq"] != 1 \
            or params["num_nextn_predict_layers"] \
            or params["num_key_value_heads"] != params["num_attention_heads"]:
        raise ValueError("models.deepseek_v3 builds silu SwiGLU, sigmoid "
                         "noaux_tc routing with normalised weights, an "
                         "untied head, no attention bias, experts in every "
                         "layer after the dense ones and no MTP layer")
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "kv_lora_rank", "q_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
            "first_k_dense_replace", "routed_scaling_factor", "rms_norm_eps",
            "n_group", "topk_group", "initializer_range",
            "n_routed_experts_held", "first_expert_held",
            "bias_update_speed")
    return model.DeepseekV3Config(max_seq_len=params["seq_len"],
                                  **{k: params[k] for k in keys})


def build(params):
    import paddle_tpu.fluid as fluid

    opt = fluid.contrib.mixed_precision.decorate(
        fluid.optimizer.AdamOptimizer(learning_rate=params["learning_rate"]),
        use_pure_bf16=True)
    handles = model.build_train(model_config(params), optimizer=opt)
    _state["handles"] = handles
    return handles["feeds"], handles["loss"]


def make_batch(rng, params):
    """One host batch: ids uniform over the vocabulary rows held, full
    sequences; the labels are the ids shifted by one (one more id is drawn
    for the last position)."""
    ids = rng.integers(0, params["vocab_size"],
                       (params["batch"], params["seq_len"] + 1),
                       dtype=np.int64)
    batch = {"ids": np.ascontiguousarray(ids[:, :-1, None]),
             "labels": np.ascontiguousarray(ids[:, 1:, None])}
    _state.setdefault("batch", batch)      # the pool's first: ``reference``
    return batch


def first_loss(params):
    """Untrained model, uniform labels.  The final RMS norm hands the head
    rows of mean square 1, and the head's weights are N(0, r^2), so the
    logits are N(0, hidden * r^2) and E[logsumexp] = ln V + hidden * r^2 /
    2 for a large vocabulary; the label's own logit has mean 0."""
    return math.log(params["vocab_size"]) + \
        params["hidden_size"] * params["initializer_range"] ** 2 / 2


def expects_in_hlo(params):
    # the flash kernels, compiled by Mosaic (causal self-attention at a
    # length that tiles; no attention dropout in this family)
    return ["tpu_custom_call"]


def expected_rows_per_token(params):
    """Rows the held experts compute per token, in expectation under an
    even router: top-k assignments, each held with probability held / E.
    (The run's own count is logged by ``reference`` from ``ExpertLoad``.)"""
    return params["num_experts_per_tok"] * params["n_routed_experts_held"] \
        / params["n_routed_experts"]


def forward_macs(params):
    """Multiply-accumulates of one sequence's forward pass, from shapes.
    Per token and layer: the MLA projections (q, kv_a, kv_b, o) and the
    attention scores and context over the CAUSAL half of the S x S square
    (S * (nope + rope + v) * heads / 2); then the dense SwiGLU (3 * H * F),
    or the shared experts (3 * H * shared * I), the router (H * E) and the
    routed experts at the EXPECTED rows a token (``expected_rows_per_
    token``: 0.75 for 6 of 64 with 8 held) times 3 * H * I.  Once: the head
    H * V.  Embedding look-ups, norms, rotary, softmax, silu, the sort and
    the gathers are not matmul work and are left out."""
    h, s = params["hidden_size"], params["seq_len"]
    n = params["num_attention_heads"]
    nope, rope, dv = params["qk_nope_head_dim"], \
        params["qk_rope_head_dim"], params["v_head_dim"]
    r = params["kv_lora_rank"]
    attn = h * n * (nope + rope) + h * (r + rope) + r * n * (nope + dv) \
        + n * dv * h + s * (nope + rope + dv) * n // 2
    dense = 3 * h * params["intermediate_size"]
    width = params["moe_intermediate_size"]
    experts = 3 * h * params["n_shared_experts"] * width \
        + h * params["n_routed_experts"] \
        + expected_rows_per_token(params) * 3 * h * width
    n_dense = params["first_k_dense_replace"]
    n_moe = params["num_hidden_layers"] - n_dense
    per_token = params["num_hidden_layers"] * attn + n_dense * dense \
        + n_moe * experts + h * params["vocab_size"]
    return s * per_token


def flops_per_sample(params):
    """Training FLOPs of one sequence: 2 per multiply-accumulate, backward
    = twice the forward.  The flash backward's recomputation of the scores
    is not counted."""
    return 3 * 2 * forward_macs(params)


def kernel_costs(params):
    """What the flash-attention kernels of ONE training step need, from
    shapes.  Per (sequence, head, layer), S = seq_len, D_qk = nope + rope,
    D_v = v, over the CAUSAL half of the square:

    FLOPs: forward QK^T and PV, 2 * (S*S/2) * (D_qk + D_v); backward the
    five products of the algorithm (scores again D_qk, dP D_v, dV D_v, dQ
    D_qk, dK D_qk) = 2 * (S*S/2) * (3 * D_qk + 2 * D_v).  The repo's
    backward is two kernels that each form the scores and dP; the needed
    five are counted.

    Bytes (bf16 = 2): the forward reads Q (nope and rope parts), K's nope
    part and V per head and writes O; the backward reads those and dO and
    writes dQ (both parts), dK's nope part and dV; the ONE rotary key head
    of a sequence is read once a pass and its gradient written once, per
    sequence and not per head; the float32 log-sum-exp / delta rows are
    written once and read once each (4 * S * 4)."""
    s, heads = params["seq_len"], params["num_attention_heads"]
    nope, rope, dv = params["qk_nope_head_dim"], \
        params["qk_rope_head_dim"], params["v_head_dim"]
    dqk = nope + rope
    seqs = params["batch"] * params["num_hidden_layers"]
    flops = seqs * heads * s * s * ((dqk + dv) + (3 * dqk + 2 * dv))
    q_k_v = dqk + nope + dv
    per_head = (q_k_v + dv) * s * 2 + (q_k_v + dv + q_k_v) * s * 2 \
        + 4 * s * 4
    per_seq = 3 * rope * s * 2
    return {"flops": flops, "bytes": seqs * (heads * per_head + per_seq)}


# -- the reference comparison ------------------------------------------------

def leaf_kind(name):
    """A parameter's kind (its name without the layer) and its class for
    ``CHANGE_LIMITS``."""
    kind = name.split(".", 2)[2] if name.startswith("layers.") else name
    return kind, "routed" if ".experts." in name else "dense"


def off_expected_change(g, p0, m0, v0, p1, m1, v1, rate, beta1, beta2, eps):
    """Adam by hand from the gradient ``g`` and the state before the step
    (``p0``, ``m0``, ``v0``; ``rate`` = the learning rate with both bias
    corrections); for the parameter and both moments after it (``p1``,
    ``m1``, ``v1``) ||after - expected|| / ||expected - before||: 0 for the
    expected step, 1 for a state left unchanged."""
    import jax.numpy as jnp

    m = beta1 * m0 + (1 - beta1) * g
    v = beta2 * v0 + (1 - beta2) * g * g
    p = p0 - rate * m / (jnp.sqrt(v) + eps)

    def off(got, want, was):
        return jnp.linalg.norm((got - want).ravel()) / jnp.maximum(
            jnp.linalg.norm((want - was).ravel()), 1e-30)
    return jnp.stack([off(p1, p, p0), off(m1, m, m0), off(v1, v, v0)])


def reference(params, scope, main):
    """Called in set-up with the warmed-up state.  Returns the faults.

    The limits hold at the published widths on the chip.  On any other
    backend (the CPU tests' tiny sizes: 32 tokens, 2 experts held, where
    ONE assignment that bf16 activations move is a fifth of an expert's
    gradient) the comparison runs and logs for the control flow's sake, and
    only what no precision excuses is a fault: a dropped token, a parameter
    without its Adam op, a selection bias that did not follow its load."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import telemetry

    handles, batch = _state["handles"], _state["batch"]
    block = main.global_block()
    on_chip = jax.default_backend() == "tpu"
    cfg = {k: v for k, v in params.items()
           if isinstance(v, (int, float)) and not isinstance(v, bool)}

    def host(var):
        return np.asarray(scope.find_var(getattr(var, "name", var)))

    # the state before the step, on the host: the chip has no room for a
    # second copy beside the step's own 12.9 GB
    adam = {op.input("Param")[0]: op for op in block.ops
            if op.type == "adam"}
    faults = ["parameter %s has no adam op" % p.name
              for p in block.all_parameters() if p.name not in adam]
    before = {name: {slot: host(op.input(slot)[0])
                     for slot in ("Param", "Moment1", "Moment2", "Beta1Pow",
                                  "Beta2Pow", "LearningRate")}
              for name, op in adam.items()}
    biases = [host(b) for b in handles["select_biases"]]
    weights = {name: b["Param"] for name, b in before.items()}
    for i, bias in enumerate(biases, params["first_k_dense_replace"]):
        weights["select_bias.%d" % i] = bias

    # the step the window times: same program, feed signature and fetch
    # list, so the same HLO; this executor's first call reads the window's
    # executable back from the compile cache entry the warm-up wrote
    backend_s = telemetry.registry().counter("xla_compile_seconds_total")
    compiled_s = backend_s.value(phase="backend", why="dispatch")
    exe = fluid.Executor(fluid.TPUPlace() if on_chip else fluid.CPUPlace())
    loss = float(np.asarray(exe.run(
        main, feed=batch, fetch_list=[handles["loss"]], scope=scope,
        return_numpy=False)[0]).reshape(-1)[0])
    print("reference: one step of the timed program, its executable handed "
          "over in %.1f s (compile cache: %s); memory_stats %s" % (
              backend_s.value(phase="backend", why="dispatch") - compiled_s,
              jax.config.jax_compilation_cache_dir,
              jax.devices()[0].memory_stats()), flush=True)

    changes = jax.jit(off_expected_change, static_argnums=(8, 9, 10))

    def take(name, grad):
        op, was = adam[name], before.pop(name)
        rate = was["LearningRate"].reshape(()) * \
            np.sqrt(1 - was["Beta2Pow"].reshape(())) / \
            (1 - was["Beta1Pow"].reshape(()))
        return np.asarray(changes(
            grad, was["Param"], was["Moment1"], was["Moment2"],
            *(scope.find_var(op.input(slot)[0])
              for slot in ("Param", "Moment1", "Moment2")),
            np.float32(rate), float(op.attr("beta1")),
            float(op.attr("beta2")), float(op.attr("epsilon"))))

    want_loss, off, want_loads = _reference().loss_and_grads(
        weights, jnp.asarray(batch["ids"][..., 0]),
        jnp.asarray(batch["labels"][..., 0]), cfg, fetch=jax.device_put,
        take=take)
    want_loss = float(want_loss)

    limit_faults = []
    loss_err = abs(loss - want_loss) / want_loss
    print("reference: loss %.6f, float32 reference %.6f: relative "
          "difference %.2e (limit %.1e)" % (loss, want_loss, loss_err,
                                            LOSS_LIMIT), flush=True)
    if not loss_err <= LOSS_LIMIT:
        limit_faults.append("loss %.6f is %.2e from the float32 "
                            "reference's %.6f (limit %.1e)" % (
                                loss, loss_err, want_loss, LOSS_LIMIT))
    worst = {}
    for name, read in off.items():
        kind, cls = leaf_kind(name)
        worst[kind] = np.maximum(worst.get(kind, 0), read)
        if not read.max() <= CHANGE_LIMITS[cls]:
            limit_faults.append(
                "%s: parameter, first and second moment are %.3f / %.3f / "
                "%.3f of their expected change away from it (limit %.2f; "
                "1 = left unchanged)" % (name, *read, CHANGE_LIMITS[cls]))
    print("reference: %d leaves, change over the step off the expected one "
          "(parameter / moment1 / moment2, the worst layer of each kind): "
          % len(off) + "; ".join("%s %.4f / %.4f / %.4f" % (k, *r)
                                 for k, r in worst.items()), flush=True)
    tokens_k = params["batch"] * params["seq_len"] * \
        params["num_experts_per_tok"]
    first, held = params["first_expert_held"], \
        params["n_routed_experts_held"]
    gamma = np.float32(params["bias_update_speed"])
    moved = []
    for i, (load_var, bias_var, bias, want) in enumerate(zip(
            handles["expert_loads"], handles["select_biases"], biases,
            want_loads)):
        load, want = host(load_var), np.asarray(want)
        moved.append(float(np.abs(load - want).sum()) / 2 / tokens_k)
        mine = load[first:first + held]
        print("reference: expert layer %d: load of the held experts max %d "
              "/ mean %.1f rows (%.3f rows a token; an even router gives "
              "%.3f); %.4f of the assignments differ from the reference's"
              % (i, mine.max(), mine.mean(),
                 mine.sum() / (tokens_k / params["num_experts_per_tok"]),
                 expected_rows_per_token(params), moved[-1]), flush=True)
        if float(load.sum()) != tokens_k:
            faults.append("expert layer %d routed %d assignments, not %d: a "
                          "token was dropped" % (i, load.sum(), tokens_k))
        if not np.allclose(host(bias_var),
                           bias + gamma * np.sign(load.mean() - load),
                           rtol=0, atol=1e-3 * gamma):
            faults.append("expert layer %d: the selection bias did not move "
                          "by gamma * sign(mean(load) - load)" % i)
    moved = sum(moved) / len(moved)
    print("reference: %.5f of all expert layers' assignments differ from the "
          "reference's (limit %.4f)" % (moved, LOAD_LIMIT), flush=True)
    if not moved <= LOAD_LIMIT:
        limit_faults.append(
            "%.5f of the expert layers' assignments differ from the float32 "
            "reference's (limit %.4f)" % (moved, LOAD_LIMIT))
    if on_chip:
        return faults + limit_faults
    for fault in limit_faults:
        print("not held to the chip's limit here: " + fault, flush=True)
    return faults
