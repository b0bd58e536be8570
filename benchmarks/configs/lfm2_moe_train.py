"""Training an ``lfm2_moe``-family decoder (gated short convolutions and
grouped-query attention by position, routed SwiGLU experts, a tied head) on
next-token prediction, and its arithmetic.  The model is the program under
test, ``paddle_tpu.models.lfm2_moe``; the plain float32 reference it is held
to is ``lfm2_moe_reference.py`` beside this file (a verbatim copy of
``paddle_tpu/models/lfm2_moe_reference.py``).

``params`` is the configuration's JSON file (keys as in the published
``config.json``, plus ``num_experts_held`` / ``first_expert_held``: the
experts of each layer this chip holds) merged with the cell's traffic file
(``batch``, ``seq_len``).
"""

import functools
import importlib.util
import math
import os

import numpy as np

# a program without the model cannot run this configuration: fail at once
from paddle_tpu.models import lfm2_moe as model

# -- the comparison that decides ``correct`` ----------------------------------
# ONE step of the timed program (the window's program, feed signature and
# fetch list, so the window's executable) on the pool's first batch; what it
# leaves in the scope — every parameter, both Adam moments of each, every
# selection bias and expert load, every position's loss — against the
# float32 reference's gradient put through Adam by hand from a host copy of
# the state before the step.  The limits are readings of the chip at the
# published widths, T = 8192 (my chip runs, PR 34: four seeds with the
# CONTROL beside the program on one state, and one run with a FAULT planted
# in the reference, each on a state of its own; PERF.md section 6 has them
# and the final tree's seeds).  The control is the same reference pass
# computed ALL in bfloat16, the router too: the nearest precision below the
# configuration's.  The planted faults are the two of
# benchmarks/tests/test_lfm2_config.py, read here at the cell's own size
# with the fault on the reference's side: key/value heads paired h % H_kv,
# and the convolution's taps reversed.
#
# 1. Share of the expert layers' T * k assignments that went to another
#    expert than in the reference (half the L1 distance of the two loads,
#    averaged over the four layers; one layer alone reads 0.00150 to
#    0.00201).  The program's float32 router sees bf16 activations and
#    moved 0.00156 / 0.00165 / 0.00175 / 0.00190 of them; the control moved
#    0.00499 / 0.00549 / 0.00510 / 0.00508 (the planted faults 0.043 and
#    0.020).  This is the limit that refuses a lower precision: the
#    geometric mean of the two readings next to it.
LOAD_LIMIT = 0.0031
# 2. Every leaf's change over the step, relative: ||after - expected|| /
#    ||expected - before||, for the parameter and both moments.  A state
#    left unchanged reads exactly 1; a precision moves it little (the
#    program and the control both multiply bf16 operands: the control reads
#    1.3 times the program), so each limit lies between the program's
#    largest reading and 1, with the more room, as a ratio, above the
#    reading.  The routed experts and their router carry the moved
#    assignments and read up to 0.095 / 0.159 / 0.164 (parameter / first /
#    second moment; the control 0.122 / 0.211 / 0.217); every other tensor
#    carries bf16 rounding alone and reads up to 0.024 / 0.064 / 0.066 (the
#    attention and convolution projections, the embedding; the control
#    0.030 / 0.084 / 0.085).  A planted fault reads, on its layer's own
#    leaves at T = 8192: key/value heads paired h % H_kv 0.48 to 0.61 (the
#    four attention projections' parameters) and 0.93 to 1.29 (their
#    moments); the taps reversed 0.82 / 1.94 / 3.05 on the taps and 0.46 /
#    1.31 / 1.11 on the convolution's projections (0.9 to 1.5 at the tiny
#    size of benchmarks/tests/test_lfm2_config.py).
CHANGE_LIMITS = {"routed": 0.45, "dense": 0.25}
# 3. Every position's loss, ||program - reference|| / ||reference|| over
#    the T positions.  The precision hardly moves it: after the warm-up's
#    six steps on a pool of four batches the loss is 5.7 and falling, the
#    logits are sharp, and bf16 logits under a float32 softmax read 7.3e-3
#    to 8.4e-3 where the control reads 9.5e-3 to 1.09e-2, 1.2 to 1.3 times
#    as much (as in the looped decoder's cell): it does not refuse the
#    control.  It refuses a wrong model: the wrong pairing of ONE attention
#    layer's key/value heads reads 5.43e-2, the reversed taps 5.28e-1.  The
#    limit is the geometric mean of the program's largest reading and the
#    smaller fault's, 2.5 times from each.
TOKEN_LOSS_LIMIT = 2.1e-2
# 4. |loss - reference| / reference.  The precision hardly moves the mean
#    (1.4e-6 to 3.1e-4 over fourteen runs; the control's 1.4e-4 to 3.7e-4
#    is signed noise a little wider), so it gets the limit the harness's
#    accepted language-model cells have, which leaves the largest reading
#    three times of room and lies twenty times under the smaller planted
#    fault's 1.99e-2 (the reversed taps read 5.13e-1); the harness holds
#    the first loss to 3% of its analytic value besides.
LOSS_LIMIT = 1e-3

_state = {}


@functools.lru_cache(maxsize=None)
def _reference():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "lfm2_moe_reference", os.path.join(here, "lfm2_moe_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers", "layer_types",
              "num_dense_layers", "num_attention_heads",
              "num_key_value_heads", "conv_L_cache", "conv_bias", "norm_eps",
              "rope_theta", "num_experts", "num_experts_per_tok",
              "norm_topk_prob", "use_expert_bias", "routed_scaling_factor",
              "tie_embedding", "initializer_range", "num_experts_held",
              "first_expert_held", "bias_update_speed")


def model_config(params):
    return model.Lfm2MoeConfig(max_seq_len=params["seq_len"],
                               **{k: params[k] for k in MODEL_KEYS})


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
    2 for a large vocabulary; the label's own logit has mean 0.

    The head is TIED to the embedding, so one logit of a position is not
    independent of its input: the input token's own row.  It enters the
    log-sum-exp as one term in V, and its mean is ||E_x||^2 / rms(h) (about
    1 at the published widths, where the first feed-forward's output, of
    variance 0.7, dominates the residual stream over the embedding's
    0.0004): e^1 among 16384 terms moves the loss by 1e-4.  The label is the
    NEXT token, drawn independently, so no label's logit is lifted."""
    return math.log(params["vocab_size"]) + \
        params["hidden_size"] * params["initializer_range"] ** 2 / 2


def expects_in_hlo(params):
    # the flash kernels, compiled by Mosaic (causal grouped-query attention
    # at a length that tiles; no attention dropout in this family)
    return ["tpu_custom_call"]


def expected_rows_per_token(params):
    """Rows the held experts compute per token, in expectation under an
    even router: top-k assignments, each held with probability held / E.
    (The run's own count is logged by ``reference`` from ``ExpertLoad``.)"""
    return params["num_experts_per_tok"] * params["num_experts_held"] \
        / params["num_experts"]


def _layer_counts(params):
    """``(convolution layers, attention layers, dense layers, expert
    layers)``."""
    kinds = params["layer_types"]
    n_conv = sum(1 for k in kinds if k == "conv")
    n_dense = params["num_dense_layers"]
    return n_conv, len(kinds) - n_conv, n_dense, len(kinds) - n_dense


def forward_macs(params):
    """Multiply-accumulates of one sequence's forward pass, from shapes.
    Per token: a convolution mixer's two projections (H * 3H + H * H; its
    L multiply-adds a channel are not matmul work); an attention mixer's
    four projections (Q and the output H * H each, K and V H * n_kv * d
    each) and the scores and context over the CAUSAL half of the S x S
    square (S * 2 * d * heads / 2); the dense SwiGLU (3 * H * F), or the
    router (H * E) and the routed experts at the EXPECTED rows a token
    (``expected_rows_per_token``: 1 for 4 of 32 with 8 held) times 3 * H *
    I.  Once: the tied head H * V.  Embedding look-ups, norms, rotary,
    softmax, silu, the sort and the gathers are left out."""
    h, s = params["hidden_size"], params["seq_len"]
    n, n_kv = params["num_attention_heads"], params["num_key_value_heads"]
    d = h // n
    n_conv, n_attn, n_dense, n_moe = _layer_counts(params)
    conv = h * 3 * h + h * h
    attn = 2 * h * n * d + 2 * h * n_kv * d + s * 2 * d * n // 2
    dense = 3 * h * params["intermediate_size"]
    experts = h * params["num_experts"] + expected_rows_per_token(params) \
        * 3 * h * params["moe_intermediate_size"]
    per_token = n_conv * conv + n_attn * attn + n_dense * dense \
        + n_moe * experts + h * params["vocab_size"]
    return s * per_token


def flops_per_sample(params):
    """Training FLOPs of one sequence: 2 per multiply-accumulate, backward
    = twice the forward.  The flash backward's recomputation of the scores
    is not counted."""
    return 3 * 2 * forward_macs(params)


def kernel_costs(params):
    """What the flash-attention kernels of ONE training step need, from
    shapes.  Per (sequence, attention layer), S = seq_len, D the head
    size, H query heads over H_kv key/value heads, over the CAUSAL half of
    the square:

    FLOPs, per QUERY head: forward QK^T and PV, 2 * (S*S/2) * 2D; backward
    the five products of the algorithm (scores again, dP, dV, dQ, dK) =
    2 * (S*S/2) * 5D.  The repo's backward is two kernels that each form
    the scores and dP; the needed five are counted.

    Bytes (bf16 = 2): per QUERY head the forward reads Q and writes O, the
    backward reads Q, dO and O (delta = rowsum(dO * O)) and writes dQ, and
    the float32 log-sum-exp / delta rows are written once and read once
    each (4 * S * 4); per KEY/VALUE head K and V are read once a pass and
    dK and dV written once (6 * S * D * 2).  A lowering that repeats K and
    V to the query heads, or writes a part of dK / dV a query head, moves
    more than this and reads a lower share."""
    s, h = params["seq_len"], params["hidden_size"]
    heads, kv_heads = params["num_attention_heads"], \
        params["num_key_value_heads"]
    d = h // heads
    seqs = params["batch"] * _layer_counts(params)[1]
    flops = seqs * heads * s * s * (2 * d + 5 * d)
    per_head = 6 * s * d * 2 + 4 * s * 4
    per_kv_head = 6 * s * d * 2
    return {"flops": flops,
            "bytes": seqs * (heads * per_head + kv_heads * per_kv_head)}


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


def moved_share(load, want, assignments):
    """Share of ``assignments`` that went to another expert than in the
    reference: half the L1 distance of the two loads."""
    return float(np.abs(np.asarray(load) - np.asarray(want)).sum()) / 2 \
        / assignments


def held_to_limits(readings):
    """The faults of one comparison's ``readings`` (``loss_err``,
    ``token_loss_err``, ``moved``, ``off``: {leaf: three numbers}) against
    the limits above, each named."""
    faults = []
    if not readings["loss_err"] <= LOSS_LIMIT:
        faults.append("loss is %.2e from the float32 reference's (limit "
                      "%.1e)" % (readings["loss_err"], LOSS_LIMIT))
    if not readings["token_loss_err"] <= TOKEN_LOSS_LIMIT:
        faults.append("the positions' losses are %.2e of their norm from "
                      "the float32 reference's (limit %.1e)" % (
                          readings["token_loss_err"], TOKEN_LOSS_LIMIT))
    if not readings["moved"] <= LOAD_LIMIT:
        faults.append(
            "%.5f of the expert layers' assignments differ from the float32 "
            "reference's (limit %.4f)" % (readings["moved"], LOAD_LIMIT))
    for name, read in readings["off"].items():
        limit = CHANGE_LIMITS[leaf_kind(name)[1]]
        if not max(read) <= limit:
            faults.append(
                "%s: parameter, first and second moment are %.3f / %.3f / "
                "%.3f of their expected change away from it (limit %.2f; "
                "1 = left unchanged)" % (name, *read, limit))
    return faults


def reference(params, scope, main, dtypes=("float32",)):
    """Called in set-up with the warmed-up state.  Returns the faults.

    The limits hold at the published widths on the chip.  On any other
    backend (the CPU tests' tiny sizes: 32 tokens, 2 experts held, where
    ONE assignment that bf16 activations move is a fifth of an expert's
    gradient) the comparison runs and logs for the control flow's sake, and
    only what no precision excuses is a fault: a dropped token, a parameter
    without its Adam op, a selection bias that did not follow its load.

    ``dtypes``: the precisions the reference pass is computed in; the first
    is held to the limits, every further one is a CONTROL whose readings
    are logged beside the limits they would break (the builder's own runs;
    the harness calls with the default)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import telemetry

    handles, batch = _state["handles"], _state["batch"]
    block = main.global_block()
    on_chip = jax.default_backend() == "tpu"
    cfg = {k: v for k, v in params.items()
           if isinstance(v, (int, float)) and not isinstance(v, bool)}
    cfg["layer_types"] = params["layer_types"]

    def host(var):
        return np.asarray(scope.find_var(getattr(var, "name", var)))

    # the state before the step, on the host: the chip has no room for a
    # second copy beside the step's own
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
    for i, bias in enumerate(biases, params["num_dense_layers"]):
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
    token_loss = host(handles["token_loss"])[..., 0]
    print("reference: one step of the timed program, its executable handed "
          "over in %.1f s (compile cache: %s); memory_stats %s" % (
              backend_s.value(phase="backend", why="dispatch") - compiled_s,
              jax.config.jax_compilation_cache_dir,
              jax.devices()[0].memory_stats()), flush=True)

    changes = jax.jit(off_expected_change, static_argnums=(8, 9, 10))

    def take(name, grad):
        op, was = adam[name], before[name]
        rate = was["LearningRate"].reshape(()) * \
            np.sqrt(1 - was["Beta2Pow"].reshape(())) / \
            (1 - was["Beta1Pow"].reshape(()))
        return np.asarray(changes(
            grad, was["Param"], was["Moment1"], was["Moment2"],
            *(scope.find_var(op.input(slot)[0])
              for slot in ("Param", "Moment1", "Moment2")),
            np.float32(rate), float(op.attr("beta1")),
            float(op.attr("beta2")), float(op.attr("epsilon"))))

    tokens_k = params["batch"] * params["seq_len"] * \
        params["num_experts_per_tok"]
    loads = [host(v) for v in handles["expert_loads"]]
    limit_faults = []
    for dtype in dtypes:
        want_loss, want_tokens, off, want_loads = _reference().loss_and_grads(
            weights, jnp.asarray(batch["ids"][..., 0]),
            jnp.asarray(batch["labels"][..., 0]), cfg, dtype=jnp.dtype(dtype),
            fetch=jax.device_put, take=take)
        want_loss, want_tokens = float(want_loss), np.asarray(
            want_tokens, np.float32)
        moved = [moved_share(load, want, tokens_k)
                 for load, want in zip(loads, want_loads)]
        readings = {
            "loss_err": abs(loss - want_loss) / want_loss,
            "token_loss_err": float(np.linalg.norm(token_loss - want_tokens)
                                    / np.linalg.norm(want_tokens)),
            "moved": sum(moved) / len(moved), "off": off}
        worst = {}
        for name, read in off.items():
            kind = leaf_kind(name)[0]
            worst[kind] = np.maximum(worst.get(kind, 0), read)
        print("reference (%s): loss %.6f against %.6f: relative difference "
              "%.2e (limit %.1e); the positions' losses %.3e of their norm "
              "apart (limit %.1e); %.5f of the assignments differ (limit "
              "%.4f; by layer %s)" % (
                  dtype, loss, want_loss, readings["loss_err"], LOSS_LIMIT,
                  readings["token_loss_err"], TOKEN_LOSS_LIMIT,
                  readings["moved"], LOAD_LIMIT,
                  " ".join("%.5f" % m for m in moved)), flush=True)
        print("reference (%s): %d leaves, change over the step off the "
              "expected one (parameter / moment1 / moment2, the worst layer "
              "of each kind): " % (dtype, len(off)) + "; ".join(
                  "%s %.4f / %.4f / %.4f" % (k, *r)
                  for k, r in worst.items()), flush=True)
        found = held_to_limits(readings)
        if dtype == dtypes[0]:
            limit_faults = found
        else:
            print("control (%s): %d limit(s) refuse it: %s" % (
                dtype, len(found), "; ".join(found) or "NONE"), flush=True)

    first, held = params["first_expert_held"], params["num_experts_held"]
    gamma = np.float32(params["bias_update_speed"])
    for i, (load, bias_var, bias) in enumerate(zip(
            loads, handles["select_biases"], biases)):
        mine = load[first:first + held]
        print("reference: expert layer %d: load of the held experts max %d "
              "/ mean %.1f rows (%.3f rows a token; an even router gives "
              "%.3f)" % (i, mine.max(), mine.mean(),
                         mine.sum() / (tokens_k /
                                       params["num_experts_per_tok"]),
                         expected_rows_per_token(params)), flush=True)
        if float(load.sum()) != tokens_k:
            faults.append("expert layer %d routed %d assignments, not %d: a "
                          "token was dropped" % (i, load.sum(), tokens_k))
        if not np.allclose(host(bias_var),
                           bias + gamma * np.sign(load.mean() - load),
                           rtol=0, atol=1e-3 * gamma):
            faults.append("expert layer %d: the selection bias did not move "
                          "by gamma * sign(mean(load) - load)" % i)
    if on_chip:
        return faults + limit_faults
    for fault in limit_faults:
        print("not held to the chip's limit here: " + fault, flush=True)
    return faults
