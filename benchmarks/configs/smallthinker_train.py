"""Training a SmallThinker-family decoder (a sliding window beside full
attention without positions, 28 query heads over 4, a router that reads the
attention's input, routed ReGLU experts, ``recompute`` spans) on next-token
prediction, and its arithmetic.  The model is the program under test,
``paddle_tpu.models.smallthinker``; the plain float32 reference it is held
to is ``smallthinker_reference.py`` beside this file (a verbatim copy of
``paddle_tpu/models/smallthinker_reference.py``).

``params`` is the configuration's JSON file (keys as in the published
``config.json``, plus ``moe_num_primary_experts_held`` /
``first_expert_held``: the experts of each layer this chip holds) merged
with the cell's traffic file (``batch``, ``seq_len``).
"""

import functools
import importlib.util
import math
import os

import numpy as np

# a program without the model cannot run this configuration: fail at once
from paddle_tpu.models import smallthinker as model

# -- the comparison that decides ``correct`` ----------------------------------
# ONE step of the timed program (the window's program, feed signature and
# fetch list, so the window's executable, ``recompute`` spans and all) on the
# pool's first batch; what it leaves in the scope — every parameter, both
# Adam moments of each, every expert load, every position's loss — against
# the float32 reference's gradient put through Adam by hand from a host copy
# of the state before the step.  The limits are readings of the chip at the
# published widths, T = 16384, on the recipe the file states (0.02 for every
# matrix, a unit embedding table, Adam at a constant 1e-6; my chip runs, PR
# 39, logs ``callI`` and ``callK``: FOUR seeds of the program, one of them
# with the three CONTROLS beside it on the same state; PERF.md section 6 has
# every reading with its log and seed, and those of the nine seeds on the
# issue's own recipe, where the same limits stood wider).  The controls are
# the same reference pass computed ALL in bfloat16 (the router too: the
# nearest precision below the configuration's), with a bfloat16 ROUTER alone,
# and with the WINDOW OFF (every layer plain causal).
#
# 1. Share of the FIRST layer's T * k assignments that went to another
#    expert than in the reference (half the L1 distance of the two loads).
#    That layer's router reads the normed embedding, which no matmul has
#    touched: float32 in the program as in the reference, so the program
#    moved NONE of its 98304 assignments on any seed of any recipe (31
#    runs).  A router that computes in bfloat16 moved 0.00166 (all bfloat16)
#    and 0.00183 (a bfloat16 router alone) here, 0.00198 to 0.00252 on the
#    issue's recipe (four seeds each): the limit is under a third of the
#    smallest control reading, 49 assignments.
FIRST_LAYER_LOAD_LIMIT = 0.0005
# 2. The same share averaged over the four layers.  The later routers see
#    a bf16 residual stream and the program moved 0.00023 to 0.00029 (a
#    layer up to 0.00048); both bfloat16 controls read 0.00185 to 0.00186
#    (every layer 0.0017 to 0.0021) and the window off 0.00627 (its last
#    layer 0.0181).  With routers that all read the token this limit too
#    refuses a precision: three times the program's largest reading, half
#    the controls' smallest.
LOAD_LIMIT = 0.0009
# 3. Every leaf's change over the step, relative: ||after - expected|| /
#    ||expected - before||, for the parameter and both moments.  A state
#    left unchanged reads exactly 1; a precision moves it little (the
#    program and the controls both multiply bf16 operands: the controls read
#    0.101 at most), so each limit is the geometric mean of the program's
#    largest reading and 1.  The program reads up to 0.058 on the experts'
#    matrices and their routers (a gate's moments) and up to 0.048 on every
#    other tensor (a post-attention norm's scale; the attention projections
#    0.013, the embedding 0.012, the head 0.004).  The window off reads
#    0.50 to 0.70 on the windowed layers' attention projections and input
#    norms and 0.33 on a gate.
CHANGE_LIMITS = {"routed": 0.24, "dense": 0.22}
# 4. Every position's loss, ||program - reference|| / ||reference||, over
#    the positions PAST the window (4096 onward: the first 4096 see the same
#    keys with and without it) and over all of them.  bf16 logits under a
#    float32 softmax read 3.49e-4 to 4.12e-4 (past the window 3.34e-4 to
#    3.83e-4), the bfloat16 controls 7.1e-4 to 8.5e-4: a precision hardly
#    moves it.  A step whose window is off reads 8.54e-3, and 9.86e-3 past
#    the window (a branch's output is 5-17% of this recipe's residual
#    stream; on the issue's recipe, where it drowns the stream, 1.9e-2 to
#    2.6e-2).  The limit is the geometric mean of the program's largest
#    reading and the window off's smallest.
TOKEN_LOSS_LIMIT = 2.0e-3
# 5. |loss - reference| / reference: the limit of the harness's accepted
#    language-model cells; the largest of four seeds read 5.80e-6 (6.15e-6
#    over the issue's recipe's nine), a hundred and seventy times under it
#    (the window off 5.05e-5: the mean hides what the positions show).
LOSS_LIMIT = 1e-3

_state = {}


@functools.lru_cache(maxsize=None)
def _reference():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "smallthinker_reference",
        os.path.join(here, "smallthinker_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "max_position_embeddings", "rms_norm_eps", "rope_theta",
              "rope_layout", "rope_scaling", "sliding_window_layout",
              "sliding_window_size", "moe_ffn_hidden_size",
              "moe_num_primary_experts", "moe_num_active_primary_experts",
              "moe_primary_router_apply_softmax", "norm_topk_prob",
              "moe_enable_early_router", "hidden_act", "tie_word_embeddings",
              "initializer_range", "embedding_initializer_range",
              "moe_num_primary_experts_held",
              "first_expert_held", "recompute")


def model_config(params):
    return model.SmallThinkerConfig(max_seq_len=params["seq_len"],
                                    **{k: params[k] for k in MODEL_KEYS})


def build(params):
    import paddle_tpu.fluid as fluid

    # ``build_train`` puts the optimizer under ``RecomputeOptimizer`` with
    # each layer's input as a checkpoint (``recompute`` in the file)
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
    """Untrained model, uniform labels.  The final RMS norm hands the
    untied head rows of mean square 1, and the head's weights are N(0,
    r^2), so the logits are N(0, hidden * r^2) and E[logsumexp] = ln V +
    hidden * r^2 / 2 for a large vocabulary; the label's own logit has
    mean 0."""
    return math.log(params["vocab_size"]) + \
        params["hidden_size"] * params["initializer_range"] ** 2 / 2


def expects_in_hlo(params):
    # the flash kernels, compiled by Mosaic (causal grouped-query attention
    # at a length that tiles, with and without the window)
    return ["tpu_custom_call"]


def expected_rows_per_token(params):
    """Rows the held experts compute per token, in expectation under an
    even router: top-k assignments, each held with probability held / E.
    (The run's own count is logged by ``reference`` from ``ExpertLoad``.)"""
    return params["moe_num_active_primary_experts"] * \
        params["moe_num_primary_experts_held"] / \
        params["moe_num_primary_experts"]


def attended_pairs(seq_len, window=0):
    """Query-key pairs of one head of one sequence under the causal mask,
    and under a sliding ``window`` (a query sees its last ``window`` keys,
    itself among them): 134,225,920 and 58,722,304 at 16384 with 4096."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def _layer_pairs(params):
    """The pairs of each layer's attention, by ``sliding_window_layout``."""
    return [attended_pairs(params["seq_len"],
                           params["sliding_window_size"] if windowed else 0)
            for windowed in params["sliding_window_layout"]]


def forward_macs(params):
    """Multiply-accumulates of one sequence's forward pass, from shapes.
    Per token and layer: attention's four projections (Q and the output H *
    n * d each, K and V H * n_kv * d each), the router (H * E) and the
    routed experts at the EXPECTED rows a token (``expected_rows_per_token``:
    0.75 for 6 of 64 with 8 held) times 3 * H * I; per layer the scores and
    context over the pairs its mask leaves (``attended_pairs`` * 2 * d *
    heads).  Once: the head H * V.  Embedding look-ups, norms, rotary,
    softmax, relu, the sort and the gathers are left out, and so is the
    replayed forward of the ``recompute`` spans: time, not needed work."""
    h, s = params["hidden_size"], params["seq_len"]
    n, n_kv = params["num_attention_heads"], params["num_key_value_heads"]
    d = params["head_dim"]
    layers = params["num_hidden_layers"]
    projections = 2 * h * n * d + 2 * h * n_kv * d
    experts = h * params["moe_num_primary_experts"] + \
        expected_rows_per_token(params) * 3 * h * params["moe_ffn_hidden_size"]
    per_token = layers * (projections + experts) + h * params["vocab_size"]
    return s * per_token + sum(_layer_pairs(params)) * 2 * d * n


def flops_per_sample(params):
    """Training FLOPs of one sequence: 2 per multiply-accumulate, backward
    = twice the forward.  The flash backward's recomputation of the scores
    and the spans' replayed forward are not counted."""
    return 3 * 2 * forward_macs(params)


def kernel_costs(params):
    """What the flash-attention kernels of ONE training step need, from
    shapes: all four layers, and under ``"window"`` the windowed layers
    alone.  Per (sequence, layer), S = seq_len, D the head size, H query
    heads over H_kv key/value heads, over the pairs the layer's mask leaves
    (``attended_pairs``: the causal half, or the band):

    FLOPs, per QUERY head: forward QK^T and PV, 2 * pairs * 2D; backward
    the five products of the algorithm (scores again, dP, dV, dQ, dK) =
    2 * pairs * 5D: 14 * D * pairs.  The repo's backward is two kernels
    that each form the scores and dP, and the ``recompute`` span runs the
    forward kernel a second time; the needed work is counted once.

    Bytes (bf16 = 2): per QUERY head the forward reads Q and writes O, the
    backward reads Q, dO and O (delta = rowsum(dO * O)) and writes dQ, and
    the float32 log-sum-exp / delta rows are written once and read once
    each (4 * S * 4); per KEY/VALUE head K and V are read once a pass and
    dK and dV written once (6 * S * D * 2).  A window does not shrink them:
    every row of every operand is still read."""
    s, d = params["seq_len"], params["head_dim"]
    heads, kv_heads = params["num_attention_heads"], \
        params["num_key_value_heads"]
    per_layer_bytes = params["batch"] * (
        heads * (6 * s * d * 2 + 4 * s * 4) + kv_heads * 6 * s * d * 2)

    def costs(pairs):
        return {"flops": params["batch"] * heads * 14 * d * sum(pairs),
                "bytes": per_layer_bytes * len(pairs)}
    pairs = _layer_pairs(params)
    windowed = [p for p, w in zip(pairs, params["sliding_window_layout"])
                if w]
    return dict(costs(pairs), window=costs(windowed))


# -- the reference comparison ------------------------------------------------

# name -> keywords of the reference's ``loss_and_grads``: the first is held
# to the limits, every further one is a CONTROL that at least one limit has
# to refuse
PASSES = {
    "float32": {},
    "bfloat16": {"dtype": "bfloat16"},
    "bfloat16_router": {"router_dtype": "bfloat16"},
    "window_off": {"window": False},
}


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
    ``token_loss_err``, ``late_loss_err``, ``moved`` and ``moved_first``:
    all layers' and the first layer's, ``off``: {leaf: three numbers})
    against the limits above, each named."""
    faults = []
    if not readings["loss_err"] <= LOSS_LIMIT:
        faults.append("loss is %.2e from the float32 reference's (limit "
                      "%.1e)" % (readings["loss_err"], LOSS_LIMIT))
    for key, which in (("token_loss_err", "the positions'"),
                       ("late_loss_err", "the positions past the window's")):
        if not readings[key] <= TOKEN_LOSS_LIMIT:
            faults.append("%s losses are %.2e of their norm from the float32 "
                          "reference's (limit %.1e)" % (
                              which, readings[key], TOKEN_LOSS_LIMIT))
    if not readings["moved_first"] <= FIRST_LAYER_LOAD_LIMIT:
        faults.append(
            "%.5f of the first expert layer's assignments differ from the "
            "float32 reference's (limit %.4f): its router reads float32"
            % (readings["moved_first"], FIRST_LAYER_LOAD_LIMIT))
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


def reference(params, scope, main, passes=("float32",)):
    """Called in set-up with the warmed-up state.  Returns the faults.

    The limits hold at the published widths on the chip.  On any other
    backend (the CPU tests' tiny sizes: 64 tokens, 2 experts held, where
    ONE assignment that bf16 activations move is a fifth of an expert's
    gradient) the comparison runs and logs for the control flow's sake, and
    only what no precision excuses is a fault: a dropped token, a parameter
    without its Adam op.

    ``passes`` (names of ``PASSES``): the first is held to the limits,
    every further one is a CONTROL whose readings are logged beside the
    limits that refuse it (the builder's own runs; the harness calls with
    the default)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import telemetry

    handles, batch = _state["handles"], _state["batch"]
    block = main.global_block()
    on_chip = jax.default_backend() == "tpu"
    cfg = {k: params[k] for k in MODEL_KEYS}

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
    weights = {name: b["Param"] for name, b in before.items()}

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

    def rel(got, want):
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    tokens = params["batch"] * params["seq_len"]
    tokens_k = tokens * params["moe_num_active_primary_experts"]
    late = min(params["sliding_window_size"], params["seq_len"] // 2)
    loads = [host(v) for v in handles["expert_loads"]]
    limit_faults = []
    for name in passes:
        keywords = {k: (jnp.dtype(v) if k.endswith("dtype") else v)
                    for k, v in PASSES[name].items()}
        want_loss, want_tokens, off, want_loads = _reference().loss_and_grads(
            weights, jnp.asarray(batch["ids"][..., 0]),
            jnp.asarray(batch["labels"][..., 0]), cfg, fetch=jax.device_put,
            take=take, **keywords)
        want_loss, want_tokens = float(want_loss), np.asarray(
            want_tokens, np.float32)
        moved = [moved_share(load, want, tokens_k)
                 for load, want in zip(loads, want_loads)]
        readings = {
            "loss_err": abs(loss - want_loss) / want_loss,
            "token_loss_err": rel(token_loss, want_tokens),
            "late_loss_err": rel(token_loss[:, late:], want_tokens[:, late:]),
            "moved": sum(moved) / len(moved), "moved_first": moved[0],
            "off": off}
        worst = {}
        for leaf, read in off.items():
            kind = leaf_kind(leaf)[0]
            worst[kind] = np.maximum(worst.get(kind, 0), read)
        print("reference (%s): loss %.6f against %.6f: relative difference "
              "%.2e (limit %.1e); the positions' losses %.3e of their norm "
              "apart, those past position %d %.3e (limit %.1e); %.5f of the "
              "assignments differ (limit %.4f; by layer %s, the first held to "
              "%.4f)" % (
                  name, loss, want_loss, readings["loss_err"], LOSS_LIMIT,
                  readings["token_loss_err"], late,
                  readings["late_loss_err"], TOKEN_LOSS_LIMIT,
                  readings["moved"], LOAD_LIMIT,
                  " ".join("%.5f" % m for m in moved),
                  FIRST_LAYER_LOAD_LIMIT), flush=True)
        print("reference (%s): %d leaves, change over the step off the "
              "expected one (parameter / moment1 / moment2, the worst layer "
              "of each kind): " % (name, len(off)) + "; ".join(
                  "%s %.4f / %.4f / %.4f" % (k, *r)
                  for k, r in worst.items()), flush=True)
        # the routers by layer, to read beside the rows their layers' held
        # experts got (below)
        print("reference (%s): the routers by layer: " % name + "; ".join(
            "%.4f / %.4f / %.4f" % tuple(r) for leaf, r in sorted(off.items())
            if leaf.endswith(".experts.router")), flush=True)
        found = held_to_limits(readings)
        if name == passes[0]:
            limit_faults = found
        else:
            print("control (%s): %d limit(s) refuse it: %s" % (
                name, len(found), "; ".join(found) or "NONE"), flush=True)

    first, held = params["first_expert_held"], \
        params["moe_num_primary_experts_held"]
    for i, load in enumerate(loads):
        mine = load[first:first + held]
        print("reference: layer %d: load of the held experts max %d / mean "
              "%.1f rows, %d rows in all (%.3f rows a token; an even router "
              "gives %.3f)" % (i, mine.max(), mine.mean(), mine.sum(),
                               mine.sum() / tokens,
                               expected_rows_per_token(params)), flush=True)
        if float(load.sum()) != tokens_k:
            faults.append("layer %d routed %d assignments, not %d: a token "
                          "was dropped" % (i, load.sum(), tokens_k))
    if on_chip:
        return faults + limit_faults
    for fault in limit_faults:
        print("not held to the chip's limit here: " + fault, flush=True)
    return faults
