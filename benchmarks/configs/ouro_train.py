"""Training a looped ``ouro``-family decoder (one stack of sandwich-norm
layers applied ``total_ut_steps`` times with tied weights, an exit head a
pass, the exit-weighted loss) on next-token prediction, and its arithmetic.
The model is the program under test, ``paddle_tpu.models.ouro``; the plain
float32 reference it is held to is ``ouro_reference.py`` beside this file (a
verbatim copy of ``paddle_tpu/models/ouro_reference.py``).

``params`` is the configuration's JSON file (keys as in the published
``config.json``) merged with the cell's traffic file (``batch``,
``seq_len``).
"""

import functools
import importlib.util
import math
import os

import numpy as np

# a program without the model cannot run this configuration: fail at once
from paddle_tpu.models import ouro as model

# -- the comparison that decides ``correct`` ----------------------------------
# ONE step of the timed program (the window's program, feed signature and
# fetch list, so the window's executable) on the pool's first batch, and
# nothing else of the program: what that step leaves in the scope (every
# parameter and both Adam moments of each, and the passes' per-token
# cross-entropies and exit distribution, which the model declares
# persistable) against the float32 reference on a host copy of the
# state before the step.  Every limit is a reading of the chip at the
# published widths (PERF.md section 6, PR 32).
#
# The state compared is the warmed-up one with THE EXIT GATE SET TO SPREAD
# THE EXITS (``spread_gate``: small weights, bias ln(1/2)): six warm-up
# steps collapse the exit distribution onto one pass on most seeds (the
# stated objective's optimum once the passes' losses differ by more than
# ``entropy_beta``), and a pass without weight has no gradient to compare.
# With it the exits weigh 0.33 / 0.22 / 0.15 / 0.30 on every seed, and the
# window trains the gate again from there.
#
# 1. THE LIMIT THAT REFUSES A LOWER PRECISION.  A token's cross-entropy, not
#    the mean: the rms over a pass's tokens of (ce - float32 reference) over
#    the reference's mean, averaged over the passes (``token_ce_error``), of
#    the ``ce`` the timed step itself left.  The pure-bf16 program keeps the
#    residual stream, the norms' outputs and the softmax float32; the
#    reference computed ALL in bfloat16 (the nearest precision below the
#    configuration's: its stream and its norms' outputs are bf16 too) reads
#    about 1.4 times as much.  Both follow the state (how far six warm-up
#    steps moved the weights), so their ranges over seeds nearly touch
#    (4.2e-4 to 5.8e-4 against 5.9e-4 to 7.6e-4), but on ONE state their
#    ratio is steady, so the comparison runs the all-bfloat16 forward too (a
#    second stream through the float32 reference's own pass over the
#    weights) and holds the program's error to ``PRECISION_LIMIT`` of it; an
#    all-bfloat16 run reads 1.
PRECISION_LIMIT = 0.85
# 2. Every leaf's change over the step, relative: ||after - expected|| /
#    ||expected - before||, for the parameter and both moments (0 = the
#    expected step, 1 = a state left unchanged).  The precision hardly
#    moves it (the all-bfloat16 reference's gradient through Adam reads 1.1
#    to 1.4 times the program's, by kind of leaf: no number of the backward
#    separates the two, PERF.md section 6), so this limit refuses a wrong
#    gradient, not a low precision.  It lies between the program's largest
#    reading (0.0164, q / k) and what a tied leaf reads whose gradient lost
#    ONE pass's contribution: every run logs that (``lost``: per pass the
#    largest ||g_t|| / ||g - m0|| over the layers' tied leaves, the first
#    moment's reading had the backward skipped that pass; 0.25 at least).
#    The gate's weight is a sum of signed terms that mostly cancel and has
#    its own class.
CHANGE_LIMITS = {"layers": 0.06, "gate": 0.2}
#    The gate's bias is ONE number: its expected change 0.1 (g - m0), or
#    0.001 (g^2 - v0), is near zero whenever the gradient happens to equal
#    the moment (0.375 read once for the second moment with the gradient 2%
#    off), so a leaf of one element is held by its parameter's change alone,
#    which Adam's normalised step keeps away from zero.
# 3. |loss - reference| / reference (the harness's accepted limit; without
#    its entropy term the loss reads 6e-3), each pass's mean cross-entropy
#    likewise, and the token-mean exit distribution (built with the last
#    pass's gate read too it is 0.19 off and more).
LOSS_LIMIT = 1e-3
EXIT_LIMIT = 5e-3

_state = {}


@functools.lru_cache(maxsize=None)
def _reference():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "ouro_reference", os.path.join(here, "ouro_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def model_config(params):
    if params["hidden_act"] != "silu" or params["tie_word_embeddings"] \
            or params["use_sliding_window"] or params["rope_scaling"] \
            or params["num_key_value_heads"] != params["num_attention_heads"]:
        raise ValueError("models.ouro builds silu SwiGLU, an untied head, "
                         "full attention without a window, plain rotary "
                         "embedding and as many KV heads as heads")
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "total_ut_steps", "rope_theta",
            "rms_norm_eps", "initializer_range", "entropy_beta")
    return model.OuroConfig(max_seq_len=params["seq_len"],
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
    """One host batch: ids uniform over the vocabulary, full sequences; the
    labels are the ids shifted by one (one more id is drawn for the last
    position)."""
    ids = rng.integers(0, params["vocab_size"],
                       (params["batch"], params["seq_len"] + 1),
                       dtype=np.int64)
    batch = {"ids": np.ascontiguousarray(ids[:, :-1, None]),
             "labels": np.ascontiguousarray(ids[:, 1:, None])}
    _state.setdefault("batch", batch)      # the pool's first: ``reference``
    return batch


def untrained_exit_entropy(params):
    """H(p) in nats of the exit distribution of a gate that says 1/2 at
    every pass: p = 1/2, 1/4, ..., the last pass taking what is left."""
    T = params["total_ut_steps"]
    p = [0.5 ** (t + 1) for t in range(T - 1)] + [0.5 ** (T - 1)]
    return -sum(q * math.log(q) for q in p)


def first_loss(params):
    """Untrained model, uniform labels.  The final RMS norm hands the head
    rows of mean square 1, and the head's weights are N(0, r^2), so each
    pass's logits are N(0, hidden * r^2) and E[logsumexp] = ln V + hidden *
    r^2 / 2 for a large vocabulary; the label's own logit has mean 0, and
    the exit distribution weighs four such losses with weights that sum to
    1.  Less ``beta`` times the entropy of the untrained gate's
    distribution (gate bias 0: lambda = 1/2 in the mean; its logits' own
    spread, variance hidden * r^2 too, lowers H by a few percent of 0.06)."""
    return math.log(params["vocab_size"]) + \
        params["hidden_size"] * params["initializer_range"] ** 2 / 2 - \
        params["entropy_beta"] * untrained_exit_entropy(params)


def expects_in_hlo(params):
    # the flash kernels, compiled by Mosaic (causal self-attention at a
    # length that tiles; no attention dropout in this family)
    return ["tpu_custom_call"]


def forward_macs(params):
    """Multiply-accumulates of one sequence's forward pass, from shapes.
    Per token and layer APPLICATION (``total_ut_steps`` x
    ``num_hidden_layers`` of them): the four attention projections, the
    scores and context over the CAUSAL half of the S x S square (S * 2 *
    head_dim * heads / 2) and the SwiGLU (3 * H * F).  Per pass: the head, H
    * V.  Embedding look-ups, norms, rotary, softmax, silu and the gate's
    2048 products a pass are not matmul work and are left out."""
    h, s = params["hidden_size"], params["seq_len"]
    n, d = params["num_attention_heads"], params["head_dim"]
    layer = 4 * h * n * d + s * 2 * d * n // 2 \
        + 3 * h * params["intermediate_size"]
    passes = params["total_ut_steps"]
    per_token = passes * (params["num_hidden_layers"] * layer
                          + h * params["vocab_size"])
    return s * per_token


def flops_per_sample(params):
    """Training FLOPs of one sequence: 2 per multiply-accumulate, backward
    = twice the forward.  Neither the rematerialised forward of the loop's
    backward nor the flash backward's recomputation of the scores is
    counted: model FLOPs, not hardware FLOPs."""
    return 3 * 2 * forward_macs(params)


def kernel_costs(params):
    """What the flash-attention kernels of ONE training step NEED, from
    shapes.  Per (sequence, head, layer application), S = seq_len, D =
    head_dim, over the CAUSAL half of the square:

    FLOPs: forward QK^T and PV, 2 * (S*S/2) * 2D; backward the five
    products of the algorithm (scores again, dP, dV, dQ, dK), 2 * (S*S/2) *
    5D.  The repo's backward is two kernels that each form the scores and
    dP, and the loop's backward runs the forward kernel a second time
    (rematerialisation): the needed seven products are counted, so both
    lower the share, as they should.

    Bytes (bf16 = 2): the forward reads Q, K, V and writes O; the backward
    reads Q, K, V and dO and writes dQ, dK, dV; the float32 log-sum-exp /
    delta rows are written once and read once each (4 * S * 4)."""
    s, heads, d = params["seq_len"], params["num_attention_heads"], \
        params["head_dim"]
    calls = params["batch"] * heads * params["num_hidden_layers"] \
        * params["total_ut_steps"]
    return {"flops": calls * s * s * 7 * d,
            "bytes": calls * ((4 + 7) * s * d * 2 + 4 * s * 4)}


# -- the reference comparison ------------------------------------------------

def leaf_kind(name):
    """A parameter's kind (its name without the layer) and its class for
    ``CHANGE_LIMITS``: the exit gate's two leaves apart from every other
    (layers, embedding, head, final norm)."""
    kind = name.split(".", 2)[2] if name.startswith("layers.") else name
    return kind, "gate" if kind.startswith("early_exit_gate") else "layers"


def off_expected_change(g, p0, m0, v0, p1, m1, v1, rate, beta1, beta2, eps):
    """Adam by hand from the gradient ``g`` and the state before the step
    (``p0``, ``m0``, ``v0``; ``rate`` = the learning rate with both bias
    corrections); for the parameter and both moments after it (``p1``,
    ``m1``, ``v1``) ||after - expected|| / ||expected - before||: 0 for the
    expected step, 1 for a state left unchanged; and last ||g - m0||, what
    a share of the gradient is measured against."""
    import jax.numpy as jnp

    m = beta1 * m0 + (1 - beta1) * g
    v = beta2 * v0 + (1 - beta2) * g * g
    p = p0 - rate * m / (jnp.sqrt(v) + eps)

    def off(got, want, was):
        return jnp.linalg.norm((got - want).ravel()) / jnp.maximum(
            jnp.linalg.norm((want - was).ravel()), 1e-30)
    return jnp.stack([off(p1, p, p0), off(m1, m, m0), off(v1, v, v0),
                      jnp.linalg.norm((g - m0).ravel())])


def token_ce_error(ce, want_ce):
    """What a lower precision moves: per token, not in the mean.  The rms
    over the tokens of a pass of (ce - reference), over the reference's
    mean, averaged over the passes ([T, ...] both)."""
    ce, want_ce = (np.asarray(a, np.float64).reshape(len(a), -1)
                   for a in (ce, want_ce))
    return float(np.mean(np.sqrt(((ce - want_ce) ** 2).mean(1))
                         / want_ce.mean(1)))


def spread_gate(batch, params):
    """The exit gate's two leaves for the compared state: the bias ln(1/2),
    with which a third of what arrives leaves at each pass, so that the
    four exits weigh 0.33 / 0.22 / 0.15 / 0.30 and every pass carries
    weight; the weights a tenth of an untrained model's (drawn from the
    pool's first batch, so from ``--seed``): the path from the gate into
    the stream stays in the comparison, and the warmed-up state's common
    component cannot tilt the exits (with the full spread the last pass
    kept 0.04 to 0.68 of the weight from seed to seed)."""
    rng = np.random.default_rng(batch["ids"].ravel()[:4].tolist())
    return {"early_exit_gate.w": rng.normal(
                0.0, 0.1 * params["initializer_range"],
                params["hidden_size"]).astype(np.float32),
            "early_exit_gate.b": np.full(1, math.log(0.5), np.float32)}


def reference(params, scope, main):
    """Called in set-up with the warmed-up state.  Returns the faults.

    The limits hold at the published widths on the chip.  On any other
    backend (the CPU tests' tiny sizes: 32 tokens, where one token is a
    thirtieth of every sum) the comparison runs and logs for the control
    flow's sake, and only what no precision excuses is a fault: a parameter
    without its Adam op, an exit distribution that does not sum to 1."""
    import time

    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import telemetry

    began = time.perf_counter()
    handles, batch = _state["handles"], _state["batch"]
    block = main.global_block()
    on_chip = jax.default_backend() == "tpu"
    T = params["total_ut_steps"]
    cfg = {k: v for k, v in params.items()
           if isinstance(v, (int, float)) and not isinstance(v, bool)}

    def host(var):
        return np.asarray(scope.find_var(getattr(var, "name", var)))

    def say(text):
        print("reference (%.1f s): %s" % (time.perf_counter() - began, text),
              flush=True)

    def peak():
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) / 1e9

    for name, value in spread_gate(batch, params).items():
        scope.set_var(name, jnp.asarray(value))
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
    say("the exit gate set to spread the exits, %d leaves and their moments "
        "copied to the host" % len(before))

    # the step the window times: same program, feed signature and fetch
    # list, so the same HLO; this executor's first call reads the window's
    # executable back from the compile cache entry the warm-up wrote
    exe = fluid.Executor(fluid.TPUPlace() if on_chip else fluid.CPUPlace())
    backend_s = telemetry.registry().counter("xla_compile_seconds_total")
    compiled_s = backend_s.value(phase="backend", why="dispatch")
    loss = float(np.asarray(exe.run(
        main, feed=batch, fetch_list=[handles["loss"]], scope=scope,
        return_numpy=False)[0]).reshape(-1)[0])
    # what the passes gave IN THAT STEP: the model leaves them in the scope
    ce, p = host(handles["ce"]), host(handles["exit_distribution"])
    say("one step of the timed program, its executable handed over in %.1f "
        "s (compile cache: %s); peak_bytes_in_use so far %.3f GB" % (
            backend_s.value(phase="backend", why="dispatch") - compiled_s,
            jax.config.jax_compilation_cache_dir, peak()))

    changes = jax.jit(off_expected_change, static_argnums=(8, 9, 10))
    sums, norms, off = {}, {}, {}
    lost = np.zeros(T)      # per pass: what losing it would read, at most

    def take(tagged, grad):
        """One application's gradient of a leaf (``<name>@<pass>``; the
        passes come last to first, the embedding once): summed on the
        host, and with the last contribution put through Adam by hand."""
        name, _, t = tagged.partition("@")
        if t:
            norms.setdefault(name, []).append(
                (int(t), float(jnp.linalg.norm(grad.ravel()))))
            if name in sums:
                sums[name] += np.asarray(grad)
            else:
                sums[name] = np.array(grad)
            if int(t):
                return
        g = sums.pop(name) if t else grad
        op, was = adam[name], before.pop(name)
        rate = was["LearningRate"].reshape(()) * \
            np.sqrt(1 - was["Beta2Pow"].reshape(())) / \
            (1 - was["Beta1Pow"].reshape(()))
        read = np.asarray(changes(
            g, was["Param"], was["Moment1"], was["Moment2"],
            *(scope.find_var(op.input(slot)[0])
              for slot in ("Param", "Moment1", "Moment2")),
            np.float32(rate), float(op.attr("beta1")),
            float(op.attr("beta2")), float(op.attr("epsilon"))))
        off[name] = read[:3]
        if t and leaf_kind(name)[1] == "layers":
            for k, norm in norms[name]:
                lost[k] = max(lost[k], norm / max(read[3], 1e-30))

    # the float32 reference, and beside it through the same pass over the
    # weights the same forward all in bfloat16: what the program's error is
    # held against
    want_loss, _, want_ce, _, want_p, (low_ce, _) = \
        _reference().loss_and_grads(
            weights, jnp.asarray(batch["ids"][..., 0]),
            jnp.asarray(batch["labels"][..., 0]), cfg, fetch=jax.device_put,
            take=take, untied=True, head_rows=1024, control=jnp.bfloat16)
    want_loss = float(want_loss)
    say("the float32 reference and its gradients, pass by pass; "
        "peak_bytes_in_use now %.3f GB" % peak())

    limit_faults = []
    loss_err = abs(loss - want_loss) / want_loss
    say("loss %.6f, float32 reference %.6f: relative difference %.2e "
        "(limit %.1e)" % (loss, want_loss, loss_err, LOSS_LIMIT))
    if not loss_err <= LOSS_LIMIT:
        limit_faults.append("loss %.6f is %.2e from the float32 "
                            "reference's %.6f (limit %.1e)" % (
                                loss, loss_err, want_loss, LOSS_LIMIT))
    worst = {}
    for name, read in off.items():
        kind, cls = leaf_kind(name)
        worst[kind] = np.maximum(worst.get(kind, 0), read)
        # a leaf of one element: its parameter's change alone (limit 2)
        held = read[:1] if weights[name].size == 1 else read
        if not held.max() <= CHANGE_LIMITS[cls]:
            limit_faults.append(
                "%s: parameter, first and second moment are %.3f / %.3f / "
                "%.3f of their expected change away from it (limit %.2f; "
                "1 = left unchanged)" % (name, *read, CHANGE_LIMITS[cls]))
    say("%d leaves, change over the step off the expected one (parameter / "
        "moment1 / moment2, the worst layer of each kind): " % len(off)
        + "; ".join("%s %.4f / %.4f / %.4f" % (k, *r)
                    for k, r in worst.items()))
    say("a backward that lost a pass would read, on the first moment of the "
        "tied leaf that shows it most (the gate's apart), %s (pass 1 to %d; "
        "limit %.2f)" % (
            " ".join("%.3f" % r for r in lost), T, CHANGE_LIMITS["layers"]))

    mine, low = token_ce_error(ce, want_ce), token_ce_error(low_ce, want_ce)
    say("a token's cross-entropy is %.3e of the mean off the float32 "
        "reference's (rms over tokens, mean of the passes), the "
        "all-bfloat16 reference's %.3e: ratio %.3f (limit %.2f)" % (
            mine, low, mine / low, PRECISION_LIMIT))
    if not mine <= PRECISION_LIMIT * low:
        limit_faults.append(
            "a token's cross-entropy is %.3e of the mean from the float32 "
            "reference's, %.3f of what a forward all in bfloat16 reads "
            "(limit %.2f)" % (mine, mine / low, PRECISION_LIMIT))

    def by_pass(a):
        return np.asarray(a, np.float64).reshape(T, -1)
    # means over the tokens of each pass: the precision hardly moves them
    ce_err = np.abs(by_pass(ce).mean(1) / by_pass(want_ce).mean(1) - 1)
    p_err = np.abs(by_pass(p).mean(1) - by_pass(want_p).mean(1))
    say("mean cross-entropy of each pass %s, relative difference from the "
        "reference's at worst %.2e (limit %.1e); token-mean exit "
        "distribution %s, off the reference's by at worst %.2e (limit "
        "%.1e)" % (
            " ".join("%.5f" % c for c in by_pass(ce).mean(1)), ce_err.max(),
            LOSS_LIMIT, " ".join("%.5f" % q for q in by_pass(p).mean(1)),
            p_err.max(), EXIT_LIMIT))
    if not ce_err.max() <= LOSS_LIMIT:
        limit_faults.append("a pass's mean cross-entropy is %.2e from the "
                            "reference's (limit %.1e)" % (ce_err.max(),
                                                          LOSS_LIMIT))
    if not p_err.max() <= EXIT_LIMIT:
        limit_faults.append("a pass's mean exit probability is %.2e from "
                            "the reference's (limit %.1e)" % (p_err.max(),
                                                              EXIT_LIMIT))
    # (the chip's float32 exp and log are good to about 1e-4)
    if not np.allclose(p.sum(axis=0), 1.0, atol=1e-3):
        faults.append("the exit distribution sums to %.6f..%.6f, not 1" % (
            p.sum(axis=0).min(), p.sum(axis=0).max()))
    if on_chip:
        return faults + limit_faults
    for fault in limit_faults:
        print("not held to the chip's limit here: " + fault, flush=True)
    return faults
