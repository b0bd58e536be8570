"""The looped ``ouro`` decoder (``models/ouro.py``: a block of layers run
``total_ut_steps`` times with tied weights as ONE ``recurrent`` op, an exit
head a pass, the exit-weighted loss) against the plain float32 reference
(``models/ouro_reference.py``) on seeded weights, at tiny sizes on the CPU
(Pallas kernels interpreted): 2 layers of 2 heads of 16, 3 passes,
vocabulary 128, 2 sequences of 16 tokens.

Tolerances: in float32 the two sides differ by summation order only.
``TOL`` (2e-5 of a tensor's largest entry) holds every activation; a
leaf's gradient is held to ``GRAD_TOL`` (1e-4 of its norm): the gate's bias
is ONE number, the sum over passes and tokens of signed terms that mostly
cancel, and reads up to 2.5e-5; every other leaf stays under 5e-6.  Both are far below
what a wrong term gives: a tied leaf that lost one pass's contribution is
off by percents and more.  Under pure-bf16 AMP the limits are loose and
say why.
"""

import collections
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import models
from paddle_tpu.fluid import layers, profiler, telemetry
from paddle_tpu.models import ouro
from paddle_tpu.models import ouro_reference as ref

TOL = 2e-5
GRAD_TOL = 1e-4


def close(got, want, tol=TOL, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert err <= tol, "%s: relative error %.3g > %.3g" % (what, err, tol)


def off(got, want):
    """||got - want|| / ||want||."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def batch(cfg, seed=0, n=2):
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n, cfg.max_seq_len + 1), dtype=np.int64)
    return {"ids": ids[:, :-1, None], "labels": ids[:, 1:, None]}


def reference_cfg(cfg):
    return {k: v for k, v in vars(cfg).items()}


def build(cfg, unrolled=False, optimizer=None, seed=11):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        handles = ouro.build_train(
            cfg, unrolled=unrolled,
            optimizer=optimizer or fluid.optimizer.SGDOptimizer(0.0))
    return main, startup, handles


def stir(scope, main, seed=1):
    """The norms' scales start at 1 and the gate's bias at 0, where a
    wrong term in their gradients could hide: move every vector leaf."""
    rng = np.random.default_rng(seed)
    params = {}
    for p in main.global_block().all_parameters():
        value = np.asarray(scope.find_var(p.name))
        if value.ndim == 1:
            value = (value + 0.3 * rng.standard_normal(value.shape)) \
                .astype(np.float32)
            scope.set_var(p.name, jnp.asarray(value))
        params[p.name] = value
    return params


def one_step(cfg, unrolled=False, optimizer=None, feed=None):
    """``(params before, loss, ce, p, {leaf: gradient}, main)`` of one
    forward + backward through ``Executor``."""
    main, startup, handles = build(cfg, unrolled, optimizer)
    feed = feed or batch(cfg)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        params = stir(fluid.global_scope(), main)
        names = sorted(params)
        grads = [main.global_block().var(main._grad_name_map[n])
                 for n in names]
        got = exe.run(main, feed=feed, fetch_list=[
            handles["loss"], handles["ce"], handles["exit_distribution"]]
            + grads)
    return params, got[0], got[1], got[2], dict(zip(names, got[3:])), main


def reference_of(params, feed, cfg, **kw):
    return ref.loss_and_grads(
        {k: jnp.asarray(v) for k, v in params.items()},
        jnp.asarray(feed["ids"][..., 0]), jnp.asarray(feed["labels"][..., 0]),
        reference_cfg(cfg), **kw)


@pytest.fixture(scope="module")
def tiny_steps():
    cfg = ouro.tiny_config()
    feed = batch(cfg)
    return {"cfg": cfg, "feed": feed,
            "loop": one_step(cfg, feed=feed),
            "unrolled": one_step(cfg, unrolled=True, feed=feed)}


@pytest.mark.parametrize("form", ["loop", "unrolled"])
def test_program_against_the_reference_in_float32(tiny_steps, form):
    """Loss, the passes' cross-entropies, the exit distribution and the
    gradient of EVERY leaf (22 in the layers, the embedding, the head, the
    final norm, the gate's weight and bias)."""
    cfg, feed = tiny_steps["cfg"], tiny_steps["feed"]
    params, loss, ce, p, grads, _ = tiny_steps[form]
    want_loss, want_grads, want_ce, _, want_p = reference_of(params, feed,
                                                             cfg)
    close(loss.reshape(()), want_loss, what="loss")
    assert ce.shape == (cfg.total_ut_steps, 2, cfg.max_seq_len)
    close(ce, want_ce, what="ce")
    close(p, want_p, what="exit distribution")
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    assert sorted(grads) == sorted(want_grads) and len(grads) == 27
    for name, want in want_grads.items():
        assert off(grads[name], want) <= GRAD_TOL, \
            (name, off(grads[name], want))
        assert np.linalg.norm(want) > 0, name


def test_the_whole_reference_agrees_with_its_blockwise_gradient(tiny_steps):
    """``loss_and_grads`` differentiates one block application at a time;
    ``jax.grad`` of ``forward_loss`` differentiates the whole."""
    cfg, feed = tiny_steps["cfg"], tiny_steps["feed"]
    params = {k: jnp.asarray(v) for k, v in tiny_steps["loop"][0].items()}
    ids, labels = (jnp.asarray(feed[k][..., 0]) for k in ("ids", "labels"))
    rcfg = reference_cfg(cfg)
    (loss, (ce, gate, p)), whole = jax.value_and_grad(
        lambda prm: (lambda l, *aux: (l, aux))(
            *ref.forward_loss(prm, ids, labels, rcfg)), has_aux=True)(params)
    want_loss, grads, want_ce, want_gate, want_p = ref.loss_and_grads(
        params, ids, labels, rcfg)
    close(loss, want_loss)
    close(ce, want_ce)
    close(gate, want_gate)
    close(p, want_p)
    for name in whole:
        assert off(grads[name], whole[name]) <= GRAD_TOL, name


def test_loop_form_equals_unrolled_form(tiny_steps):
    """ONE ``recurrent`` op over T passes against T copies of the block over
    shared parameter names (``@RENAME@`` + ``sum``): the same loss and the
    same gradient of every leaf, while the unrolled program holds T times
    the block's ops."""
    cfg = tiny_steps["cfg"]
    _, loss, ce, p, grads, main = tiny_steps["loop"]
    _, loss_u, ce_u, p_u, grads_u, main_u = tiny_steps["unrolled"]
    close(loss, loss_u, what="loss")
    close(ce, ce_u, what="ce")
    close(p, p_u, what="p")
    for name in grads:
        assert off(grads[name], grads_u[name]) <= GRAD_TOL, name
    loops = [op for op in main.global_block().ops if op.type == "recurrent"]
    assert len(loops) == 1 and loops[0].attr("n_steps") == cfg.total_ut_steps
    assert not loops[0].input("Inputs")          # a count, no step input
    body = main.blocks[loops[0].attr("sub_block")].ops

    def forward_ops(ops):
        return collections.Counter(
            op.type for op in ops if not op.type.endswith("_grad")
            and op.type not in ("sum", "adam", "sgd", "fill_constant"))

    in_body, in_line = forward_ops(body), forward_ops(
        main_u.global_block().ops)
    for kind in ("mul", "fused_attention", "rms_norm", "rotary_embedding",
                 "softmax_with_cross_entropy"):
        assert in_body[kind] and \
            in_line[kind] == cfg.total_ut_steps * in_body[kind], kind
    assert not any(op.type == "recurrent" for op in main_u.global_block().ops)
    # ... and T - 1 more gradient contributions a tied leaf to sum
    sums = [op for op in main_u.global_block().ops if op.type == "sum"]
    tied = [op for op in sums if len(op.input("X")) >= cfg.total_ut_steps - 1]
    assert len(tied) >= 26


def test_the_tied_gradient_is_the_sum_of_the_passes(tiny_steps):
    """The reference with the passes' contributions kept apart (T untied
    copies of every weight): they add up to the program's gradient, and no
    single pass gives it."""
    cfg, feed = tiny_steps["cfg"], tiny_steps["feed"]
    params, _, _, _, grads, _ = tiny_steps["loop"]
    apart = reference_of(params, feed, cfg, untied=True)[1]
    T = cfg.total_ut_steps
    for name, got in grads.items():
        if name == "embed_tokens":
            continue                    # read once, before the first pass
        parts = [np.asarray(apart["%s@%d" % (name, t)]) for t in range(T)]
        assert off(got, sum(parts)) <= GRAD_TOL, name
        if name.startswith("early_exit_gate"):
            # the last pass's gate is not read: p_T is what is left
            assert np.abs(parts[-1]).max() == 0, name
            parts = parts[:-1]
        for t, part in enumerate(parts):
            assert off(got, sum(parts) - part) > 1e-3, (name, t)


def test_a_step_leaves_what_the_passes_gave_in_the_scope():
    """The passes' cross-entropies and the exit distribution are
    persistable: after a training step the scope holds that step's own."""
    cfg = ouro.tiny_config()
    main, startup, handles = build(cfg)
    kept = [handles[k] for k in ("ce", "exit_distribution")]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fetched = exe.run(main, feed=batch(cfg), fetch_list=kept)
        exe.run(main, feed=batch(cfg, seed=1), fetch_list=[handles["loss"]])
        exe.run(main, feed=batch(cfg), fetch_list=[handles["loss"]])
        left = [np.asarray(fluid.global_scope().find_var(v.name))
                for v in kept]
    for got, want in zip(left, fetched):
        assert got.shape == (cfg.total_ut_steps, 2, cfg.max_seq_len)
        np.testing.assert_array_equal(got, want)


def test_the_control_stream_is_the_reference_in_that_precision(tiny_steps):
    """``control=``: a second precision through the same pass over the
    weights gives what a run of its own in that precision gives, and leaves
    the first stream as it was."""
    cfg, feed = tiny_steps["cfg"], tiny_steps["feed"]
    params = {k: jnp.asarray(v) for k, v in tiny_steps["loop"][0].items()}
    ids, labels = (jnp.asarray(feed[k][..., 0]) for k in ("ids", "labels"))
    rcfg = reference_cfg(cfg)
    ce, gate, (low_ce, low_gate) = ref.forward_by_blocks(
        params, ids, labels, rcfg, head_rows=8, control=jnp.bfloat16)
    alone = ref.forward_by_blocks(params, ids, labels, rcfg, head_rows=8)
    low_alone = ref.forward_by_blocks(params, ids, labels, rcfg,
                                      dtype=jnp.bfloat16, head_rows=8)
    for got, want in zip((ce, gate, low_ce, low_gate), alone + low_alone):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 0 < off(low_ce, ce) < 2e-2
    assert len(reference_of(tiny_steps["loop"][0], feed, cfg,
                            control=jnp.bfloat16)) == 6


def test_pure_bf16_against_the_reference():
    """Under pure-bf16 AMP (bf16 matmul operands and activations; float32
    norms' statistics, softmax, gate, exit distribution and loss) the
    program stays within bf16's reach of the float32 reference: bf16 keeps
    8 bits, 2^-9 = 0.002 relative a rounding, a few dozen roundings deep;
    the loss is a mean over 32 tokens of float32 losses (1e-2), a leaf's
    gradient within 10% of its norm.  A wrong term is off by far more
    (dropping one pass moves a tied leaf by 20% and more)."""
    cfg = ouro.tiny_config()
    feed = batch(cfg)
    opt = fluid.contrib.mixed_precision.decorate(
        fluid.optimizer.SGDOptimizer(0.0), use_pure_bf16=True)
    params, loss, ce, p, grads, _ = one_step(cfg, optimizer=opt, feed=feed)
    want_loss, want_grads, want_ce, _, want_p = reference_of(params, feed,
                                                             cfg)
    assert loss.dtype == np.float32 and ce.dtype == np.float32
    assert abs(float(loss.reshape(())) - float(want_loss)) <= \
        1e-2 * float(want_loss)
    close(ce, want_ce, tol=5e-2, what="ce")
    close(p, want_p, tol=5e-2, what="p")
    reads = {n: off(grads[n], want_grads[n]) for n in grads}
    # the gate's bias is one number, a sum of signed terms that mostly
    # cancel: its rounding is relative to the terms, not to the sum (0.13
    # here, 0.09 for the all-bfloat16 reference)
    assert reads.pop("early_exit_gate.b") <= 0.5
    assert 1e-4 < max(reads.values()) <= 0.1, max(reads.values())


def test_the_reference_in_bfloat16_is_the_control_not_the_reference(
        tiny_steps):
    cfg, feed = tiny_steps["cfg"], tiny_steps["feed"]
    params = tiny_steps["loop"][0]
    want = reference_of(params, feed, cfg)[0]
    low, grads = reference_of(
        params, feed, cfg, dtype=jnp.bfloat16,
        take=lambda name, grad: float(jnp.linalg.norm(grad.ravel())))[:2]
    assert 0 < abs(float(low) - float(want)) < 2e-2 * float(want)
    assert all(isinstance(g, float) and g > 0 for g in grads.values())


def test_three_adam_steps_loop_and_unrolled():
    """Training moves the same way in both forms: three Adam steps, the
    losses equal to float32 rounding, and falling."""
    cfg = ouro.tiny_config()
    feed = batch(cfg)
    losses = {}
    for unrolled in (False, True):
        main, startup, handles = build(
            cfg, unrolled, fluid.optimizer.AdamOptimizer(1e-2))
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            losses[unrolled] = [float(exe.run(
                main, feed=feed, fetch_list=[handles["loss"]])[0][0])
                for _ in range(3)]
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-5)
    assert losses[False][2] < losses[False][0]
    # an untrained model: ln V + hidden r^2 / 2, less beta H of the
    # untrained gate's distribution (lambda = 1/2: p = 1/2, 1/4, 1/4)
    h_p = -(0.5 * np.log(0.5) + 2 * 0.25 * np.log(0.25))
    want = np.log(cfg.vocab_size) + cfg.hidden_size * 0.02 ** 2 / 2 \
        - cfg.entropy_beta * h_p
    assert abs(losses[False][0] - want) < 0.03 * want


# -- what the compiled step holds ---------------------------------------------

def test_counters_say_what_was_traced():
    cfg = ouro.tiny_config()
    lowered = telemetry.registry().counter("recurrent_lowered_total")
    grad = telemetry.registry().counter("recurrent_grad_lowered_total")
    before = lowered.value(steps="3", saves="1"), grad.value()
    one_step(cfg)
    assert lowered.value(steps="3", saves="1") == before[0] + 1
    assert grad.value() == before[1] + 1


def test_the_loops_ops_keep_their_own_names_in_the_compiled_step():
    """Inside ``recurrent`` / ``recurrent_grad`` an instruction's FIRST
    ``fluid_*`` scope is its own op's and its first ``role_*`` the
    container's, so a device trace's breakdown shows the loop's ``mul``,
    ``fused_attention`` and ``rms_norm`` and not one ``fluid_recurrent``
    line; the body sits under ``ut_loop``, the rematerialised forward
    under ``ut_remat`` (its transposes under ``transpose(...)``), the exit
    head under ``exit_head``."""
    cfg = ouro.tiny_config()
    main, startup, handles = build(cfg)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.compiled_hlo(main, feed=batch(cfg), fetch_list=[handles["loss"]])
    # (the scalar computations of a reduce carry a name cut at its head)
    names = [n for n in profiler.step_scopes().values()
             if "ut_loop" in n and n.startswith("jit(")]
    assert names and not any("fluid_recurrent/" in n for n in names)
    first_fluid = collections.Counter()
    for n in names:
        role = re.search(r"role_(fwd|bwd|opt)", n).group(0)
        found = re.search(r"fluid_[A-Za-z0-9_]+", n)
        if found:
            first_fluid[role, found.group(0)] += 1
    for kind in ("fluid_mul", "fluid_fused_attention", "fluid_rms_norm",
                 "fluid_rotary_embedding"):
        assert first_fluid["role_fwd", kind], kind
        assert first_fluid["role_bwd", kind], kind
    remat = [n for n in names if "ut_remat" in n and "transpose(" not in n]
    back = [n for n in names if "transpose(" in n]
    assert remat and all(n.split("/")[1] == "role_bwd" for n in remat)
    assert back and all(n.split("/")[1] == "role_bwd" for n in back)
    assert any("fluid_mul" in n for n in remat)
    assert any("fluid_mul" in n for n in back)
    heads = [n for n in names if "exit_head" in n]
    assert any("fluid_softmax_with_cross_entropy" in n for n in heads)
    assert any("transpose(" in n for n in heads)
    assert not any("exit_head" in n for n in names
                   if "fluid_fused_attention" in n)


LAYERS, PASSES = 2, 3


def _mid_config(seq=512):
    """Shapes at which the flash kernels are on the path for a v5e; at the
    default length a head is ONE tile of them."""
    return ouro.OuroConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=LAYERS,
        num_attention_heads=2, num_key_value_heads=2, head_dim=64,
        intermediate_size=256, total_ut_steps=PASSES, max_seq_len=seq)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _compile_step(cfg, chip):
    """The pure-bf16 training step of ``cfg`` compiled for ``chip``."""
    from paddle_tpu.fluid import executor
    opt = fluid.contrib.mixed_precision.decorate(
        fluid.optimizer.SGD(0.01), use_pure_bf16=True)
    main, startup, handles = build(cfg, optimizer=opt)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        compiled, feed_vals = exe._resolve_compiled(
            main, batch(cfg, n=1), [handles["loss"]], scope, None)
        args = (executor._scope_state(scope, compiled.state_mut),
                executor._scope_state(scope, compiled.state_ro),
                tuple(feed_vals), np.int32(0))
        shapes = jax.tree.map(lambda v: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=chip), args)
        return compiled._jitted.lower(*shapes).compile()


@pytest.fixture(scope="module")
def compiled_for_v5e(one_chip):
    """The pure-bf16 training step compiled for a described v5e (no chip
    needed: XLA:TPU and Mosaic run here), with ``recurrent_grad``'s own
    lowering and, ``"replay"``, with ``generic_grad_lower``."""
    from paddle_tpu.fluid.registry import OP_DEFS
    cfg = _mid_config()
    steps = {"remat": _compile_step(cfg, one_chip)}
    own = OP_DEFS["recurrent"].grad_lower
    OP_DEFS["recurrent"].grad_lower = None
    try:
        steps["replay"] = _compile_step(cfg, one_chip)
    finally:
        OP_DEFS["recurrent"].grad_lower = own
    return steps


def _mosaic_calls(executable):
    return collections.Counter(re.findall(
        r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        executable.as_text()))


def test_the_step_holds_one_forward_and_one_backward_loop(compiled_for_v5e):
    """One ``while`` for the passes and one for their backward; in them 2L
    ``flash_fwd`` calls (the forward scan's and the rematerialised
    forward's) and L of ``flash_bwd`` (S=512: a head is one tile, so dQ,
    dK and dV come from one kernel): the forward scan is not run a second
    time, which would make it 3L."""
    text = compiled_for_v5e["remat"].as_text()
    assert len(re.findall(r" while\(", text)) == 2
    calls = _mosaic_calls(compiled_for_v5e["remat"])
    assert calls == {"flash_fwd": 2 * LAYERS, "flash_bwd": LAYERS}, calls


def test_a_longer_sequence_keeps_the_two_backward_passes(one_chip):
    """S=1024 is two 512-row tiles a side: dQ is summed over k tiles and
    dK/dV over q tiles, so the backward stays the pair of passes, as at
    the cell's S=4096."""
    calls = _mosaic_calls(_compile_step(_mid_config(seq=1024), one_chip))
    assert calls == {"flash_fwd": 2 * LAYERS, "flash_dq": LAYERS,
                     "flash_dkv": LAYERS}, calls


def test_the_replay_keeps_every_pass_and_the_own_lowering_one(
        compiled_for_v5e):
    """What crosses from forward to backward: the replaying lowering's scan
    transpose stacks every residual of every pass ([T, ...] each), the own
    lowering the carry alone, so its step needs the fewer temporaries."""
    own, replay = (compiled_for_v5e[k].memory_analysis().temp_size_in_bytes
                   for k in ("remat", "replay"))
    assert own < replay, (own, replay)


# -- satellites ---------------------------------------------------------------

@pytest.mark.parametrize("interleaved", [False, True])
def test_rotary_takes_the_pairing_as_an_attribute(interleaved):
    """``interleaved=False``: lane i pairs with lane i + D/2 (rotate-half,
    this family's published layout); ``True`` (the default, unchanged): 2i
    with 2i + 1, de-interleaved first."""
    from paddle_tpu.models import deepseek_v3_reference
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 16, 2, 8)).astype(np.float32)
    w = rng.normal(size=x.shape).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xv = layers.data(name="x", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        wv = layers.data(name="w", shape=list(x.shape), dtype="float32",
                         append_batch_size=False)
        y = layers.rotary_embedding(xv, theta=1e6, interleaved=interleaved) \
            if not interleaved else layers.rotary_embedding(xv, theta=1e6)
        fluid.backward.append_backward(layers.reduce_sum(y * wv))
    with fluid.scope_guard(fluid.Scope()):
        got, dx = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": x, "w": w},
            fetch_list=[y, main.global_block().var("x@GRAD")])
    plain = deepseek_v3_reference.rotary if interleaved else ref.rotary
    want, vjp = jax.vjp(lambda a: plain(a, 1e6), x)
    close(got, want, what="rotary")
    close(dx, vjp(jnp.asarray(w))[0], what="d rotary")
    if not interleaved:
        np.testing.assert_allclose(got[:, 0], x[:, 0])   # position 0
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_the_two_copies_of_the_reference_are_one():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "paddle_tpu", "models",
                           "ouro_reference.py")) as f:
        program_side = f.read()
    with open(os.path.join(here, "..", "benchmarks", "configs",
                           "ouro_reference.py")) as f:
        assert f.read() == program_side


def test_the_model_is_exported():
    assert models.ouro is ouro and models.ouro_reference is ref
