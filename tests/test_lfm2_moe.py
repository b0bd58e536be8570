"""The ``lfm2_moe`` decoder (``models/lfm2_moe.py``) and what it brought to
the program — grouped key/value heads in ``fused_attention``, the gated
short convolution, the router's epsilon — against the plain float32
reference (``models/lfm2_moe_reference.py``) on seeded weights, at tiny
sizes on the CPU (Pallas kernels interpreted).

Tolerances: everything here runs in float32 on both sides, so the two
differ by summation order only; 2e-5 relative to a tensor's largest entry
is ten times what the worst case showed and far below what a wrong term
gives (a key/value head paired ``h % H_kv`` instead of ``h // G``, or taps
read in the reverse order, move the output by tens of percents; both are
planted below).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import models
from paddle_tpu.fluid import layers, telemetry
from paddle_tpu.fluid.backward import append_backward
from paddle_tpu.fluid.ops import decoder_ops, pallas_ops
from paddle_tpu.models import lfm2_moe
from paddle_tpu.models import lfm2_moe_reference as ref

TOL = 2e-5
HERE = os.path.dirname(os.path.abspath(__file__))


def close(got, want, tol=TOL, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert err <= tol, "%s: relative error %.3g > %.3g" % (what, err, tol)


def far(got, want, least=0.05):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max() > least


def run_program(build, feed):
    """``build()`` -> (outputs to fetch, variables whose gradients to
    fetch, loss): one forward + backward through ``Executor``."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        outs, wrt, loss = build()
        append_backward(loss)
        grads = [main._grad_name_map.get(v.name, v.name + "@GRAD")
                 for v in wrt]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=feed, fetch_list=list(outs) + grads)
    return got[:len(outs)], got[len(outs):]


def data(name, shape, dtype="float32"):
    v = layers.data(name=name, shape=list(shape), dtype=dtype,
                    append_batch_size=False)
    v.stop_gradient = False
    return v


# -- the gated short convolution -------------------------------------------------

@pytest.mark.parametrize("taps,seq", [(3, 16), (4, 16), (3, 2), (3, 1)])
def test_gated_short_conv_against_the_sum_over_taps(taps, seq):
    """Forward and both gradients through the Fluid op; positions 0 and 1
    read the left padding (with 3 taps position 0 sees one product, position
    1 two); a sequence shorter than the taps drops the taps that reach
    before it."""
    rng = np.random.default_rng(taps * 10 + seq)
    C = 8
    x = rng.normal(size=(2, seq, 3 * C)).astype(np.float32)
    g = rng.normal(size=(2, seq, C)).astype(np.float32)
    w = rng.normal(size=(C, taps)).astype(np.float32)
    telemetry.reset_metrics()

    def build():
        xv = data("x", x.shape)
        y = layers.gated_short_conv(xv, kernel_size=taps,
                                    param_attr=fluid.ParamAttr(
            name="w", initializer=fluid.initializer.NumpyArrayInitializer(w)))
        assert y.shape == g.shape
        wv = fluid.default_main_program().global_block().var("w")
        return [y], [xv, wv], layers.reduce_sum(y * data("g", g.shape))

    (y,), (dx, dw) = run_program(build, {"x": x, "g": g})
    want, vjp = jax.vjp(ref.gated_conv, jnp.asarray(x), jnp.asarray(w))
    wdx, wdw = vjp(jnp.asarray(g))
    close(y, want, what="gated_short_conv")
    close(dx, wdx, what="d / d bcx")
    close(dw, wdw, what="d / d taps")
    # position 0 is the LAST tap alone on its own product: the padding
    b, c, s = x[:, 0, :C], x[:, 0, C:2 * C], x[:, 0, 2 * C:]
    np.testing.assert_allclose(y[:, 0], c * w[:, -1] * b * s, rtol=1e-5,
                               atol=1e-6)
    if seq > 1:
        z = x[:, :2, :C] * x[:, :2, 2 * C:]
        np.testing.assert_allclose(
            y[:, 1], x[:, 1, C:2 * C] * (w[:, -1] * z[:, 1] +
                                         w[:, -2] * z[:, 0]),
            rtol=1e-5, atol=1e-6)
        # the planted fault: taps in the reverse order are another result
        assert far(decoder_ops.gated_short_conv(jnp.asarray(x),
                                                jnp.asarray(w[:, ::-1])), want)
    counter = telemetry.registry().get("gated_short_conv_lowered_total")
    assert counter.value(kernel_size=taps) == counter.value() >= 2


def test_gated_short_conv_is_elementwise_under_one_scope():
    """No convolution and no dot in the traced body, forward or backward,
    and every equation under the ``short_conv`` scope; bf16 in, bf16 out
    with the sum over the taps in float32."""
    x = jnp.ones((2, 16, 24), jnp.bfloat16)
    w = jnp.ones((8, 3), jnp.float32)

    def loss(x, w):
        return decoder_ops.gated_short_conv(x, w).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).as_text(
        debug_info=True)
    assert "convolution" not in text and "dot_general" not in text
    assert "short_conv" in text
    assert decoder_ops.gated_short_conv(x, w).dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="multiple of 3"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            layers.gated_short_conv(data("x", (2, 16, 25)))


# -- grouped key/value heads in the flash kernels ---------------------------------

B, H, H_KV, S, D = 2, 4, 2, 256, 16
G = H // H_KV


def _gqa_arrays(seed=0, seq=S, bias=False):
    rng = np.random.default_rng(seed)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (arr(B * H, seq, D), arr(B * H_KV, seq, D), arr(B * H_KV, seq, D),
            arr(B * H, seq, seq) if bias else None, arr(B * H, seq, D))


def _repeated(q, k, v, bias, causal, pairing=None):
    """The composition on K and V repeated to the query heads: head ``h``
    reads key/value head ``h // G``; ``pairing`` plants another."""
    if pairing is None:
        k, v = jnp.repeat(k, G, axis=0), jnp.repeat(v, G, axis=0)
    else:
        rows = jnp.asarray([(i // H) * H_KV + pairing(i % H)
                            for i in range(B * H)])
        k, v = k[rows], v[rows]
    return pallas_ops._reference_attention(q, k, v, bias, D ** -0.5,
                                           causal=causal)


@pytest.mark.parametrize("seq,causal,biased", [
    (256, True, False), (1024, True, False), (256, False, False),
    (256, False, True), (1024, True, True), (136, True, False)],
    ids=["causal_one_tile", "causal_looped", "plain", "bias", "bias_causal",
         "untileable"])
def test_flash_kernels_with_grouped_key_value_heads(seq, causal, biased):
    """Forward, dQ and dK / dV summed over the group against the
    composition on repeated K and V: one tile a head, the looped causal
    sweep over two tiles, the unrolled sweeps with and without a bias (the
    dbias pass reads K and V by group too).  A length that does not tile
    is refused by name: K and V are repeated in ONE place, the op's
    lowering (``test_fused_attention_op_with_grouped_heads``)."""
    q, k, v, bias, g = _gqa_arrays(seq, seq, biased)

    def flash(q, k, v, bias):
        return pallas_ops.flash_attention(q, k, v, bias, D ** -0.5, causal)
    if seq % 128:
        with pytest.raises(ValueError, match="no tile for; repeat K and V"):
            flash(q, k, v, bias)
        return
    out, vjp = jax.vjp(flash, q, k, v, bias)
    want, want_vjp = jax.vjp(
        lambda *a: _repeated(*a, causal=causal), q, k, v, bias)
    assert out.shape == q.shape
    close(out, want, what="forward")
    for name, got, exp in zip(("dq", "dk", "dv", "dbias"), vjp(g),
                              want_vjp(g)):
        if exp is not None:
            assert got.shape == exp.shape
            close(got, exp, what=name)
    # the planted fault: heads paired h % H_kv are another result
    assert far(_repeated(q, k, v, bias, causal, lambda h: h % H_KV), want)


def _index_maps(fn, *args):
    """``{kernel name: [equations of each operand's index map]}`` of the
    Pallas calls ``fn`` traces."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                maps = eqn.params["grid_mapping"].block_mappings
                found[eqn.params["name"]] = [
                    [e.primitive.name for e in m.index_map_jaxpr.jaxpr.eqns]
                    for m in maps]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("seq,kernels", [
    (256, ["flash_bwd", "flash_fwd"]),
    (1024, ["flash_dkv", "flash_dq", "flash_fwd"])])
def test_at_equal_heads_the_kernels_are_what_they_were(seq, kernels):
    """THE PIN for the cells that run the flash kernels with ``H_kv == H``:
    no operand's index map holds an operation (K and V are read at block
    row ``i`` itself, not ``i // 1``), the backward at one tile a head is
    still the fused kernel, the dK/dV pass writes K's and V's dtype, and
    the chooser sees the shapes it saw — while two key/value heads under
    four query heads divide, write float32 parts and keep the pair of
    passes."""
    q, k, v, _, _ = _gqa_arrays(1, seq)

    def grads(q, k, v):
        return jax.grad(lambda *a: pallas_ops.flash_attention(
            *a, None, D ** -0.5, True).sum(), argnums=(0, 1, 2))(q, k, v)
    same = _index_maps(grads, q, jnp.repeat(k, G, 0), jnp.repeat(v, G, 0))
    assert sorted(same) == kernels
    assert all(not eqns for maps in same.values() for eqns in maps), same
    grouped = _index_maps(grads, q, k, v)
    assert sorted(grouped) == sorted(set(kernels) - {"flash_bwd"} |
                                     ({"flash_dq", "flash_dkv"}
                                      if "flash_bwd" in kernels else set()))
    for name, maps in grouped.items():
        # K and V (operands 1 and 2) are read by group, nothing else is
        assert [bool(eqns) for eqns in maps[:3]] == [False, True, True], name
        assert all(not eqns for eqns in maps[3:]), (name, maps)
    # the chooser: a ninth element of 1 changes nothing it answers
    for shape in [(512, 512, 64, 64, 0, True, False, 2),      # flash cell
                  (4096, 4096, 128, 128, 64, False, True, 2),  # latent
                  (4096, 4096, 128, 128, 0, False, True, 2)]:  # looped
        for kernel in ("fwd", "dq", "dkv", "bwd"):
            assert pallas_ops._tiles(kernel, *shape) == \
                pallas_ops._tiles(kernel, *shape, 1)
        assert pallas_ops._fused_backward(*shape) == \
            pallas_ops._fused_backward(*shape, 1)
        assert pallas_ops._vmem_bytes("dkv", 512, 512, *shape) == \
            pallas_ops._vmem_bytes("dkv", 512, 512, *shape, 1) < \
            pallas_ops._vmem_bytes("dkv", 512, 512, *shape, 4)
    assert pallas_ops._fused_backward(512, 512, 64, 64, 0, True, False, 2)
    assert not pallas_ops._fused_backward(512, 512, 64, 64, 0, True, False,
                                          2, 4)


def test_flash_kernels_refuse_heads_that_do_not_divide_and_a_pair():
    q, k, v, _, _ = _gqa_arrays(2)
    with pytest.raises(ValueError, match="query heads over"):
        pallas_ops.flash_attention(q[:6], k, v, None, 1.0, True)
    with pytest.raises(ValueError, match="not both"):
        pallas_ops.flash_attention(
            q, k, v, None, 1.0, True,
            (jnp.zeros((B * H, S, 8)), jnp.zeros((B, S, 8))))


@pytest.mark.parametrize("seq,dropout,path,kv_sum", [
    (128, 0.0, "flash", "partials"), (1024, 0.0, "flash", "partials"),
    (136, 0.0, "composition", "repeat"), (128, 0.25, "composition", "repeat")])
def test_fused_attention_op_with_grouped_heads(seq, dropout, path, kv_sum):
    """Through the Fluid op and its grad op: the flash path hands K and V
    over at their own head count and reads the LSE back; a length that
    does not tile and attention dropout repeat K and V first; both are
    counted by name.  (Dropout: shapes and the path alone; its mask is the
    composition's own.)"""
    rng = np.random.default_rng(3)
    shapes = {"q": (B, H, seq, D), "k": (B, H_KV, seq, D),
              "v": (B, H_KV, seq, D), "w": (B, H, seq, D)}
    feed = {n: rng.normal(size=s).astype(np.float32)
            for n, s in shapes.items()}
    telemetry.reset_metrics()

    def build():
        v = {n: data(n, s) for n, s in shapes.items()}
        out = layers.fused_attention(v["q"], v["k"], v["v"], scale=D ** -0.5,
                                     causal=True, dropout_prob=dropout)
        assert out.shape == shapes["w"]
        return [out], [v[n] for n in "qkv"], layers.reduce_sum(out * v["w"])

    (out,), grads = run_program(build, feed)
    for got, name in zip(grads, "qkv"):
        assert got.shape == shapes[name]
    if not dropout:
        flat = {n: jnp.asarray(a).reshape((-1,) + a.shape[2:])
                for n, a in feed.items()}
        want, vjp = jax.vjp(lambda *a: _repeated(*a, None, True),
                            flat["q"], flat["k"], flat["v"])
        close(out.reshape(want.shape), want, what="op forward")
        for name, got, exp in zip("qkv", grads, vjp(flat["w"])):
            close(np.asarray(got).reshape(exp.shape), exp, what="d" + name)
    lowered = telemetry.registry().get("fused_attention_lowered_total")
    assert lowered.value(shape="gqa", path=path) == lowered.value() >= 1
    grad = telemetry.registry().get("fused_attention_grad_lowered_total")
    assert grad.value(kv_sum=kv_sum) == grad.value() == 1
    assert grad.value(path="residual" if path == "flash" else "replay") == 1


def test_fused_attention_refuses_a_pair_with_grouped_heads():
    def build():
        v = {n: data(n, s) for n, s in {
            "q": (B, H, S, D), "k": (B, H_KV, S, D), "v": (B, H_KV, S, D),
            "qr": (B, H, S, 8), "kr": (B, 1, S, 8)}.items()}
        out = layers.fused_attention(v["q"], v["k"], v["v"], causal=True,
                                     q_rope=v["qr"], k_rope=v["kr"])
        return [out], [], layers.reduce_sum(out)
    with pytest.raises(NotImplementedError, match="grouped key/value heads"):
        run_program(build, {n: np.zeros(s, np.float32) for n, s in {
            "q": (B, H, S, D), "k": (B, H_KV, S, D), "v": (B, H_KV, S, D),
            "qr": (B, H, S, 8), "kr": (B, 1, S, 8)}.items()})


# -- the router's epsilon ----------------------------------------------------------

def test_the_weights_epsilon_is_the_references_departure():
    """``route`` adds 1e-20 to the sum of the chosen scores (the
    ``deepseek_v3`` family's code), this family's code and the reference
    1e-6: the program's weights sum to ``scale``, the reference's to
    ``scale * s / (s + 1e-6)``, under 1e-6 apart on a sum of about 1 (noted
    in the reference's docstring; no attribute carries it)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    router_w = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    zero = jnp.zeros(8)
    idx, weight, _ = decoder_ops.route(x, router_w, zero, 2, 1.0)
    scores = np.take_along_axis(
        np.asarray(jax.nn.sigmoid(x @ router_w)), np.asarray(idx), -1)
    np.testing.assert_allclose(weight.sum(-1), 1.0, rtol=1e-6)
    mask, want = ref.router(x, router_w, zero, 2, 1.0)
    want = np.take_along_axis(np.asarray(want), np.asarray(idx), -1)
    np.testing.assert_allclose(
        want, scores / (scores.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(want, weight, rtol=3e-6)
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        layers.routed_experts(data("x", (4, 32)), 8, 2, 24)
        op, = [op for op in fluid.default_main_program().global_block().ops
               if op.type == "routed_experts"]
        assert not op.has_attr("weight_epsilon")


# -- the model ----------------------------------------------------------------------

def _reference_cfg(cfg):
    keys = ("num_hidden_layers", "layer_types", "num_dense_layers",
            "num_attention_heads", "num_key_value_heads", "norm_eps",
            "rope_theta", "num_experts", "num_experts_per_tok",
            "routed_scaling_factor", "num_experts_held", "first_expert_held")
    return {k: getattr(cfg, k) for k in keys}


def _reference_params(scope, handles):
    """The scope's parameters under the reference's names."""
    block = handles["loss"].block.program.global_block()
    # copies: the step donates the scope's own buffers
    params = {p.name: jnp.asarray(np.array(scope.find_var(p.name)))
              for p in block.all_parameters()}
    dense = handles["config"].num_dense_layers
    for i, bias in enumerate(handles["select_biases"]):
        params["select_bias.%d" % (dense + i)] = \
            jnp.asarray(np.array(scope.find_var(bias.name)))
    return params


def _batch(cfg, seed, batch=2):
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, cfg.max_seq_len + 1))
    return {"ids": ids[:, :-1, None].astype(np.int64),
            "labels": ids[:, 1:, None].astype(np.int64)}


def _squeeze(feed):
    return jnp.asarray(feed["ids"][..., 0]), jnp.asarray(feed["labels"][..., 0])


def _build(cfg, seed, **kw):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        handles = lfm2_moe.build_train(cfg, **kw)
    return main, startup, handles


@pytest.mark.parametrize("held,first,tied", [(8, 0, True), (2, 4, True),
                                             (8, 0, False)])
def test_model_logits_loss_and_every_gradient_through_executor(held, first,
                                                               tied):
    """S=128 tiles, so the attention layer runs the (interpreted) flash
    kernels on 4 query heads over 2 key/value heads; the second case is a
    share (2 of 8 experts held, routed over all 8); the third an untied
    head.  Tied, the embedding's gradient is the look-up's plus the
    head's."""
    cfg = lfm2_moe.tiny_config(max_seq_len=128, num_experts_held=held,
                               first_expert_held=first, tie_embedding=tied)
    telemetry.reset_metrics()
    main, startup, handles = _build(
        cfg, 11, optimizer=fluid.optimizer.SGD(learning_rate=0.0))
    feed = _batch(cfg, 0)
    names = [p.name for p in main.global_block().all_parameters()]
    assert ("lm_head" in names) == (not tied)
    fetch_list = [handles["loss"], handles["logits"],
                  handles["token_loss"]] + [
        main._grad_name_map.get(n, n + "@GRAD") for n in names] + \
        handles["expert_loads"]
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        params = _reference_params(scope, handles)
        got = exe.run(main, feed=feed, fetch_list=fetch_list)
    lowered = telemetry.registry().get("fused_attention_lowered_total")
    assert lowered.value(shape="gqa", path="flash") == lowered.value() == 1
    assert telemetry.registry().get("gated_short_conv_lowered_total") \
        .value(kernel_size=3) == 4          # two layers, forward and replay
    rcfg = _reference_cfg(cfg)
    ids, labels = _squeeze(feed)
    want_loss, want_tokens, want_grads, want_loads = ref.loss_and_grads(
        params, ids, labels, rcfg)
    assert abs(float(got[0][0]) - float(want_loss)) < 2e-5 * float(want_loss)
    assert abs(float(want_loss) - np.log(cfg.vocab_size)) < 0.1
    close(got[1], ref.logits(params, ids, rcfg), what="logits")
    close(got[2][..., 0], want_tokens, what="per-token loss")
    assert set(names) == set(want_grads)
    for name, grad in zip(names, got[3:3 + len(names)]):
        # a gradient is small against the loss's own rounding where its
        # tensor barely matters (norm scales): hold it to the largest entry
        close(grad, want_grads[name], tol=2e-4, what="d loss / d " + name)
    for load, want_load in zip(got[3 + len(names):], want_loads):
        np.testing.assert_array_equal(load, want_load)
    # the block-by-block reference is the whole reference
    whole, _ = ref.forward_loss(params, ids, labels, rcfg)
    assert abs(float(whole) - float(want_loss)) < 1e-6


def test_adam_steps_the_bias_rule_and_what_a_step_leaves():
    """Two steps of the training program against Adam applied by hand to
    the reference's gradients; after each every selection bias has moved by
    gamma * sign(mean(load) - load); the step's loads and every position's
    loss stay in the scope with no fetch of them."""
    lr, gamma, b1, b2, eps = 1e-3, 0.01, 0.9, 0.999, 1e-8
    cfg = lfm2_moe.tiny_config(bias_update_speed=gamma)
    main, startup, handles = _build(cfg, 5, lr=lr)
    assert [op.type for op in main.global_block().ops][-1] == \
        "moe_bias_update"
    rcfg = _reference_cfg(cfg)
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        params = _reference_params(scope, handles)
        m = {n: jnp.zeros_like(v) for n, v in params.items()}
        v = dict(m)
        for step in range(1, 3):
            feed = _batch(cfg, step)
            loss, = exe.run(main, feed=feed, fetch_list=[handles["loss"]])
            want, tokens, grads, loads = ref.loss_and_grads(
                params, *_squeeze(feed), rcfg)
            assert abs(float(loss[0]) - float(want)) < 1e-4 * float(want)
            close(np.asarray(scope.find_var(handles["token_loss"].name))
                  [..., 0], tokens, tol=1e-4, what="token loss in the scope")
            for n, g in grads.items():
                m[n] = b1 * m[n] + (1 - b1) * g
                v[n] = b2 * v[n] + (1 - b2) * g * g
                rate = lr * np.sqrt(1 - b2 ** step) / (1 - b1 ** step)
                params[n] = params[n] - rate * m[n] / (jnp.sqrt(v[n]) + eps)
            for var, load in zip(handles["expert_loads"], loads):
                np.testing.assert_array_equal(scope.find_var(var.name), load)
            for i, load in enumerate(loads, cfg.num_dense_layers):
                key = "select_bias.%d" % i
                params[key] = params[key] + gamma * jnp.sign(load.mean() -
                                                             load)
            now = _reference_params(scope, handles)
            for n in params:
                close(now[n], params[n], tol=1e-3 if "select" not in n
                      else 1e-6, what="step %d %s" % (step, n))
        bias = np.asarray(now["select_bias.1"])
        assert np.abs(bias).max() > 0


def test_the_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: at a tiny size with 8 experts, the routed parts that
    the four shares ``first_expert_held = 0, 2, 4, 6`` give for one expert
    layer (each told which two experts it holds and routing over all 8, the
    family's epsilon in the weights) add up to what the uncut reference
    gives for the whole layer; there is no shared expert to count once."""
    rng = np.random.default_rng(7)
    T, HID, E, K, WIDTH = 24, 32, 8, 2, 24
    e = "feed_forward.experts."
    p = {e + "router": rng.normal(size=(HID, E), scale=0.5),
         e + "gate": rng.normal(size=(E, HID, WIDTH), scale=0.2),
         e + "up": rng.normal(size=(E, HID, WIDTH), scale=0.2),
         e + "down": rng.normal(size=(E, WIDTH, HID), scale=0.2)}
    p = {n: jnp.asarray(v, jnp.float32) for n, v in p.items()}
    bias = jnp.asarray(rng.normal(size=(E,), scale=0.1), jnp.float32)
    x = jnp.asarray(rng.normal(size=(T, HID)), jnp.float32)
    cfg = {"num_experts": E, "num_experts_per_tok": K,
           "routed_scaling_factor": 1.0}
    with jax.default_matmul_precision("highest"):
        want, want_load = ref.expert_ffn(x[None], p, cfg, "feed_forward",
                                         bias)
    parts, loads = zip(*(decoder_ops.routed_experts(
        x, p[e + "router"], bias,
        *(p[e + n][first:first + 2] for n in ("gate", "up", "down")),
        top_k=K, scale=1.0, first_expert=first)
        for first in (0, 2, 4, 6)))
    close(sum(parts), want[0], what="four shares")
    for load in loads:          # every share routes over the whole model
        np.testing.assert_array_equal(load, want_load)
    # a share alone is NOT the layer, and the reference told it holds two
    # experts gives that share
    assert far(parts[1], want[0])
    held = {n: (v[2:4] if n != e + "router" else v) for n, v in p.items()}
    with jax.default_matmul_precision("highest"):
        share, _ = ref.expert_ffn(
            x[None], held, dict(cfg, num_experts_held=2,
                                first_expert_held=2), "feed_forward", bias)
    close(parts[1], share[0], what="the reference's share")


def test_layers_differ_in_kind_by_position():
    cfg = lfm2_moe.Lfm2MoeConfig()
    kinds = "".join("A" if k == lfm2_moe.ATTENTION else "c"
                    for k in cfg.layer_types)
    assert kinds == "ccAcccAcccAcccAcccAccAcc" and cfg.head_dim == 64
    with pytest.raises(ValueError, match="layer_types"):
        lfm2_moe.Lfm2MoeConfig(num_hidden_layers=2, layer_types=["conv"])
    with pytest.raises(NotImplementedError):
        lfm2_moe.Lfm2MoeConfig(conv_bias=True)
    main, _, _ = _build(lfm2_moe.tiny_config(), 1)
    kinds = [op.type for op in main.global_block().ops
             if op.type in ("gated_short_conv", "fused_attention",
                            "routed_experts")]
    assert kinds == ["gated_short_conv", "fused_attention", "routed_experts",
                     "gated_short_conv", "routed_experts"]


def test_the_configuration_counts_its_parameters():
    """The benchmark's configuration at its published widths, from the
    shapes of the program it builds (nothing is run): 507,820,288."""
    with open(os.path.join(HERE, "..", "benchmarks", "configs",
                           "lfm2-8b-a1b-ep4share.json")) as f:
        params = json.load(f)
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "layer_types",
            "num_dense_layers", "num_attention_heads", "num_key_value_heads",
            "conv_L_cache", "num_experts", "num_experts_per_tok",
            "num_experts_held", "first_expert_held", "tie_embedding")
    cfg = lfm2_moe.Lfm2MoeConfig(max_seq_len=8192,
                                 **{k: params[k] for k in keys})
    main, _, _ = _build(cfg, 1)
    sizes = {p.name: int(np.prod(p.shape))
             for p in main.global_block().all_parameters()}

    def layer(i):
        return sum(n for name, n in sizes.items()
                   if name.startswith("layers.%d." % i))
    H, I, W, L = 2048, 7168, 1792, 3
    conv_mixer = H * 3 * H + H * L + H * H
    attention = H * H + 2 * H * 512 + H * H + 2 * 64
    experts = H * 32 + 8 * 3 * H * W
    assert conv_mixer == 16783360 and attention == 10485888
    assert layer(0) == conv_mixer + 2 * H + 3 * H * I == 60827648
    assert layer(1) == attention + 2 * H + experts == 98635904
    assert layer(2) == layer(3) == layer(4) == \
        conv_mixer + 2 * H + experts == 104933376
    assert sizes["embed_tokens"] == 16384 * H and "lm_head" not in sizes
    assert sum(sizes.values()) == 507820160
    # the selection biases are state, not parameters: 4 x 32 more
    assert sum(sizes.values()) + 4 * 32 == 507820288


def test_the_reference_in_bfloat16_is_the_control_not_the_reference():
    """``dtype`` lowers everything in the reference, the router too: the
    reading the benchmark's limits have to refuse.  It routes every token
    (no drop), lands near the float32 loss, and is not it."""
    cfg = lfm2_moe.tiny_config(max_seq_len=64)
    _, startup, handles = _build(cfg, 7)
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        params = _reference_params(fluid.global_scope(), handles)
    ids, labels = _squeeze(_batch(cfg, 3))
    rcfg = _reference_cfg(cfg)
    want, _, _, want_loads = ref.loss_and_grads(params, ids, labels, rcfg)
    low, _, grads, loads = ref.loss_and_grads(
        params, ids, labels, rcfg, dtype=jnp.bfloat16,
        take=lambda name, grad: float(jnp.linalg.norm(grad.ravel())))
    assert 0 < abs(float(low) - float(want)) < 2e-2 * float(want)
    assert all(isinstance(g, float) and g > 0 for g in grads.values())
    for load, want_load in zip(loads, want_loads):
        assert float(load.sum()) == float(want_load.sum()) == \
            ids.size * cfg.num_experts_per_tok


def test_pure_bf16_step_runs_and_learns():
    """Under pure-bf16 AMP the step runs, its first loss is the untrained
    model's and a batch seen again reads lower."""
    cfg = lfm2_moe.tiny_config(max_seq_len=128)
    opt = fluid.contrib.mixed_precision.decorate(
        fluid.optimizer.AdamOptimizer(learning_rate=1e-2),
        use_pure_bf16=True)
    main, startup, handles = _build(cfg, 3, optimizer=opt)
    feed = _batch(cfg, 1)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [float(exe.run(main, feed=feed,
                                fetch_list=[handles["loss"]])[0][0])
                  for _ in range(4)]
    assert abs(losses[0] - np.log(cfg.vocab_size)) < 0.15
    assert losses[-1] < losses[0]


def test_the_two_copies_of_the_reference_are_one():
    with open(os.path.join(HERE, "..", "paddle_tpu", "models",
                           "lfm2_moe_reference.py")) as f:
        program_side = f.read()
    with open(os.path.join(HERE, "..", "benchmarks", "configs",
                           "lfm2_moe_reference.py")) as f:
        assert f.read() == program_side


def test_the_model_is_exported():
    assert models.lfm2_moe is lfm2_moe and models.lfm2_moe_reference is ref
