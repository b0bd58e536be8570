"""The ``deepseek_v3`` decoder (``models/deepseek_v3.py``) and its ops
against the plain float32 reference (``models/deepseek_v3_reference.py``) on
seeded weights, at tiny sizes on the CPU (Pallas kernels interpreted): 2
heads of nope 16 / rope 8 / v 16, latent 32, 8 experts top-2, 2 shared,
vocabulary 256.

Tolerances: everything here runs in float32 on both sides, so the two
differ by summation order only; 2e-5 relative to a tensor's largest entry
is ten times what the worst case showed and far below what a wrong term
gives (a dropped expert or a bias that weighs moves the output by percents).
"""

import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import models
from paddle_tpu.fluid import layers, telemetry
from paddle_tpu.fluid.backward import append_backward
from paddle_tpu.fluid.ops import decoder_ops, pallas_ops
from paddle_tpu.models import deepseek_v3_reference as ref

TOL = 2e-5


def close(got, want, tol=TOL, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert err <= tol, "%s: relative error %.3g > %.3g" % (what, err, tol)


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


def weighted_sum(out, w):
    return layers.reduce_sum(out * w)


# -- rms_norm, rotary ----------------------------------------------------------

def test_rms_norm_forward_and_gradient():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 5, 32)).astype(np.float32)
    w = rng.normal(size=x.shape).astype(np.float32)
    scale = rng.normal(size=(32,)).astype(np.float32)

    def build():
        xv = data("x", x.shape)
        y = layers.rms_norm(xv, epsilon=1e-5, param_attr=fluid.ParamAttr(
            name="s", initializer=fluid.initializer.NumpyArrayInitializer(
                scale)))
        s = fluid.default_main_program().global_block().var("s")
        return [y], [xv, s], weighted_sum(y, data("w", w.shape))

    (y,), (dx, ds) = run_program(build, {"x": x, "w": w})
    want, vjp = jax.vjp(lambda a, b: ref.rms_norm(a, b, 1e-5), x, scale)
    wdx, wds = vjp(jnp.asarray(w))
    close(y, want, what="rms_norm")
    close(dx, wdx, what="d rms_norm / dx")
    close(ds, wds, what="d rms_norm / dscale")


@pytest.mark.parametrize("heads", [1, 2])
def test_rotary_forward_and_gradient(heads):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 16, heads, 8)).astype(np.float32)
    w = rng.normal(size=x.shape).astype(np.float32)

    def build():
        xv = data("x", x.shape)
        y = layers.rotary_embedding(xv, theta=50000.0)
        return [y], [xv], weighted_sum(y, data("w", w.shape))

    (y,), (dx,) = run_program(build, {"x": x, "w": w})
    want, vjp = jax.vjp(lambda a: ref.rotary(a, 50000.0), x)
    close(y, want, what="rotary")
    close(dx, vjp(jnp.asarray(w))[0], what="d rotary")
    # position 0 is the de-interleave alone; a rotation keeps every norm
    np.testing.assert_allclose(
        y[:, 0], np.concatenate([x[:, 0, :, 0::2], x[:, 0, :, 1::2]], -1))
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


# -- the flash kernels at D_qk != D_v with a shared rotary key head ------------

B, H, S, NOPE, ROPE, DV = 2, 2, 256, 16, 8, 32


def _mla_arrays(seed=0):
    rng = np.random.default_rng(seed)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (arr(B * H, S, NOPE), arr(B * H, S, NOPE), arr(B * H, S, DV),
            arr(B * H, S, ROPE), arr(B, S, ROPE), arr(B * H, S, DV))


def _mla_composed(q, k, v, qr, kr, causal, bias=None):
    qq, kk = pallas_ops._compose_rope(q, k, (qr, kr))
    return pallas_ops._reference_attention(
        qq, kk, v, bias, (NOPE + ROPE) ** -0.5, causal=causal)


def test_flash_kernels_with_a_shared_rotary_key_head():
    """Forward and the three backward outputs (dQ with its rotary part, dK
    with the rotary part summed over the heads that share it, dV) against
    the composition that repeats the shared head and appends it.  The
    kernels take the pair under the causal mask alone."""
    q, k, v, qr, kr, g = _mla_arrays()
    with pytest.raises(ValueError, match="rotary pair"):
        pallas_ops.flash_attention(q, k, v, None, 1.0, False, (qr, kr))

    def flash(q, k, v, qr, kr):
        return pallas_ops.flash_attention(
            q, k, v, None, (NOPE + ROPE) ** -0.5, True, (qr, kr))
    out, vjp = jax.vjp(flash, q, k, v, qr, kr)
    want, want_vjp = jax.vjp(
        lambda *a: _mla_composed(*a, causal=True), q, k, v, qr, kr)
    assert out.shape == (B * H, S, DV)
    close(out, want, what="flash forward")
    for name, got, exp in zip(("dq", "dk", "dv", "dq_rope", "dk_rope"),
                              vjp(g), want_vjp(g)):
        close(got, exp, what=name)


def test_flash_kernels_take_another_head_size_for_v_alone():
    q, k, v, _, _, g = _mla_arrays(1)
    out, vjp = jax.vjp(lambda *a: pallas_ops.flash_attention(
        *a, None, 0.25, True), q, k, v)
    want, want_vjp = jax.vjp(lambda *a: pallas_ops._reference_attention(
        *a, None, 0.25, causal=True), q, k, v)
    close(out, want, what="forward")
    for got, exp in zip(vjp(g), want_vjp(g)):
        close(got, exp, what="backward")


@pytest.mark.parametrize("seq,causal,biased,path", [
    (128, True, False, "flash"), (136, True, False, "composition"),
    (128, False, False, "composition"), (128, True, True, "composition")])
def test_fused_attention_op_with_a_rotary_pair(seq, causal, biased, path):
    """Through the Fluid op and its grad op: the flash path hands the pair
    to the kernels and reads the LSE back; a non-tileable length, an op
    without the causal mask and one with a bias compose one head size; all
    give the reference's numbers."""
    rng = np.random.default_rng(2)
    shapes = {"q": (B, H, seq, NOPE), "k": (B, H, seq, NOPE),
              "v": (B, H, seq, DV), "qr": (B, H, seq, ROPE),
              "kr": (B, 1, seq, ROPE), "w": (B, H, seq, DV)}
    if biased:
        shapes["bias"] = (B, 1, seq, seq)
    feed = {n: rng.normal(size=s).astype(np.float32)
            for n, s in shapes.items()}
    telemetry.reset_metrics()

    def build():
        v = {n: data(n, s) for n, s in shapes.items()}
        out = layers.fused_attention(
            v["q"], v["k"], v["v"], attn_bias=v.get("bias"),
            scale=(NOPE + ROPE) ** -0.5, causal=causal, q_rope=v["qr"],
            k_rope=v["kr"])
        assert out.shape == shapes["w"]
        return [out], [v[n] for n in ("q", "k", "v", "qr", "kr")], \
            weighted_sum(out, v["w"])

    (out,), grads = run_program(build, feed)
    flat = {n: jnp.asarray(a).reshape((-1,) + a.shape[2:])
            for n, a in feed.items()}
    want, vjp = jax.vjp(
        lambda q, k, v, qr, kr: _mla_composed(
            q, k, v, qr, kr, causal,
            jnp.repeat(flat["bias"], H, axis=0) if biased else None),
        flat["q"], flat["k"], flat["v"], flat["qr"], flat["kr"])
    close(out.reshape(want.shape), want, what="op forward")
    for name, got, exp in zip(("q", "k", "v", "qr", "kr"), grads,
                              vjp(flat["w"])):
        close(np.asarray(got).reshape(exp.shape), exp, what="d" + name)
    counter = telemetry.registry().get("fused_attention_lowered_total")
    assert counter.value(shape="mla", path=path) >= 1
    assert counter.value(shape="mha") == 0


# -- the routed-expert layer -----------------------------------------------------

T, HID, E, K, WIDTH = 24, 32, 8, 2, 24
SCALE = 2.446


def _expert_params(seed=0, router=None, bias=None, T=T, E=E):
    rng = np.random.default_rng(seed)
    p = {"mlp.experts.router": rng.normal(size=(HID, E), scale=0.5),
         "mlp.experts.gate": rng.normal(size=(E, HID, WIDTH), scale=0.2),
         "mlp.experts.up": rng.normal(size=(E, HID, WIDTH), scale=0.2),
         "mlp.experts.down": rng.normal(size=(E, WIDTH, HID), scale=0.2)}
    for n in ("gate_proj", "up_proj"):
        p["mlp.shared_experts." + n] = rng.normal(size=(HID, 2 * WIDTH),
                                                  scale=0.2)
    p["mlp.shared_experts.down_proj"] = rng.normal(size=(2 * WIDTH, HID),
                                                   scale=0.2)
    if router is not None:
        p["mlp.experts.router"] = router
    p = {n: jnp.asarray(v, jnp.float32) for n, v in p.items()}
    p["select_bias"] = jnp.zeros(E) if bias is None else jnp.asarray(bias)
    x = jnp.asarray(rng.normal(size=(T, HID)), jnp.float32)
    return p, x


def _cfg(held=E, first=0, E=E):
    return {"num_experts_per_tok": K, "routed_scaling_factor": SCALE,
            "n_routed_experts": E, "n_routed_experts_held": held,
            "first_expert_held": first}


def _op(p, x, first=0, held=None):
    e = "mlp.experts."
    held = p[e + "router"].shape[1] if held is None else held
    return decoder_ops.routed_experts(
        x, p[e + "router"], p["select_bias"],
        *(p[e + n][first:first + held] for n in ("gate", "up", "down")),
        top_k=K, scale=SCALE, first_expert=first)


def _reference_routed(p, x, first=0, held=None):
    """The reference layer's routed part alone (its shared experts off)."""
    zero = {n: jnp.zeros_like(v) if "shared" in n else v
            for n, v in p.items()}
    e = "mlp.experts."
    experts = p[e + "router"].shape[1]
    held = experts if held is None else held
    if held < experts:
        zero.update({e + n: zero[e + n][first:first + held]
                     for n in ("gate", "up", "down")})
    with jax.default_matmul_precision("highest"):
        y, load = ref.expert_ffn(x[None], zero, _cfg(held, first, experts),
                                 "mlp", p["select_bias"])
    return y[0], load


@pytest.mark.parametrize("case", ["random", "one_expert_takes_all", "ties",
                                  "bias_chooses"])
def test_routed_experts_against_the_reference(case):
    """No capacity and no drop: ``one_expert_takes_all`` sends every token
    to expert 3 first (three times the even share of rows) and all are
    served.  ``ties``: equal scores choose the lower indices.
    ``bias_chooses``: a bias that lifts the two worst experts changes who
    is chosen and leaves the weights those of the scores alone."""
    router = bias = None
    if case == "one_expert_takes_all":
        router = np.random.default_rng(5).normal(size=(HID, E), scale=0.01)
        bias = np.where(np.arange(E) == 3, 5.0, 0.0)
    elif case == "ties":
        router = np.zeros((HID, E))
    elif case == "bias_chooses":
        bias = np.where(np.arange(E) >= 6, 10.0, 0.0)
    p, x = _expert_params(3, router, bias)
    (out, load), vjp = jax.vjp(lambda x_, p_: _op(p_, x_), x, p)
    (want, want_load), want_vjp = jax.vjp(
        lambda x_, p_: _reference_routed(p_, x_), x, p)
    close(out, want, what=case)
    np.testing.assert_array_equal(load, want_load)
    assert float(load.sum()) == T * K
    g = jnp.asarray(np.random.default_rng(4).normal(size=out.shape),
                    jnp.float32)
    (dx, dp), (wdx, wdp) = vjp((g, jnp.zeros(E))), \
        want_vjp((g, jnp.zeros(E)))
    close(dx, wdx, what=case + " dx")
    for n in ("router", "gate", "up", "down"):
        close(dp["mlp.experts." + n], wdp["mlp.experts." + n],
              what=case + " d" + n)
    idx, weight, _ = decoder_ops.route(x, p["mlp.experts.router"],
                                       p["select_bias"], K, SCALE)
    np.testing.assert_allclose(weight.sum(-1), SCALE, rtol=1e-6)
    if case == "one_expert_takes_all":
        assert float(load[3]) == T
    if case == "ties":
        np.testing.assert_array_equal(np.sort(idx, -1),
                                      np.tile([0, 1], (T, 1)))
    if case == "bias_chooses":
        np.testing.assert_array_equal(np.sort(idx, -1),
                                      np.tile([6, 7], (T, 1)))
        scores = jax.nn.sigmoid(x @ p["mlp.experts.router"])
        chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
        np.testing.assert_allclose(
            weight, chosen / chosen.sum(-1, keepdims=True) * SCALE,
            rtol=1e-5)


@pytest.mark.parametrize("held", [1, 2, 8])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """THE SHARE TEST: the routed parts that all E / held shares give, each
    told which experts it holds and routing over all E, plus the shared
    expert counted once, equal the uncut reference layer."""
    p, x = _expert_params(7)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(x[None], p, _cfg(), "mlp",
                                 p["select_bias"])
        shared = ref.swiglu(x, p, "mlp.shared_experts")
    parts, loads = zip(*(_op(p, x, first, held)
                         for first in range(0, E, held)))
    close(sum(parts) + shared, want[0], what="%d shares" % (E // held))
    for load in loads:      # every share routes over the whole model
        np.testing.assert_array_equal(load, loads[0])
    # and a share alone is NOT the layer: it leaves the absent experts out
    if held < E:
        assert np.abs(np.asarray(parts[0] + shared - want[0])).max() > 1e-2


def test_routed_experts_layer_is_told_what_it_holds():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = data("x", (4, 32))
        out, load, bias = layers.routed_experts(x, 64, 6, 24, num_held=8,
                                                first_expert=8)
        block = fluid.default_main_program().global_block()
        op = block.ops[-1]
        assert op.type == "routed_experts" and op.attr("first_expert") == 8
        assert block.var(op.input("WGate")[0]).shape == (8, 32, 24)
        assert block.var(op.input("RouterW")[0]).shape == (32, 64)
        assert load.shape == bias.shape == (64,) and bias.stop_gradient
        with pytest.raises(ValueError):
            layers.routed_experts(x, 64, 6, 24, num_held=8, first_expert=60)


# -- the rungs of a share's row buffers ------------------------------------------

# 1024 tokens, 2 of 16 experts held: 2048 assignments, an even router sends
# 256 here, so the buffers have the rungs 512 | 2048
RT, RE, RHELD, RUNG = 1024, 16, 2, 512


def test_the_rungs_come_from_shapes():
    rungs = decoder_ops._rungs
    assert rungs(RT, K, RHELD, RE) == (RUNG, RT * K)
    # the Moonlight cell: 4096 tokens, 6 of 64 each, 8 held
    assert rungs(4096, 6, 8, 64) == (6144, 24576)
    # an uncut layer, and small shapes: one rung, the worst case
    assert rungs(4096, 6, 64, 64) == (24576,)
    assert rungs(RT, K, RE, RE) == (RT * K,)
    assert rungs(T, K, 2, E) == (T * K,)
    assert rungs(2 * 128, 2, 2, 8) == (512,)
    for shape in [(4096, 6, 8, 64), (4096, 8, 32, 256), (333, 3, 1, 40)]:
        ladder = rungs(*shape)
        assert ladder[-1] == shape[0] * shape[1]
        assert all(R % 512 == 0 for R in ladder[:-1])
        assert all(2 * R < ladder[-1] for R in ladder[:-1])


def _share_params(both, one, bias=None, seed=11):
    """Seeded weights and ``RT`` tokens of which the first ``both`` choose
    the held experts 0 AND 1, the next ``one`` expert 0 and an absent one,
    the rest two absent ones: ``2 * both + one`` rows come.  (Experts 0 and
    1 score the tokens' first two features alone, set to +-10.)"""
    p, x = _expert_params(seed, T=RT, E=RE)
    router = np.array(p["mlp.experts.router"]) * 0.4
    router[:2], router[:, :2] = 0.0, 0.0
    router[0, 0] = router[1, 1] = 1.0
    x = np.array(x)
    x[:, :2] = -10.0
    x[:both + one, 0] = 10.0
    x[:both, 1] = 10.0
    p["mlp.experts.router"] = jnp.asarray(router)
    if bias is not None:
        p["select_bias"] = jnp.asarray(bias, jnp.float32)
    return p, jnp.asarray(x)


ALL_HELD = np.where(np.arange(RE) < RHELD, 30.0, 0.0)


@pytest.mark.parametrize("both,one,bias,rows", [
    (100, 50, None, 250), (200, 112, None, RUNG), (200, 113, None, RUNG + 1),
    (0, 0, None, 0), (0, 0, ALL_HELD, RT * K)],
    ids=["well_under", "exactly_the_rung", "one_row_more", "no_row",
         "every_assignment_held"])
def test_the_rung_follows_the_rows_that_came(both, one, bias, rows):
    """Output and every gradient against the float32 reference with the
    rung forced each way by the data: the first rung up to exactly its 512
    rows, the last from 513 on, and every assignment of every token held
    here (a selection bias lifts the two held experts over all others: the
    last rung's forward AND its recomputing backward, no token dropped)."""
    p, x = _share_params(both, one, bias)
    (out, load), vjp = jax.vjp(
        lambda x_, p_: _op(p_, x_, held=RHELD), x, p)
    (want, want_load), want_vjp = jax.vjp(
        lambda x_, p_: _reference_routed(p_, x_, held=RHELD), x, p)
    assert float(load[:RHELD].sum()) == rows
    np.testing.assert_array_equal(load, want_load)
    close(out, want, what="out")
    g = jnp.asarray(np.random.default_rng(4).normal(size=out.shape),
                    jnp.float32)
    (dx, dp), (wdx, wdp) = vjp((g, jnp.zeros(RE))), \
        want_vjp((g, jnp.zeros(RE)))
    close(dx, wdx, what="dx")
    e = "mlp.experts."
    for n in ("router", "gate", "up", "down"):
        want_grad = wdp[e + n] if n == "router" else wdp[e + n][:RHELD]
        got = dp[e + n] if n == "router" else dp[e + n][:RHELD]
        if rows == 0:
            assert not np.asarray(got).any() and not np.asarray(want_grad).any()
        else:
            close(got, want_grad, what="d" + n)


def _rung_operands(p, x):
    e = "mlp.experts."
    weight, (plan, _) = decoder_ops._route_and_plan(
        x, p[e + "router"], p["select_bias"], top_k=K, scale=SCALE,
        first_expert=0, n_held=RHELD)
    return (x, weight) + tuple(p[e + n][:RHELD]
                               for n in ("gate", "up", "down")), plan


@pytest.mark.parametrize("rows,compute,tol", [
    (250, jnp.float32, 1e-6), (RUNG, jnp.float32, 1e-6),
    (250, jnp.bfloat16, 1e-2)])
def test_the_two_rungs_agree(rows, compute, tol):
    """On rows that fit the first rung, both rungs give the same numbers to
    float32 round-off, forward and backward: the first from the rows its
    forward kept, the last by ``jax.vjp`` through every row.  Under
    pure-bf16 AMP as the Moonlight cell runs it (``x`` float32, the rows
    and the expert weights bfloat16) they agree to bfloat16's: the first
    rung keeps ``ys`` as the matmul gave it and widens the sum."""
    p, x = _share_params(100, rows - 200)
    diff, plan = _rung_operands(p, x)
    diff = diff[:2] + tuple(w.astype(compute) for w in diff[2:])
    dtypes = (compute, None, "silu")
    g = jnp.asarray(np.random.default_rng(5).normal(size=x.shape),
                    jnp.float32)
    first, kept = decoder_ops._first_rung(RUNG, dtypes, *diff, plan)
    last, vjp = jax.vjp(
        lambda *a: decoder_ops._every_row(dtypes, *a, plan), *diff)
    assert [a.shape[0] for a in kept] == [RUNG] * 4
    assert {a.dtype for a in kept} == {jnp.dtype(compute)}
    assert first.dtype == last.dtype == x.dtype
    close(first, last, tol=tol, what="forward")
    for name, got, want in zip(
            ("dx", "dweight", "dgate", "dup", "ddown"),
            decoder_ops._first_rung_backward(RUNG, dtypes, *diff, plan,
                                             kept, g),
            vjp(g)):
        assert got.dtype == want.dtype and got.shape == want.shape
        close(got, want, tol=tol, what=name)


def _conditionals(fn, *args):
    """The ``cond`` equations of the traced program, the Pallas calls
    left out: a call is a ``cond`` on ``platform_index`` (Mosaic or the
    interpreter: ``pallas_ops._pallas_call``) and a kernel's ``pl.when``
    a ``cond`` of its own."""
    def count(jaxpr):
        found, platform = 0, set()
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "platform_index":
                platform.update(eqn.outvars)
                continue
            if eqn.primitive.name == "pallas_call" or (
                    eqn.primitive.name == "cond" and
                    eqn.invars[0] in platform):
                continue
            found += eqn.primitive.name == "cond"
            for param in eqn.params.values():
                for sub in param if isinstance(param, (list, tuple)) \
                        else (param,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        found += count(sub)
        return found
    return count(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("tokens,experts,held,conds,rows", [
    (RT, RE, RHELD, 2, "512|2048"), (RT, RE, RE, 0, "2048"),
    (T, E, 2, 0, "48"), (T, E, E, 0, "48")])
def test_one_rung_traces_no_conditional(tokens, experts, held, conds, rows):
    """A share with two rungs traces one forward and one backward
    conditional; an uncut layer (``held == E``) and the tiny shapes trace
    none: their program is the one from before there were rungs.  The
    counter says what was traced."""
    p, x = _expert_params(2, T=tokens, E=experts)
    telemetry.reset_metrics()

    def loss(x_, p_):
        return _op(p_, x_, held=held)[0].sum()
    assert _conditionals(jax.grad(loss, argnums=(0, 1)), x, p) == conds
    counter = telemetry.registry().get("moe_experts_lowered_total")
    assert counter.value(path="ragged_dot", rows=rows) == 1
    assert counter.value(path="ragged_dot") == 1


# -- the token side: the sums by token, a Pallas kernel (interpreted here) -------

def _composed_sum(rows, slot_of, held, weight=None):
    """The composition ``row_sum`` replaces: one gather of ``[T, H]`` a
    choice, masked, summed over ``k`` in order in float32."""
    total = 0
    for j in range(slot_of.shape[1]):
        chosen = jnp.where(held[:, j, None], rows[slot_of[:, j]], 0) \
            .astype(jnp.float32)
        total = total + (chosen if weight is None
                         else chosen * weight[:, j, None])
    return total


def _sum_in_row_order(rows, token_of, n_live, tokens, weight=None):
    """``row_sum`` as a loop: float32, the rows added in their order."""
    rows = np.asarray(rows, np.float32)
    out = np.zeros((tokens, rows.shape[1]), np.float32)
    for r in range(n_live):
        row = rows[r] if weight is None else \
            rows[r] * np.float32(weight[r])
        out[token_of[r]] = out[token_of[r]] + row
    return out


def _nan_past(rows, n_live):
    return jnp.where(jnp.arange(rows.shape[0])[:, None] < n_live, rows,
                     jnp.nan).astype(rows.dtype)


@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("held_share", [0.0, 0.4, 1.0],
                         ids=["no_token_held", "mixed", "every_assignment"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_row_sum_is_the_masked_sum_by_token(weighted, held_share, dtype):
    """``row_sum`` over a row buffer sorted as ``_plan`` sorts it (the
    held assignments first) is the loop that adds the live rows in their
    order (to the bit unweighted; weighted, a multiply-add may round once
    where the loop rounds twice), and the composition it replaces
    (``_composed_sum``, a choice at a time) to float32 round-off: the same
    products, added in another order.  Tokens with no held assignment read zeros, tokens with
    all ``k`` held sum them all, the rows past the live ones are NaN (never
    read), and a buffer of no whole number of 8-row groups is padded."""
    rng = np.random.default_rng(2)
    tokens, k = 40, 3
    held = rng.random((tokens, k)) < held_share
    held[:3] = False                   # tokens with none held
    if held_share:
        held[3:6] = True               # ... and with all k
    idx = np.where(held, rng.integers(0, 2, (tokens, k)), 2)
    order, token_of, slot_of, _, sizes = decoder_ops._plan(
        jnp.asarray(idx, jnp.int32), 0, 2)
    n_live = int(sizes.sum())
    R = tokens * k - 3                 # the live rows are a prefix
    order, token_of = order[:R], token_of[:R]
    slot_of = jnp.minimum(slot_of, R - 1)
    rows = _nan_past(jnp.asarray(rng.normal(size=(R, HID)), dtype), n_live)
    weight = jnp.asarray(rng.random((tokens, k)), jnp.float32) \
        if weighted else None
    w_row = None if weight is None else \
        decoder_ops._row_weights(weight, order, jnp.asarray(held))
    got = jax.jit(pallas_ops.row_sum, static_argnums=3)(
        rows, token_of, jnp.int32(n_live), tokens, w_row)
    assert got.dtype == jnp.float32 and got.shape == (tokens, HID)
    assert np.isfinite(np.asarray(got)).all()
    in_order = _sum_in_row_order(rows, np.asarray(token_of), n_live, tokens,
                                 None if w_row is None else np.asarray(w_row))
    if weighted:        # a multiply-add may be fused where the loop rounds
        close(got, in_order, tol=1e-6, what="against the loop")
    else:
        np.testing.assert_array_equal(np.asarray(got), in_order)
    want = jax.jit(_composed_sum)(rows, slot_of, jnp.asarray(held), weight)
    close(got, want, tol=1e-6, what="against the composition")
    assert not np.asarray(got)[~held.any(axis=1)].any()


def _leaves_nan_past_the_groups(monkeypatch):
    """A grouped matmul that writes NaN past its last group, as a chip's
    may leave anything there: the token side must never read those rows."""
    real = decoder_ops._grouped

    def grouped(a, w, group_sizes, acc):
        return _nan_past(real(a, w, group_sizes, acc), group_sizes.sum())
    monkeypatch.setattr(decoder_ops, "_grouped", grouped)


def _composed_token_side(monkeypatch):
    """The sums by token as XLA's segment sum of the live rows."""
    def sums(rows, token_of, n_live, tokens, dtype, w_row=None):
        rows = decoder_ops._narrowed(rows, dtype).astype(jnp.float32)
        if w_row is not None:
            rows = rows * w_row[:, None]
        live = jnp.arange(rows.shape[0])[:, None] < n_live
        return jax.ops.segment_sum(jnp.where(live, rows, 0), token_of,
                                   tokens).astype(dtype)
    monkeypatch.setattr(decoder_ops, "_sum_rows", sums)


@pytest.mark.parametrize("path,both,one,bias", [
    ("first_rung", 100, 50, None), ("every_row", 200, 113, None),
    ("every_row", 0, 0, ALL_HELD)],
    ids=["first_rung", "every_row", "every_assignment_held"])
def test_the_sums_by_token_are_the_composition_they_replace(
        monkeypatch, path, both, one, bias):
    """The layer with two rungs, forward and every gradient, with the
    kernel's sums and with XLA's segment sum of the same rows, to float32
    round-off: on a step that fits the first rung, one that takes the last
    (``_every_row``) and one where every assignment is held.  The grouped
    matmul leaves NaN past its last group, so no sum reads there."""
    p, x = _share_params(both, one, bias)
    _leaves_nan_past_the_groups(monkeypatch)
    g = jnp.asarray(np.random.default_rng(6).normal(size=x.shape),
                    jnp.float32)

    def layer():
        (out, load), vjp = jax.vjp(
            lambda x_, p_: _op(p_, x_, held=RHELD), x, p)
        return jax.tree.map(np.asarray, (out, load, vjp((g, jnp.zeros(RE)))))
    telemetry.reset_metrics()
    got = layer()
    counter = telemetry.registry().get("moe_token_rows_lowered_total")
    rung = {"first_rung": RUNG, "every_row": RT * K}[path]
    assert (float(got[1][:RHELD].sum()) > RUNG) == (path == "every_row")
    for form in ("combine", "dx"):
        assert counter.value(form=form, rows=str(rung)) >= 1
    _composed_token_side(monkeypatch)
    want = layer()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(a).all()
        close(a, b, tol=1e-6)


# cell -> (T, H, E, top_k, held, I, scoring, act, the rungs' rows)
CELL_LAYERS = {
    "moonlight_ep8share_s4096_train":
        (4096, 2048, 64, 6, 8, 1408, "sigmoid", "silu", ("6144", "24576")),
    "lfm2_ep4share_s8192_train":
        (8192, 2048, 32, 4, 8, 1792, "sigmoid", "silu", ("32768",)),
    "smallthinker_ep8share_s16384_train":
        (16384, 2560, 64, 6, 8, 768, "softmax", "relu", ("24576", "98304")),
}


@pytest.mark.parametrize("cell", sorted(CELL_LAYERS))
def test_every_rung_of_the_cells_layers_lowers_both_sums(cell):
    """At each expert cell's shapes (traced, not run) every rung of the
    layer lowers the combine's sum and the dispatch's backward:
    ``moe_token_rows_lowered_total{form, rows}``."""
    T, H, E, k, held, inner, scoring, act, rungs = CELL_LAYERS[cell]
    assert tuple(str(R) for R in decoder_ops._rungs(T, k, held, E)) == rungs
    state = types.SimpleNamespace(amp_dtype="bfloat16", amp_keep=True)

    def loss(x, rw, wg, wu, wd, bias):
        out, load = decoder_ops.routed_experts(
            x, rw, bias, wg, wu, wd, top_k=k, scale=1.0, first_expert=0,
            state=state, scoring_func=scoring, hidden_act=act)
        return out.astype(jnp.float32).sum() + load.sum() * 0

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)
    telemetry.reset_metrics()
    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), arr(T, H),
                   arr(H, E), arr(held, H, inner), arr(held, H, inner),
                   arr(held, inner, H), arr(E))
    counter = telemetry.registry().get("moe_token_rows_lowered_total")
    for rows in rungs:
        for form in ("combine", "dx"):
            assert counter.value(form=form, rows=rows) >= 1, (form, rows)
    assert counter.value() == counter.value(form="combine") + \
        counter.value(form="dx")


# -- the whole model -------------------------------------------------------------

def _reference_cfg(cfg):
    keys = ("num_hidden_layers", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "rms_norm_eps", "first_k_dense_replace",
            "n_routed_experts", "n_routed_experts_held", "first_expert_held",
            "num_experts_per_tok", "routed_scaling_factor")
    return {k: getattr(cfg, k) for k in keys}


def _reference_params(scope, handles):
    """The scope's parameters under the reference's names."""
    block = handles["loss"].block.program.global_block()
    # copies: the step donates the scope's own buffers
    params = {p.name: jnp.asarray(np.array(scope.find_var(p.name)))
              for p in block.all_parameters()}
    dense = handles["config"].first_k_dense_replace
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


@pytest.mark.parametrize("held,first,experts,top_k,batch,rows", [
    (8, 0, 8, 2, 2, "512"), (2, 4, 8, 2, 2, "512"),
    (2, 4, 32, 4, 4, "512|2048")])
def test_model_loss_and_every_gradient_through_executor(
        held, first, experts, top_k, batch, rows):
    """S=128 tiles, so attention runs the (interpreted) flash kernels; the
    second case is a share: 2 of 8 experts held, routed over all 8; the
    third a share whose row buffers have two rungs (512 tokens choosing 4
    of 32 experts, 2 held: 128 of 2048 assignments expected): the forward
    op hands its first rung's rows to the grad op through ``Kept``, and
    the step holds one forward and one backward conditional."""
    cfg = models.deepseek_v3.tiny_config(
        max_seq_len=128, n_routed_experts_held=held, first_expert_held=first,
        n_routed_experts=experts, num_experts_per_tok=top_k)
    telemetry.reset_metrics()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        handles = models.deepseek_v3.build_train(
            cfg, optimizer=fluid.optimizer.SGD(learning_rate=0.0))
    feed = _batch(cfg, 0, batch)
    names = [p.name for p in main.global_block().all_parameters()]
    fetch_list = [handles["loss"]] + [
        main._grad_name_map.get(n, n + "@GRAD") for n in names] + \
        handles["expert_loads"]
    with fluid.scope_guard(fluid.Scope()) as _:
        scope = fluid.global_scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        params = _reference_params(scope, handles)
        got = exe.run(main, feed=feed, fetch_list=fetch_list)
        hlo = exe.compiled_hlo(main, feed, fetch_list)
    # the forward op and its grad op each trace the layer's rows
    counter = telemetry.registry().get("moe_experts_lowered_total")
    assert counter.value(path="ragged_dot", rows=rows) == \
        counter.value(path="ragged_dot") >= 2
    # the interpreted sums by token hold conditionals of their own
    assert len(re.findall(r" conditional\((?![^\n]*moe_row_)", hlo)) == \
        2 * ("|" in rows)
    want_loss, want_grads, want_loads = ref.loss_and_grads(
        params, *_squeeze(feed), _reference_cfg(cfg))
    assert abs(float(got[0][0]) - float(want_loss)) < 2e-5 * float(want_loss)
    assert abs(float(want_loss) - np.log(cfg.vocab_size)) < 0.1
    assert set(names) == set(want_grads)
    for name, grad in zip(names, got[1:1 + len(names)]):
        # a gradient is small against the loss's own rounding where its
        # tensor barely matters (norm scales): hold it to the largest entry
        close(grad, want_grads[name], tol=2e-4, what="d loss / d " + name)
    for load, want_load in zip(got[1 + len(names):], want_loads):
        np.testing.assert_array_equal(load, want_load)
    # the block-by-block reference is the whole reference
    whole, _ = ref.forward_loss(params, *_squeeze(feed), _reference_cfg(cfg))
    assert abs(float(whole) - float(want_loss)) < 1e-6


def test_three_adam_steps_and_the_bias_rule():
    """Three steps of the training program against Adam applied by hand to
    the reference's gradients; after each step every selection bias has
    moved by gamma * sign(mean(load) - load), and nothing else moved it."""
    lr, gamma, b1, b2, eps = 1e-3, 0.01, 0.9, 0.999, 1e-8
    cfg = models.deepseek_v3.tiny_config(bias_update_speed=gamma)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        handles = models.deepseek_v3.build_train(cfg, lr=lr)
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
        for step in range(1, 4):
            feed = _batch(cfg, step)
            loss, = exe.run(main, feed=feed, fetch_list=[handles["loss"]])
            want, grads, loads = ref.loss_and_grads(params, *_squeeze(feed),
                                                    rcfg)
            assert abs(float(loss[0]) - float(want)) < 1e-4 * float(want), \
                step
            for n, g in grads.items():
                m[n] = b1 * m[n] + (1 - b1) * g
                v[n] = b2 * v[n] + (1 - b2) * g * g
                rate = lr * np.sqrt(1 - b2 ** step) / (1 - b1 ** step)
                params[n] = params[n] - rate * m[n] / (jnp.sqrt(v[n]) + eps)
            for var, load in zip(handles["expert_loads"], loads):
                # the step's load stays in the scope, with no fetch of it
                np.testing.assert_array_equal(scope.find_var(var.name), load)
            for i, load in enumerate(loads, cfg.first_k_dense_replace):
                key = "select_bias.%d" % i
                params[key] = params[key] + gamma * jnp.sign(load.mean() -
                                                             load)
            now = _reference_params(scope, handles)
            for n in params:
                close(now[n], params[n], tol=1e-3 if "select" not in n
                      else 1e-6, what="step %d %s" % (step, n))
        bias = np.asarray(now["select_bias.1"])
        assert np.abs(bias).max() > 0 and \
            np.allclose(np.abs(bias) / gamma, np.round(np.abs(bias) / gamma))


def test_the_reference_in_bfloat16_is_the_control_not_the_reference():
    """``dtype`` lowers everything in the reference, the router too: the
    reading the benchmark's limits have to refuse.  It routes every token
    (no drop), lands near the float32 loss, and is not it."""
    cfg = models.deepseek_v3.tiny_config(max_seq_len=64)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        handles = models.deepseek_v3.build_train(cfg)
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        params = _reference_params(fluid.global_scope(), handles)
    ids, labels = _squeeze(_batch(cfg, 3))
    rcfg = _reference_cfg(cfg)
    want, _, want_loads = ref.loss_and_grads(params, ids, labels, rcfg)
    low, grads, loads = ref.loss_and_grads(
        params, ids, labels, rcfg, dtype=jnp.bfloat16,
        take=lambda name, grad: float(jnp.linalg.norm(grad.ravel())))
    assert 0 < abs(float(low) - float(want)) < 2e-2 * float(want)
    assert all(isinstance(g, float) and g > 0 for g in grads.values())
    for load, want_load in zip(loads, want_loads):
        assert float(load.sum()) == float(want_load.sum()) == \
            ids.size * cfg.num_experts_per_tok


def test_the_two_copies_of_the_reference_are_one():
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "paddle_tpu", "models",
                           "deepseek_v3_reference.py")) as f:
        program_side = f.read()
    with open(os.path.join(here, "..", "benchmarks", "configs",
                           "deepseek_v3_reference.py")) as f:
        assert f.read() == program_side


def test_pure_bf16_keeps_the_router_float32():
    """Under pure-bf16 AMP the expert matmuls take bf16 operands and the
    router's scores and weights stay float32: the choice of experts is that
    of float32 scores of the bf16 activations, not of bf16 scores."""
    from paddle_tpu.fluid.lowering import ExecState
    p, x = _expert_params(9)
    state = ExecState(None, 0, None, amp_dtype="bfloat16", amp_keep=True)
    e = "mlp.experts."
    xb = x.astype(jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda: decoder_ops.routed_experts(
        xb, p[e + "router"], p["select_bias"], p[e + "gate"], p[e + "up"],
        p[e + "down"], top_k=K, scale=SCALE, first_expert=0, state=state))()
    lines = str(jaxpr).splitlines()
    ragged = [l for l in lines if "= ragged_dot_general[" in l]
    assert len(ragged) == 3 and all(":bf16[" in l for l in ragged), ragged
    top_k = [l for l in lines if "= top_k[" in l]
    assert top_k and all(":f32[" in l and "bf16" not in l for l in top_k)
    out, _ = decoder_ops.routed_experts(
        xb, p[e + "router"], p["select_bias"], p[e + "gate"], p[e + "up"],
        p[e + "down"], top_k=K, scale=SCALE, first_expert=0, state=state)
    assert out.dtype == jnp.bfloat16
    idx, _, _ = decoder_ops.route(xb, p[e + "router"], p["select_bias"], K,
                                  SCALE)
    mask, _ = ref.router(xb.astype(jnp.float32), p[e + "router"],
                         p["select_bias"], K, SCALE)
    np.testing.assert_array_equal(
        (np.asarray(idx)[..., None] == np.arange(E)).any(1), mask)
