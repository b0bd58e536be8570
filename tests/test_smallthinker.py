"""The SmallThinker decoder (``models/smallthinker.py``) and what it brought
to the program — a sliding window inside the flash kernels, a router with
its own input, softmax scoring, ReGLU experts, ``recompute`` spans on a
path a benchmark cell runs — against the plain float32 reference
(``models/smallthinker_reference.py``) on seeded weights, at tiny sizes on
the CPU (Pallas kernels interpreted).

Tolerances: everything here runs in float32 on both sides, so the two
differ by summation order only; 2e-5 relative to a tensor's largest entry
(2e-4 for gradients) is what the sibling decoders' tests hold, far below
what a wrong term gives (a window off by one key, the router fed from the
experts' input: both planted below).
"""

import hashlib
import json
import os
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
from paddle_tpu.fluid.ops import control_flow_ops, decoder_ops, pallas_ops
from paddle_tpu.models import smallthinker
from paddle_tpu.models import smallthinker_reference as ref

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


# -- the sliding window inside the flash kernels --------------------------------

BAND_S, BAND_D = 512, 16


@pytest.fixture
def tiles_of_128(monkeypatch):
    """Four tiles a side at S = 512, so that a band has edges to cut."""
    monkeypatch.setattr(pallas_ops, "_TILE_SIDES", (128,))


def _band_arrays(group, seed):
    rng = np.random.default_rng(seed)

    def arr(heads):
        return jnp.asarray(rng.normal(size=(heads, BAND_S, BAND_D)) * 0.5,
                           jnp.float32)
    return arr(2 * group), arr(2), arr(2), arr(2 * group)


def _masked_composition(q, k, v, window):
    group = q.shape[0] // k.shape[0]
    return pallas_ops._reference_attention(
        q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0), None,
        0.25, causal=True, window=window)


@pytest.mark.parametrize("group", [1, 3, 7])
@pytest.mark.parametrize("window", [64, 128, 200, 256, 385],
                         ids=lambda w: "w%d" % w)
def test_band_kernels_against_the_masked_composition(tiles_of_128, group,
                                                     window):
    """Forward, dQ and dK / dV of the looped kernels under a window below,
    equal to, between multiples of and above the 128-row blocks (four tiles
    a side), for 1, 3 and 7 query heads a key/value head, against the
    composition whose band is a boolean mask on K and V repeated."""
    q, k, v, g = _band_arrays(group, 1000 * group + window)
    out, vjp = jax.vjp(lambda *a: pallas_ops.flash_attention(
        *a, None, 0.25, True, None, window), q, k, v)
    want, want_vjp = jax.vjp(
        lambda *a: _masked_composition(*a, window), q, k, v)
    close(out, want, what="forward")
    for name, got, exp in zip(("dq", "dk", "dv"), vjp(g), want_vjp(g)):
        assert got.shape == exp.shape
        close(got, exp, what=name)
    # the planted fault: a window one key longer is another result
    assert far(_masked_composition(q, k, v, window + 64), want, 1e-3)


def test_a_window_that_covers_the_sequence_is_the_causal_call(tiles_of_128):
    """``W >= S`` traces exactly what ``causal`` alone traces (no
    ``attn_window`` scope, no ``window`` label), and a window without the
    causal mask, beside a rotary pair or under the sequence-parallel
    islands is refused by name."""
    q, k, v, _ = _band_arrays(3, 5)

    def program(window):
        def loss(q, k, v):
            out, lse = pallas_ops.flash_attention_lse(q, k, v, None, 0.25,
                                                      True, None, window)
            return out.sum() + lse.sum() * 0
        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    telemetry.reset_metrics()
    assert program(0) == program(BAND_S) == program(4 * BAND_S)
    assert program(0) != program(BAND_S - 1)
    tiles = telemetry.registry().get("flash_tiles_total")
    assert tiles.value(window=0) == 12 and tiles.value(window=BAND_S - 1) == 3
    with pytest.raises(ValueError, match="needs causal=True"):
        pallas_ops.flash_attention(q, k, v, None, 0.25, False, None, 64)
    rope = (jnp.zeros((6, BAND_S, 8)), jnp.zeros((2, BAND_S, 8)))
    with pytest.raises((NotImplementedError, ValueError)):
        pallas_ops.flash_attention(q, q, q, None, 0.25, True, rope, 64)
    with pytest.raises(ValueError, match="needs causal=True"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = data("x", (1, 2, 128, 16))
            layers.fused_attention(x, x, x, window=8)


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (512, 256),
                                             (256, 512)])
@pytest.mark.parametrize("window", [1, 64, 128, 200, 1000, 4096])
def test_the_sweeps_visit_the_band_s_tiles_and_no_other(block_q, block_k,
                                                        window):
    """The loop bounds against a count by hand: a k tile is visited by a q
    tile exactly where some (query, key) pair of the two lies in the band;
    the lower bound of the forward and dQ sweeps and the upper bound of the
    dK/dV sweep are the first and one past the last such tile."""
    S = 2048
    qi, ki = np.arange(S)[:, None], np.arange(S)[None, :]
    band = (ki <= qi) & (ki > qi - window)
    live = band.reshape(S // block_q, block_q, S // block_k, block_k) \
        .any(axis=(1, 3))                       # [q tiles, k tiles]
    for qb in range(S // block_q):
        first = int(pallas_ops._first_k_block(jnp.int32(qb), block_q,
                                              block_k, window))
        last = min(-(-(qb + 1) * block_q // block_k), S // block_k)
        assert list(range(first, last)) == list(np.flatnonzero(live[qb]))
    for kb in range(S // block_k):
        last = int(pallas_ops._last_q_block(jnp.int32(kb), block_k, block_q,
                                            S // block_q, window))
        first = kb * block_k // block_q
        assert list(range(first, last)) == list(np.flatnonzero(live[:, kb]))
    assert pallas_ops._first_k_block(3, block_q, block_k, 0) == 0
    assert pallas_ops._last_q_block(3, block_k, block_q, 16, 0) == 16


def test_the_chooser_keys_on_the_window():
    """A windowed call has a plan of its own (the tenth element of the
    shape key), takes the looped pair of passes even where a head is one
    tile, composes beside a bias, and at the cell's shape (28 query heads
    over 4 of 128, S = 16384, bf16) every kernel has a 512 x 512 tile."""
    cell = (16384, 16384, 128, 128, 0, False, True, 2, 7)
    assert pallas_ops._flash_fits(*cell) and \
        pallas_ops._flash_fits(*cell, 4096)
    for kernel in ("fwd", "dq", "dkv"):
        assert pallas_ops._tiles(kernel, *cell, 4096) == (True, 512, 512) == \
            pallas_ops._tiles(kernel, *cell)
    one_tile = (512, 512, 64, 64, 0, False, True, 2)
    assert pallas_ops._fused_backward(*one_tile)
    assert not pallas_ops._fused_backward(*one_tile, 1, 128)
    assert not pallas_ops._flash_fits(512, 512, 64, 64, 0, True, True, 2, 1,
                                      128)
    q = jnp.zeros((2, 256, 16))
    assert pallas_ops._shape_key(q, q, q, None, True, None)[9] == 0
    assert pallas_ops._shape_key(q, q, q, None, True, None, 64)[9] == 64


@pytest.mark.parametrize("seq,window,dropout,biased,path", [
    (256, 100, 0.0, False, "flash"), (1024, 300, 0.0, False, "flash"),
    (136, 50, 0.0, False, "composition"), (256, 100, 0.0, True, "composition"),
    (256, 100, 0.25, False, "composition"), (256, 256, 0.0, False, "flash")])
def test_fused_attention_op_with_a_window(seq, window, dropout, biased, path):
    """Through the Fluid op and its grad op, 6 query heads over 2: the flash
    path runs the band kernels and reads the LSE back; a length that does
    not tile, a bias and attention dropout compose and mask the same band;
    a window of the whole sequence is counted as none."""
    rng = np.random.default_rng(seq + window)
    B, H, H_KV, D = 1, 6, 2, 16
    shapes = {"q": (B, H, seq, D), "k": (B, H_KV, seq, D),
              "v": (B, H_KV, seq, D), "w": (B, H, seq, D)}
    if biased:
        shapes["b"] = (B, 1, seq, seq)
    feed = {n: rng.normal(size=s).astype(np.float32)
            for n, s in shapes.items()}
    telemetry.reset_metrics()

    def build():
        v = {n: data(n, s) for n, s in shapes.items()}
        if biased:
            v["b"].stop_gradient = True
        out = layers.fused_attention(v["q"], v["k"], v["v"], scale=D ** -0.5,
                                     causal=True, dropout_prob=dropout,
                                     attn_bias=v.get("b"), window=window)
        return [out], [v[n] for n in "qkv"], layers.reduce_sum(out * v["w"])

    (out,), grads = run_program(build, feed)
    lowered = telemetry.registry().get("fused_attention_lowered_total")
    counted = 0 if window >= seq else window
    assert lowered.value(shape="gqa", path=path, window=counted) == \
        lowered.value() >= 1
    if dropout:
        return
    flat = {n: jnp.asarray(a).reshape((-1,) + a.shape[2:])
            for n, a in feed.items()}

    def composed(q, k, v):
        G = H // H_KV
        return pallas_ops._reference_attention(
            q, jnp.repeat(k, G, 0), jnp.repeat(v, G, 0), flat.get("b"),
            D ** -0.5, causal=True, window=counted)
    want, vjp = jax.vjp(composed, flat["q"], flat["k"], flat["v"])
    close(out.reshape(want.shape), want, what="forward")
    for got, exp, name in zip(grads, vjp(flat["w"]), "qkv"):
        close(got.reshape(exp.shape), exp, what="d" + name)


# -- the router: its own input, softmax over the chosen, ReGLU -------------------

def test_softmax_over_the_chosen_is_softmax_over_all_renormalised():
    """``route(scoring_func="softmax")`` against the second way of writing
    it (the reference's): the softmax over all E experts, the chosen kept
    and renormalised; the same choice, the same weights, the same gradient
    to the router's input and weights (through the weights alone), times
    the scale; and sigmoid scoring is what it was."""
    rng = np.random.default_rng(0)
    T, H, E, K = 48, 32, 8, 3
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(H, E), scale=0.5), jnp.float32)
    bias = jnp.zeros((E,), jnp.float32)
    g = jnp.asarray(rng.normal(size=(T, K)), jnp.float32)

    def by_route(x, w):
        return decoder_ops.route(x, w, bias, K, 1.5, "softmax")[1]

    def second_way(x, w):
        with jax.default_matmul_precision("highest"):
            mask, weight = ref.router(x, w, K)
        order = jnp.argsort(-(x @ w), axis=-1, stable=True)[:, :K]
        return jnp.take_along_axis(weight, order, axis=-1) * 1.5
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(by_route, x, w)
        want, want_vjp = jax.vjp(second_way, x, w)
    close(got, want, what="weights")
    np.testing.assert_allclose(np.asarray(got).sum(axis=-1), 1.5, rtol=1e-6)
    for a, b, name in zip(vjp(g), want_vjp(g), ("d x", "d router")):
        close(a, b, tol=2e-4, what=name)
    idx, _, load = decoder_ops.route(x, w, bias, K, 1.0, "softmax")
    with jax.default_matmul_precision("highest"):
        mask, _ = ref.router(x, w, K)
    np.testing.assert_array_equal(load, mask.sum(axis=0))
    # a selection bias chooses and does not weigh, under either scoring
    lifted = decoder_ops.route(x, w, bias.at[5].set(100.0), K, 1.0,
                               "softmax")
    assert float(lifted[2][5]) == T
    np.testing.assert_allclose(np.asarray(lifted[1]).sum(axis=-1), 1.0,
                               rtol=1e-6)
    with pytest.raises(KeyError, match="tanh"):
        decoder_ops.route(x, w, bias, K, 1.0, "tanh")


def _expert_weights(rng, hid, experts, width):
    e = "block_sparse_moe.experts."
    p = {e + "router": rng.normal(size=(hid, experts), scale=0.5),
         e + "gate": rng.normal(size=(experts, hid, width), scale=0.2),
         e + "up": rng.normal(size=(experts, hid, width), scale=0.2),
         e + "down": rng.normal(size=(experts, width, hid), scale=0.2)}
    return {n: jnp.asarray(v, jnp.float32) for n, v in p.items()}


MOE_CFG = {"moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2}


@pytest.mark.parametrize("tokens,held,rungs", [(24, 8, 1), (24, 2, 1),
                                               (1024, 1, 2)])
def test_router_input_apart_from_the_experts_input(tokens, held, rungs):
    """Through the Fluid op and its grad op with a ``RouterX``: the output,
    the load and the gradients of X, RouterX, the router and the three
    expert matrices against ``jax.grad`` of the reference layer, with one
    rung (the replayed forward) and with two (the ``Kept`` path of
    ``routed_experts_grad``); X's gradient holds nothing of the router's,
    and the op fed from X alone is another result."""
    rng = np.random.default_rng(tokens + held)
    HID, E, K, WIDTH = 32, 8, 2, 24
    e = "block_sparse_moe.experts."
    p = _expert_weights(rng, HID, E, WIDTH)
    mine = {n: (v[:held] if n != e + "router" else v) for n, v in p.items()}
    x = rng.normal(size=(1, tokens, HID)).astype(np.float32)
    r = rng.normal(size=(1, tokens, HID)).astype(np.float32)
    g = rng.normal(size=(1, tokens, HID)).astype(np.float32)
    assert len(decoder_ops._rungs(tokens, K, held, E)) == rungs
    telemetry.reset_metrics()

    def run(router_input=True):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            xv, rv = data("x", x.shape), data("r", r.shape)
            out, load, _ = layers.routed_experts(
                xv, E, K, WIDTH, num_held=held,
                param_attr=fluid.ParamAttr(name="e"),
                router_input=rv if router_input else None,
                scoring_func="softmax", hidden_act="relu")
            loss = layers.reduce_sum(out * data("g", g.shape))
            append_backward(loss)
            wrt = ["x"] + (["r"] if router_input else []) + \
                ["e." + n for n in ("router", "gate", "up", "down")]
            grads = [main._grad_name_map.get(n, n + "@GRAD") for n in wrt]
        with fluid.scope_guard(fluid.Scope()):
            scope = fluid.global_scope()
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for n in ("router", "gate", "up", "down"):
                scope.set_var("e." + n, jnp.asarray(mine[e + n]))
            return exe.run(main, feed={"x": x, "r": r, "g": g},
                           fetch_list=[out, load] + grads)

    out, load, dx, dr, *dws = run()
    cfg = dict(MOE_CFG, moe_num_primary_experts_held=held)

    def layer(x, r, p):
        return ref.expert_ffn(x, r, p, cfg, "block_sparse_moe")
    with jax.default_matmul_precision("highest"):
        (want, want_load), vjp = jax.vjp(layer, jnp.asarray(x),
                                         jnp.asarray(r), mine)
        wdx, wdr, wdp = vjp((jnp.asarray(g), jnp.zeros_like(want_load)))
    close(out, want, what="routed sum")
    np.testing.assert_array_equal(load, want_load)
    close(dx, wdx, tol=2e-4, what="d X")
    close(dr, wdr, tol=2e-4, what="d RouterX")
    for got, n in zip(dws, ("router", "gate", "up", "down")):
        close(got, wdp[e + n], tol=2e-4, what="d " + n)
    counter = telemetry.registry().get("moe_experts_lowered_total")
    assert counter.value(score="softmax", act="relu") == counter.value() == 2
    # the planted fault: the router fed from the experts' own input
    assert far(run(router_input=False)[0], want)


@pytest.mark.parametrize("keywords", [{"scoring_func": "tanh"},
                                      {"hidden_act": "gelu"}])
def test_the_lowering_refuses_a_scoring_or_an_activation_it_has_not(keywords):
    """The op's attributes are held to what ``route`` and ``_gated`` have
    in ONE place, where the lowering reads them."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        out, _, _ = layers.routed_experts(data("x", (1, 8, 16)), 4, 2, 8,
                                          **keywords)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with pytest.raises(ValueError, match="scoring_func .* hidden_act"):
            exe.run(main, feed={"x": np.zeros((1, 8, 16), np.float32)},
                    fetch_list=[out])


def test_the_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: at a tiny size with 8 experts, the routed parts that
    the four shares ``first_expert_held = 0, 2, 4, 6`` give for one layer
    (each told which two experts it holds, routing over all 8 from the
    router's own input) add up to what the uncut reference gives for the
    whole layer; there is no shared expert to count once."""
    rng = np.random.default_rng(7)
    T, HID, E, K, WIDTH = 24, 32, 8, 2, 24
    e = "block_sparse_moe.experts."
    p = _expert_weights(rng, HID, E, WIDTH)
    x = jnp.asarray(rng.normal(size=(T, HID)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(T, HID)), jnp.float32)
    bias = jnp.zeros((E,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_load = ref.expert_ffn(x[None], r[None], p, MOE_CFG,
                                         "block_sparse_moe")
    parts, loads = zip(*(decoder_ops.routed_experts(
        x, p[e + "router"], bias,
        *(p[e + n][first:first + 2] for n in ("gate", "up", "down")),
        top_k=K, scale=1.0, first_expert=first, router_x=r,
        scoring_func="softmax", hidden_act="relu")
        for first in (0, 2, 4, 6)))
    close(sum(parts), want[0], what="four shares")
    for load in loads:          # every share routes over the whole model
        np.testing.assert_array_equal(load, want_load)
    # a share alone is NOT the layer, and the reference told it holds two
    # experts gives that share; SwiGLU experts are another layer
    assert far(parts[1], want[0])
    held = {n: (v[2:4] if n != e + "router" else v) for n, v in p.items()}
    with jax.default_matmul_precision("highest"):
        share, _ = ref.expert_ffn(
            x[None], r[None], held,
            dict(MOE_CFG, moe_num_primary_experts_held=2,
                 first_expert_held=2), "block_sparse_moe")
    close(parts[1], share[0], what="the reference's share")
    silu = decoder_ops.routed_experts(
        x, p[e + "router"], bias, *(p[e + n] for n in ("gate", "up", "down")),
        top_k=K, scale=1.0, first_expert=0, router_x=r,
        scoring_func="softmax")[0]
    assert far(silu, want[0])


# -- what must not move ---------------------------------------------------------------

# cell -> ((T, H, E, top_k, held, I, scale), sha1 of the traced text since
# the sums by token became a Pallas kernel; before, at the parent of the PR
# that gave routed_experts its second input: 7c1c9b0b..., d939de24...)
EXPERT_LAYERS = {
    "moonlight": ((4096, 2048, 64, 6, 8, 1408, 2.446),
                  "ea760f321542b6b160b824b08fd7dcd1550d77a9"),
    "lfm2": ((8192, 2048, 32, 4, 8, 1792, 1.0),
             "240b37768144fc91fb1b545564e7d3759db7ee26"),
}


@pytest.mark.parametrize("name", sorted(EXPERT_LAYERS))
def test_expert_layers_at_their_defaults_are_the_ones_pinned(name):
    """THE PIN for the two cells whose steps hold ``routed_experts`` at
    the defaults (no ``router_x``, sigmoid scores, SiLU gates): a layer's
    whole traced program, forward and backward, conditionals and the row
    copies included, is the text pinned, so those steps compile to what
    they compiled to (``tools/step_memory.py`` says so for the whole
    step).  Re-pinned once, when the sums by token became a Pallas kernel
    (PERF.md section 6); the op's second input had left the text
    as it was."""
    (T, H, E, k, held, I, scale), want = EXPERT_LAYERS[name]
    state = types.SimpleNamespace(amp_dtype="bfloat16", amp_keep=True)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    def loss(x, rw, wg, wu, wd, bias):
        out, load = decoder_ops.routed_experts(
            x, rw, bias, wg, wu, wd, top_k=k, scale=scale, first_expert=0,
            state=state)
        return out.astype(jnp.float32).sum() + load.sum() * 0
    f32 = jnp.float32
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        arr(jnp.bfloat16, T, H), arr(f32, H, E), arr(f32, held, H, I),
        arr(f32, held, H, I), arr(f32, held, I, H), arr(f32, E)))
    assert hashlib.sha1(text.encode()).hexdigest() == want


# -- the model --------------------------------------------------------------------------

def _reference_cfg(cfg):
    keys = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "rope_layout",
            "sliding_window_layout", "sliding_window_size",
            "moe_num_primary_experts", "moe_num_active_primary_experts",
            "moe_num_primary_experts_held", "first_expert_held",
            "moe_enable_early_router")
    return {k: getattr(cfg, k) for k in keys}


def _reference_params(scope, handles):
    """The scope's parameters under the reference's names (copies: the
    step donates the scope's own buffers)."""
    block = handles["loss"].block.program.global_block()
    return {p.name: jnp.asarray(np.array(scope.find_var(p.name)))
            for p in block.all_parameters()}


def _batch(cfg, seed, batch=2):
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, cfg.max_seq_len + 1))
    return {"ids": ids[:, :-1, None].astype(np.int64),
            "labels": ids[:, 1:, None].astype(np.int64)}


def _squeeze(feed):
    return jnp.asarray(feed["ids"][..., 0]), \
        jnp.asarray(feed["labels"][..., 0])


def _build(cfg, seed, **kw):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        handles = smallthinker.build_train(cfg, **kw)
    return main, startup, handles


def _step(cfg, seed=11):
    """One forward and backward of the training program of ``cfg`` with a
    rate of zero: ``(names, fetched, reference parameters)``."""
    main, startup, handles = _build(
        cfg, seed, optimizer=fluid.optimizer.SGD(learning_rate=0.0))
    names = [p.name for p in main.global_block().all_parameters()]
    fetch_list = [handles["loss"], handles["token_loss"]] + [
        main._grad_name_map.get(n, n + "@GRAD") for n in names] + \
        handles["expert_loads"]
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        params = _reference_params(scope, handles)
        got = exe.run(main, feed=_batch(cfg, 0), fetch_list=fetch_list)
    return main, names, got, params


@pytest.mark.parametrize("held,first,seq", [(8, 0, 32), (2, 4, 32),
                                            (8, 0, 128)])
def test_model_loss_and_every_gradient_with_and_without_recompute(held, first,
                                                                  seq):
    """The program against the reference: loss, every position's loss,
    every leaf's gradient and the loads; with the ``recompute`` spans and
    without them: the forward equal TO THE BIT in float32, the gradients to
    the order of their sums (2e-6 of a tensor's largest entry).  6 query
    heads over 2 (a group of 3), a window of 8 that bites at 32 and at 128
    tokens (one tile and, at 128, still one: the bounds' own test cuts
    tiles), a full layer without positions and three windowed with rotary;
    the second case is a share (2 of 8 experts held, routed over all 8)."""
    kw = dict(max_seq_len=seq, moe_num_primary_experts_held=held,
              first_expert_held=first)
    telemetry.reset_metrics()
    main, names, got, params = _step(smallthinker.tiny_config(**kw))
    kinds = [op.type for op in main.global_block().ops]
    assert kinds.count("recompute") == kinds.count("recompute_grad") == 4
    assert "fused_attention" not in kinds and "routed_experts" not in kinds
    tiles = telemetry.registry().get("flash_tiles_total")
    # a layer's forward is traced three times: by the span, and twice by its
    # grad op (the primal of the span's vjp, which XLA drops as dead code,
    # and the replay inside the backward)
    assert tiles.value(kernel="fwd", window=8) == 9
    assert tiles.value(kernel="fwd", window=0) == 3
    assert tiles.value(kernel="dq", window=8) == 3 == \
        tiles.value(kernel="dkv", window=8)
    assert tiles.value(kernel="dq", window=0) == 1 == \
        tiles.value(kernel="dkv", window=0)     # the full layer
    lowered = telemetry.registry().get("recompute_lowered_total")
    spans = {op.attr("sub_block"): len(main.blocks[op.attr("sub_block")].ops)
             for op in main.global_block().ops if op.type == "recompute"}
    assert lowered.value() == 8 and sum(
        lowered.value(ops=n) for n in set(spans.values())) == 8

    cfg = smallthinker.tiny_config(**kw)
    rcfg = _reference_cfg(cfg)
    ids, labels = _squeeze(_batch(cfg, 0))
    want_loss, want_tokens, want_grads, want_loads = ref.loss_and_grads(
        params, ids, labels, rcfg)
    assert abs(float(got[0][0]) - float(want_loss)) < 2e-5 * float(want_loss)
    assert abs(float(want_loss) - np.log(cfg.vocab_size)) < 0.1
    close(got[1][..., 0], want_tokens, what="per-token loss")
    assert set(names) == set(want_grads) and len(names) == 4 * 10 + 3
    for name, grad in zip(names, got[2:2 + len(names)]):
        close(grad, want_grads[name], tol=2e-4, what="d loss / d " + name)
    for load, want_load in zip(got[2 + len(names):], want_loads):
        np.testing.assert_array_equal(load, want_load)
        assert float(load.sum()) == ids.size * 2
    # the block-by-block reference is the whole reference
    whole, whole_tokens, _ = ref.forward_loss(params, ids, labels, rcfg)
    assert abs(float(whole) - float(want_loss)) < 1e-6
    # without the spans: the same numbers to the bit
    plain_main, plain_names, plain, _ = _step(
        smallthinker.tiny_config(recompute=False, **kw))
    assert "recompute" not in [op.type for op in
                               plain_main.global_block().ops]
    assert plain_names == names
    for a, b in zip(got[:2] + got[2 + len(names):],
                    plain[:2] + plain[2 + len(names):]):
        np.testing.assert_array_equal(a, b)     # loss, token loss, loads
    # the gradients: a span's vjp and the grad ops of the plain program add
    # the residual stream's contributions in another order, nothing else
    for a, b, what in zip(got[2:], plain[2:], names):
        close(a, b, tol=2e-6, what="with and without the spans: " + what)
    # the planted faults: the window off, and the router fed late
    off = ref.forward_loss(params, ids, labels, rcfg, window=False)[1]
    assert far(off[:, 8:], want_tokens[:, 8:], 1e-3)
    np.testing.assert_allclose(off[:, :8], want_tokens[:, :8], rtol=1e-5)
    late = ref.forward_loss(params, ids, labels,
                            dict(rcfg, moe_enable_early_router=False))[1]
    assert far(late, want_tokens, 1e-3)


def test_adam_steps_and_what_a_step_leaves():
    """Two steps of the training program (the spans on) against Adam
    applied by hand to the reference's gradients; the step's loads and
    every position's loss stay in the scope with no fetch of them, and no
    selection bias moves: there is no ``moe_bias_update``."""
    lr, b1, b2, eps = 1e-3, 0.9, 0.999, 1e-8
    cfg = smallthinker.tiny_config()
    main, startup, handles = _build(cfg, 5, lr=lr)
    kinds = [op.type for op in main.global_block().ops]
    assert "moe_bias_update" not in kinds and kinds.count("adam") == 43
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
            now = _reference_params(scope, handles)
            for n in params:
                close(now[n], params[n], tol=1e-3,
                      what="step %d %s" % (step, n))
        biases = [n for n in scope.var_names() if ".select_bias" in n]
        assert len(biases) == 4
        for n in biases:
            assert not np.asarray(scope.find_var(n)).any()


def test_layers_differ_by_position_in_two_ways():
    cfg = smallthinker.SmallThinkerConfig()
    assert cfg.rope_layout == cfg.sliding_window_layout == \
        [0, 1, 1, 1] * 13 and cfg.max_seq_len == 16384
    with pytest.raises(ValueError, match="rope_layout"):
        smallthinker.SmallThinkerConfig(num_hidden_layers=2,
                                        rope_layout=[0])
    with pytest.raises(NotImplementedError):
        smallthinker.SmallThinkerConfig(norm_topk_prob=False)
    # each layout is read from its own key
    cfg = smallthinker.tiny_config(rope_layout=[1, 0, 0, 1],
                                   sliding_window_layout=[0, 0, 1, 1])
    main, _, _ = _build(cfg, 1)
    spans = [main.blocks[op.attr("sub_block")].ops
             for op in main.global_block().ops if op.type == "recompute"]
    assert [[op.type for op in ops].count("rotary_embedding")
            for ops in spans] == [2, 0, 0, 2]
    windows = [op.attr("window") if op.has_attr("window") else 0
               for ops in spans for op in ops if op.type == "fused_attention"]
    assert windows == [0, 0, 8, 8]
    experts = [op for ops in spans for op in ops
               if op.type == "routed_experts"]
    assert all(op.attr("scoring_func") == "softmax" and
               op.attr("hidden_act") == "relu" and op.input("RouterX") and
               op.input("RouterX") != op.input("X") for op in experts)
    # the router's input is the attention's input
    for ops in spans:
        norms = [op for op in ops if op.type == "rms_norm"]
        routed, = [op for op in ops if op.type == "routed_experts"]
        assert routed.input("RouterX") == norms[0].output("Y")
        assert routed.input("X") == norms[1].output("Y")


def test_the_configuration_counts_its_parameters():
    """The benchmark's configuration at its published widths, from the
    shapes of the program it builds (nothing is run): 370,547,200."""
    with open(os.path.join(HERE, "..", "benchmarks", "configs",
                           "smallthinker-21b-a3b-ep8share.json")) as f:
        params = json.load(f)
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_layout", "sliding_window_layout", "sliding_window_size",
            "moe_ffn_hidden_size", "moe_num_primary_experts",
            "moe_num_active_primary_experts", "moe_num_primary_experts_held",
            "first_expert_held")
    cfg = smallthinker.SmallThinkerConfig(**{k: params[k] for k in keys})
    assert cfg.max_seq_len == 16384
    main, _, _ = _build(cfg, 1)
    sizes = {p.name: int(np.prod(p.shape))
             for p in main.global_block().all_parameters()}

    def layer(i):
        return sum(n for name, n in sizes.items()
                   if name.startswith("layers.%d." % i))
    H = 2560
    attention = H * 3584 + 2 * H * 512 + 3584 * H
    experts = H * 64 + 8 * 3 * H * 768
    assert attention == 20971520 and experts == 47349760
    assert all(layer(i) == attention + experts + 2 * H == 68326400
               for i in range(4))
    assert sizes["embed_tokens"] == sizes["lm_head"] == 18992 * H == 48619520
    assert sum(sizes.values()) == 370547200


@pytest.mark.parametrize("given,table", [(None, 0.02), (1.0, 1.0)])
def test_the_embedding_table_has_its_own_deviation(given, table):
    """``embedding_initializer_range``: the table alone is drawn at it (the
    benchmark's configuration gives 1.0, so that a fresh residual stream
    carries the token and the routers of every layer read it); absent, the
    table is drawn like every matrix."""
    cfg = smallthinker.tiny_config(embedding_initializer_range=given,
                                   vocab_size=2048, hidden_size=64)
    assert cfg.embedding_initializer_range == table
    assert cfg.initializer_range == 0.02
    _, startup, handles = _build(cfg, 5)
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        drawn = _reference_params(fluid.global_scope(), handles)
    assert float(jnp.std(drawn["embed_tokens"])) == pytest.approx(table,
                                                                  rel=0.02)
    for name, value in drawn.items():
        if name != "embed_tokens" and value.ndim > 1:
            assert float(jnp.std(value)) == pytest.approx(0.02, rel=0.1), name


def test_the_reference_in_bfloat16_is_the_control_not_the_reference():
    """``dtype`` lowers everything in the reference, the router too: the
    reading the benchmark's limits have to refuse.  It routes every token
    (no drop), lands near the float32 loss, and is not it."""
    cfg = smallthinker.tiny_config(max_seq_len=64)
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
            ids.size * cfg.moe_num_active_primary_experts


def test_pure_bf16_step_runs_and_learns():
    """Under pure-bf16 AMP inside the ``recompute`` spans the step runs,
    its first loss is the untrained model's and a batch seen again reads
    lower."""
    cfg = smallthinker.tiny_config(max_seq_len=128)
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


# -- the spans, compiled for the chip -----------------------------------------------

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


def test_recomputed_layers_compiled_for_v5e(one_chip):
    """The pure-bf16 step of a small two-layer model (one full layer, one
    windowed; 4 query heads over 2 of 128; 2048 tokens, 16 experts with 2
    held so that the layer has two rungs) compiled for a v5e, no chip:
    inside a ``recompute`` span the backward is the span's generic vjp, so
    each layer's attention goes through its ``custom_vjp`` — ``flash_fwd``
    TWICE a layer (the span and its replay), ``flash_dq`` and ``flash_dkv``
    once — and each expert layer through ``_ladder``: three conditionals a
    layer (forward, replayed forward, backward).  The windowed layer's
    kernels sit under ``attn_window``, the replayed forward under
    ``rematted_computation`` or ``checkpoint``, and ``ExpertLoad`` leaves
    the span as an output."""
    from paddle_tpu.fluid import executor

    cfg = smallthinker.SmallThinkerConfig(
        vocab_size=512, hidden_size=256, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        rope_layout=[0, 1], sliding_window_layout=[0, 1],
        sliding_window_size=512, moe_ffn_hidden_size=128,
        moe_num_primary_experts=16, moe_num_active_primary_experts=2,
        moe_num_primary_experts_held=2, max_seq_len=2048)
    assert len(decoder_ops._rungs(2048, 2, 2, 16)) == 2
    opt = fluid.contrib.mixed_precision.decorate(
        fluid.optimizer.AdamOptimizer(learning_rate=1e-4),
        use_pure_bf16=True)
    main, startup, handles = _build(cfg, 3, optimizer=opt)
    for op in main.global_block().ops:
        if op.type == "recompute":
            loads = [n for n in op.output("Out") if "expert_load" in n]
            assert len(loads) == 1
    feed = _batch(cfg, 0, batch=1)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        compiled, feed_vals = exe._resolve_compiled(
            main, feed, [handles["loss"]], scope, None)
        args = (executor._scope_state(scope, compiled.state_mut),
                executor._scope_state(scope, compiled.state_ro),
                tuple(feed_vals), np.int32(0))
        shapes = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                           sharding=one_chip), args)
        text = compiled._jitted.lower(*shapes).compile().as_text()
    calls = re.findall(r'%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    flash = sorted(name for name, _ in calls if name.startswith("flash_"))
    assert flash == sorted(["flash_fwd"] * 4 + ["flash_dq"] * 2 +
                           ["flash_dkv"] * 2), flash
    windowed = sorted(name for name, scope_ in calls
                      if "attn_window" in scope_)
    assert windowed == ["flash_dkv", "flash_dq", "flash_fwd", "flash_fwd"]
    replayed = [scope_ for name, scope_ in calls if name == "flash_fwd"
                and "role_bwd" in scope_]
    assert len(replayed) == 2 and all(
        control_flow_ops.REPLAY_SCOPE in s for s in replayed), replayed
    conditionals = re.findall(r"= [^\n]* conditional\(", text)
    assert len(conditionals) == 6, len(conditionals)


def test_the_two_copies_of_the_reference_are_one():
    with open(os.path.join(HERE, "..", "paddle_tpu", "models",
                           "smallthinker_reference.py")) as f:
        program_side = f.read()
    with open(os.path.join(HERE, "..", "benchmarks", "configs",
                           "smallthinker_reference.py")) as f:
        assert f.read() == program_side


def test_the_model_is_exported():
    assert models.smallthinker is smallthinker and \
        models.smallthinker_reference is ref
