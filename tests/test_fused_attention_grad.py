"""``fused_attention_grad`` runs the flash backward kernels on the forward
op's ``LSE`` instead of replaying the forward lowering (which traced a
second ``flash_fwd`` Mosaic call XLA cannot merge with the first).

CPU, interpret-mode kernels: counts of kernel calls per traced step, which
grad ops take which path, and gradient parity through the Fluid op against
``_reference_attention``'s vjp at test_pallas_attention.py's tolerances.
"""

import collections
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, telemetry
from paddle_tpu.fluid.ops import pallas_ops
from paddle_tpu.fluid.ops.pallas_ops import _reference_attention
from tests.test_flash_tiles import _rebuilt_mask

B, H, D = 2, 2, 16


def _data(name, shape, dtype="float32", grad=True):
    v = layers.data(name=name, shape=list(shape), dtype=dtype,
                    append_batch_size=False)
    v.stop_gradient = not grad
    return v


def _program(S_q=128, S_kv=128, bias_shape=None, causal=False, dropout=0.0,
             n_ops=1, with_lse=True, cast=None, backward=True,
             bias_grad=True):
    """``n_ops`` chained attention ops (each one's output is the next
    one's Q) under the loss ``sum(out * w)``; the fetch list is the loss
    and the gradients of q, k, v and (``bias_grad``; else it is a mask
    that wants none) the bias.  ``with_lse=False`` builds the op as a
    program from before the ``LSE`` slot existed."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ins = [_data("q", (B, H, S_q, D)), _data("k", (B, H, S_kv, D)),
               _data("v", (B, H, S_kv, D))]
        if bias_shape is not None:
            ins.append(_data("b", bias_shape, grad=bias_grad))
        w = _data("w", (B, H, S_q, D), grad=False)
        q, k, v, b = (ins + [None])[:4]
        if cast:
            q, k, v = (layers.cast(x, cast) for x in (q, k, v))
            b = layers.cast(b, cast) if b is not None else None
        out = q
        for _ in range(n_ops):
            out = layers.fused_attention(out, k, v, b, scale=D ** -0.5,
                                         causal=causal,
                                         dropout_prob=dropout)
        if not with_lse:
            for op in main.global_block().ops:
                if op.type == "fused_attention":
                    del op.outputs["LSE"]
        if cast:
            out = layers.cast(out, "float32")
        loss = layers.reduce_sum(out * w)
        wanted = [x for x in ins if not x.stop_gradient]
        grads = fluid.gradients(loss, wanted) if backward else []
    return main, startup, [loss] + grads


def _feed(S_q=128, S_kv=128, bias_shape=None, seed=0):
    rng = np.random.RandomState(seed)
    feed = {"q": rng.randn(B, H, S_q, D) * 0.5,
            "k": rng.randn(B, H, S_kv, D) * 0.5,
            "v": rng.randn(B, H, S_kv, D) * 0.5,
            "w": rng.randn(B, H, S_q, D)}
    if bias_shape is not None:
        feed["b"] = rng.randn(*bias_shape) * 0.3
    return {n: a.astype(np.float32) for n, a in feed.items()}


def _run(main, startup, fetches, feed):
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return [np.asarray(x) for x in
                exe.run(main, feed=feed, fetch_list=fetches)]


@pytest.fixture
def kernel_calls(monkeypatch):
    """Kernel calls traced, by kernel name, with each call's number of
    outputs: every ``_pallas_call`` is one kernel call of the step being
    traced (a Mosaic custom call on the chip)."""
    calls = collections.defaultdict(list)
    real = pallas_ops._pallas_call

    def spy(kernel, name, **kwargs):
        calls[name].append(len(jax.tree.leaves(kwargs["out_shape"])))
        return real(kernel, name, **kwargs)

    monkeypatch.setattr(pallas_ops, "_pallas_call", spy)
    return calls


def _grad_paths():
    c = telemetry.counter("fused_attention_grad_lowered_total")
    return c.value(path="residual"), c.value(path="replay")


def _paths_taken(before):
    after = _grad_paths()
    return after[0] - before[0], after[1] - before[1]


@pytest.mark.parametrize("n_ops,S", [(1, 128), (3, 128), (1, 1024)])
def test_training_step_runs_each_flash_kernel_once_per_op(kernel_calls,
                                                          n_ops, S):
    """A head of 128 rows is one tile: one backward kernel an op.  At
    S=1024 (two 512-row tiles a side) the dQ pass and the dK/dV pass."""
    before = _grad_paths()
    _run(*_program(S_q=S, S_kv=S, n_ops=n_ops), _feed(S, S))
    # the forward kernel writes (out, LSE); the fused backward (dq, dk, dv)
    # and no delta, which nothing reads; the dQ kernel (dq, delta)
    backward = {"flash_bwd": [3] * n_ops} if S == 128 else \
        {"flash_dq": [2] * n_ops, "flash_dkv": [2] * n_ops}
    assert dict(kernel_calls) == dict(backward, flash_fwd=[2] * n_ops)
    assert _paths_taken(before) == (n_ops, 0)


def test_program_without_the_lse_slot_replays_the_forward(kernel_calls):
    """What every program did before the slot existed, and what one built
    then still does: the grad op differentiates a second forward."""
    before = _grad_paths()
    feed = _feed()
    old = _run(*_program(n_ops=2, with_lse=False), feed)
    assert {n: len(c) for n, c in kernel_calls.items()} == {
        "flash_fwd": 4, "flash_bwd": 2}
    assert _paths_taken(before) == (0, 2)
    new = _run(*_program(n_ops=2), feed)
    for a, b in zip(old, new):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_for_test_clone_asks_the_kernel_for_no_lse(kernel_calls):
    main, startup, fetches = _program(backward=False)
    feed = _feed()
    train, = _run(main, startup, fetches, feed)
    assert kernel_calls.pop("flash_fwd") == [2]
    infer, = _run(main.clone(for_test=True), startup, fetches, feed)
    assert dict(kernel_calls) == {"flash_fwd": [1]}
    np.testing.assert_allclose(infer, train, rtol=1e-6)


CASES = {
    "plain": dict(),
    "bias": dict(bias_shape=(B, H, 128, 128)),
    "mask_bias": dict(bias_shape=(B, 1, 1, 128)),
    "causal": dict(S_q=256, S_kv=256, causal=True),
    "causal_bias": dict(S_q=256, S_kv=256, causal=True,
                        bias_shape=(B, 1, 256, 256)),
    "cross": dict(S_q=128, S_kv=256, bias_shape=(B, H, 128, 256)),
}


def _reference_grads(feed, causal):
    S_q, S_kv = feed["q"].shape[2], feed["k"].shape[2]
    flat = lambda x: x.reshape(B * H, x.shape[2], D)
    args = [feed["q"], feed["k"], feed["v"]] + \
        ([feed["b"]] if "b" in feed else [])

    def loss(q, k, v, b=None):
        bf = None if b is None else jnp.broadcast_to(
            b, (B, H, S_q, S_kv)).reshape(B * H, S_q, S_kv)
        out = _reference_attention(flat(q), flat(k), flat(v), bf, D ** -0.5,
                                   causal=causal)
        return jnp.sum(out * flat(feed["w"]))

    val, grads = jax.value_and_grad(loss, argnums=tuple(range(len(args))))(
        *[jnp.asarray(a) for a in args])
    return [np.asarray(val)] + [np.asarray(g) for g in grads]


@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_through_the_op_match_the_reference(case):
    kw = dict(CASES[case])
    causal = kw.get("causal", False)
    before = _grad_paths()
    feed = _feed(kw.get("S_q", 128), kw.get("S_kv", 128),
                 kw.get("bias_shape"), seed=len(case))
    got = _run(*_program(**kw), feed)
    assert _paths_taken(before) == (1, 0)
    want = _reference_grads(feed, causal)
    for name, a, b in zip(("loss", "dq", "dk", "dv", "dbias"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


def test_bf16_gradients_through_the_op_follow_the_fp32_ones():
    """bf16 products with float32 accumulation, as the flash cell trains:
    the gradients agree in direction with the float32 reference's, the
    measure test_pallas_attention.py holds the bf16 backward to."""
    shape = (B, 1, 128, 128)
    feed = _feed(bias_shape=shape, seed=9)
    before = _grad_paths()
    got = _run(*_program(bias_shape=shape, cast="bfloat16"), feed)
    assert _paths_taken(before) == (1, 0)
    want = _reference_grads(feed, False)
    np.testing.assert_allclose(got[0], want[0], rtol=3e-2)
    for name, a, b in zip(("dq", "dk", "dv", "dbias"), got[1:], want[1:]):
        a, b = a.ravel().astype(np.float64), b.ravel().astype(np.float64)
        cos = a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12)
        assert cos > 0.99, (name, cos)


@pytest.mark.parametrize("causal,with_bias", [(False, False), (False, True),
                                              (True, False), (True, True)])
def test_dq_kernel_forms_delta(causal, with_bias):
    """delta_i = sum_j P_ij dP_ij, summed in the dQ kernel, is
    sum_d dO_id O_id; dq is what a passed delta gives."""
    rng = np.random.RandomState(3)
    S = 256
    q, k, v, g = (jnp.asarray(rng.randn(2, S, D).astype(np.float32) * 0.5)
                  for _ in range(4))
    bias = jnp.asarray(rng.randn(2, S, S).astype(np.float32) * 0.3) \
        if with_bias else None
    out, lse = pallas_ops._flash_forward(q, k, v, bias, 0.25, with_lse=True,
                                         causal=causal)
    # the pass reads and writes its statistics as [BH, S_q, 1] columns
    lse, want = lse[..., None], pallas_ops._row_delta(g, out)[..., None]
    dq, delta = pallas_ops._flash_dq(q, k, v, bias, 0.25, lse, g, causal,
                                     None)
    np.testing.assert_allclose(np.asarray(delta), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    dq_passed, same = pallas_ops._flash_dq(q, k, v, bias, 0.25, lse, g,
                                           causal, want)
    assert same is want
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_passed),
                               rtol=2e-4, atol=2e-5)


FUSED_CASES = {
    "plain": dict(),
    "bias": dict(with_bias=True),
    "causal": dict(causal=True),
    "causal_bias": dict(causal=True, with_bias=True),
    "dv_wider": dict(D_v=32, with_bias=True),
    "cross": dict(S_kv=256, D_v=8),
    "short": dict(S_q=64, S_kv=64, causal=True),
}


def _fused_case(S_q=128, S_kv=128, D_v=D, with_bias=False, causal=False,
                seed=11):
    rng = np.random.RandomState(seed)

    def arr(*dims, scale=0.5):
        return jnp.asarray(rng.randn(*dims).astype(np.float32) * scale)
    q, k, v, g = arr(3, S_q, D), arr(3, S_kv, D), arr(3, S_kv, D_v), \
        arr(3, S_q, D_v)
    bias = arr(3, S_q, S_kv, scale=0.3) if with_bias else None
    assert pallas_ops._fused_backward(
        *pallas_ops._shape_key(q, k, v, bias, causal, None))
    return q, k, v, bias, g, causal


@pytest.mark.parametrize("passed", [False, True],
                         ids=["delta_formed", "delta_passed"])
@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_backward_is_the_pair_of_passes_in_one_kernel(case, passed):
    """``flash_bwd``'s dQ, dK, dV and delta against ``flash_dq`` then
    ``flash_dkv`` on the same inputs: the same five products in the same
    dtypes, so they agree to rounding's last bits, with delta formed by
    the kernel or passed in."""
    q, k, v, bias, g, causal = _fused_case(**FUSED_CASES[case])
    out, lse = pallas_ops._flash_forward(q, k, v, bias, 0.25, with_lse=True,
                                         causal=causal)
    delta = pallas_ops._row_delta(g, out) if passed else None
    # the fused kernel's statistics are [BH, 1, S_q] rows, the passes'
    # [BH, S_q, 1] columns
    row, col = (functools.partial(pallas_ops._kernel_stat, rows=rows)
                for rows in (True, False))
    as_row = row(delta)
    dq, dk, dv, formed = pallas_ops._flash_bwd(
        q, k, v, bias, 0.25, row(lse), g, causal, as_row, delta_out=True)
    want_dq, want_delta = pallas_ops._flash_dq(q, k, v, bias, 0.25, col(lse),
                                               g, causal, col(delta))
    want_dk, want_dv = pallas_ops._flash_dkv(q, k, v, bias, 0.25, col(lse),
                                             g, causal, want_delta)
    if passed:
        assert formed is as_row
    for name, a, b in (("dq", dq, want_dq), ("dk", dk, want_dk),
                       ("dv", dv, want_dv),
                       ("delta", formed[:, 0], want_delta[..., 0])):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7, err_msg=name)
    # a delta nothing reads is not written
    assert pallas_ops._flash_bwd(q, k, v, bias, 0.25, row(lse), g, causal,
                                 None)[3] is None


@pytest.mark.parametrize("bias_grad", [False, True])
@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_backward_matches_the_reference(kernel_calls, case, bias_grad):
    """Through ``_flash_backward``, which takes the fused form from the
    shape alone, against ``_reference_attention``'s vjp; with
    ``bias_grad`` the dbias pass still gets its delta, from the fused
    kernel's fourth output."""
    q, k, v, bias, g, causal = _fused_case(**FUSED_CASES[case])
    out, lse = pallas_ops._flash_forward(q, k, v, bias, 0.25, with_lse=True,
                                         causal=causal)
    got = pallas_ops._flash_backward(q, k, v, bias, 0.25, lse, g,
                                     causal=causal, bias_grad=bias_grad)
    want_dbias = bias is not None and bias_grad
    assert dict(kernel_calls) == dict(
        {"flash_fwd": [2], "flash_bwd": [3 + want_dbias]},
        **({"flash_dbias": [1]} if want_dbias else {}))
    _, vjp = jax.vjp(lambda *a: _reference_attention(*a, 0.25,
                                                     causal=causal),
                     q, k, v, bias)
    want = vjp(g)
    assert (got[3] is None) == (not want_dbias)
    for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
        if a is not None:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("S_kv,backward", [
    (256, {"flash_bwd": [3]}),                      # one 128 x 256 tile
    (1024, {"flash_dq": [1], "flash_dkv": [2]})])   # two k tiles of 512
def test_long_kv_keeps_out_and_passes_delta(monkeypatch, kernel_calls, S_kv,
                                            backward):
    """Past ``_DELTA_IN_KERNEL_MAX_SKV`` the held tiles would not fit in
    VMEM beside K/V: the same lowering passes delta in, from ``Out``, and
    neither the fused kernel nor the dQ pass writes one."""
    monkeypatch.setattr(pallas_ops, "_DELTA_IN_KERNEL_MAX_SKV", 128)
    kw = dict(S_q=128, S_kv=S_kv, bias_shape=(B, H, 128, S_kv))
    feed = _feed(kw["S_q"], kw["S_kv"], kw["bias_shape"], seed=4)
    before = _grad_paths()
    got = _run(*_program(**kw), feed)
    assert _paths_taken(before) == (1, 0)
    assert dict(kernel_calls) == dict(backward, flash_fwd=[2],
                                      flash_dbias=[1])
    for name, a, b in zip(("loss", "dq", "dk", "dv", "dbias"), got,
                          _reference_grads(feed, False)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("case", ["dropout_4d", "dropout_s1024", "s192"])
def test_dropout_off_the_in_place_route_and_untileable_shapes_keep_the_replay(
        kernel_calls, case):
    """A 4-D op (no ``num_heads``) with attention dropout, at one tile or
    several, is the exact composition replayed with its key: only the
    kernels that read ``[B, S, H * D]`` in place draw a mask; S = 192 does
    not tile into 128-row blocks, and takes the reference composition."""
    S = {"dropout_4d": 128, "dropout_s1024": 1024, "s192": 192}[case]
    before = _grad_paths()
    feed = _feed(S, S, seed=2)
    got = _run(*_program(S_q=S, S_kv=S, dropout=0.0 if case == "s192"
                         else 0.1), feed)
    assert _paths_taken(before) == (0, 1)
    assert not kernel_calls
    if case == "s192":
        for name, a, b in zip(("loss", "dq", "dk", "dv"), got,
                              _reference_grads(feed, False)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                       err_msg=name)


def test_sequence_parallel_island_keeps_the_replay():
    """An op the sequence-parallel transpiler stamped, compiled over a
    mesh carrying that axis, is a shard_map island in both directions."""
    from paddle_tpu.fluid.transpiler import SequenceParallelTranspiler

    S = 256
    main, startup, fetches = _program(S_q=S, S_kv=S)
    assert SequenceParallelTranspiler(2).transpile(main, startup)
    assert all(op.attr("sp_axis") for op in main.global_block().ops
               if op.type.startswith("fused_attention"))
    feed = _feed(S, S, seed=6)
    before = _grad_paths()
    got = _run(main, startup, fetches, feed)
    assert _paths_taken(before) == (0, 1)
    for name, a, b in zip(("loss", "dq", "dk", "dv"), got,
                          _reference_grads(feed, False)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


def test_ring_attention_passes_its_global_delta(monkeypatch):
    """Each ring step sees one shard of K/V: a delta summed there by a
    kernel would be wrong, so the ring forms it from the merged output and
    every step's backward takes it as an input — the fused kernel here,
    whose shard of four rows is one tile and which would else form its
    own."""
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.fluid.mesh_utils import shard_map
    from paddle_tpu.parallel.sequence_parallel import (ring_attention,
                                                       local_attention)

    passed = []
    real = pallas_ops._flash_bwd

    def spy(q, k, v, bias, scale, lse, g, causal, delta, **kwargs):
        passed.append(delta is not None)
        return real(q, k, v, bias, scale, lse, g, causal, delta, **kwargs)

    monkeypatch.setattr(pallas_ops, "_flash_bwd", spy)
    monkeypatch.setattr(pallas_ops, "_flash_dq", None)      # never reached
    sp = 4
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(1, 4 * sp, 2, 8).astype(np.float32)
                           * 0.3) for _ in range(3))
    mesh = Mesh(np.array(jax.devices("cpu")[:sp]), ("sp",))

    def grads(fn):
        def loss(a, b, c):
            mapped = shard_map(fn, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                               out_specs=P(None, "sp"), check_vma=False)
            return jnp.sum(mapped(a, b, c) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    ring = grads(lambda a, b, c: ring_attention(a, b, c, "sp",
                                                use_flash=True))
    assert passed == [True] * sp
    whole = jax.grad(lambda a, b, c: jnp.sum(local_attention(a, b, c) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ring, whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_op_inside_a_recompute_span_is_differentiated_by_jax(kernel_calls):
    """``RecomputeOptimizer`` moves the op into a span whose backward is
    ``jax.vjp`` of the forward lowering, LSE output and all: the training
    forward is a ``custom_vjp`` too, so the span's gradients are those of
    the plain program."""
    def losses(recompute):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 3
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = _data("x", (B, 128, H * D), grad=False)
            h = layers.fc(x, H * D, num_flatten_dims=2)
            q = layers.transpose(layers.reshape(h, [0, 128, H, D]),
                                 [0, 2, 1, 3])
            ctx = layers.fused_attention(q, q, q, scale=D ** -0.5)
            out = layers.fc(layers.reshape(
                layers.transpose(ctx, [0, 2, 1, 3]), [0, 128, H * D]),
                1, num_flatten_dims=2)
            loss = layers.mean(layers.square(out))
            opt = fluid.optimizer.SGDOptimizer(0.1)
            if recompute:
                opt = fluid.optimizer.RecomputeOptimizer(opt)
                opt._set_checkpoints([ctx])
            opt.minimize(loss)
        feed = {"x": np.random.RandomState(1).randn(B, 128, H * D)
                .astype(np.float32)}
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            return [float(np.asarray(exe.run(
                main, feed=feed, fetch_list=[loss])[0]).reshape(()))
                for _ in range(3)]

    plain = losses(False)
    assert {n: len(c) for n, c in kernel_calls.items()} == {
        "flash_fwd": 1, "flash_bwd": 1}
    remat = losses(True)
    np.testing.assert_allclose(remat, plain, rtol=1e-6)
    assert plain[-1] < plain[0]


# -- what crosses HBM beside Q, K, V: the statistics' layout and the mask -----

# several tiles: 384 = 3 x 128 rows a side (two passes, column statistics);
# one tile: 128 (the fused kernel, row statistics); no tile: 192, which no
# side divides (the composition, handed the same bias as the kernels)
TILES = {"one_tile": 128, "several_tiles": 384, "no_tile": 192}
# bias shape by name (S x S filled in), and whether its gradient is wanted:
# a mask that wants none is what the kernels read once a sequence
BIASES = {
    "no_bias": (None, False),
    "key_mask": ((B, 1, 1, "S"), False),
    "key_mask_grad": ((B, 1, 1, "S"), True),
    "sequence_mask": ((B, 1, "S", "S"), False),
    "sequence_mask_grad": ((B, 1, "S", "S"), True),
    "head_bias": ((B, H, "S", "S"), True),
}
_layout_runs = {}


def _layout_case(tiles, bias, causal):
    """(got, want) of one run through the Fluid op, by name; cached, so the
    cases below that read one run share it."""
    key = (tiles, bias, causal)
    if key not in _layout_runs:
        S = TILES[tiles]
        shape, grad = BIASES[bias]
        shape = shape and tuple(S if d == "S" else d for d in shape)
        feed = _feed(S, S, shape, seed=len(bias) + S + causal)
        got = _run(*_program(S_q=S, S_kv=S, bias_shape=shape, causal=causal,
                             bias_grad=grad), feed)
        want = _reference_grads(feed, causal)
        names = ("out", "dq", "dk", "dv") + (("dbias",) if grad else ())
        _layout_runs[key] = dict(zip(names, got)), dict(zip(names, want))
    return _layout_runs[key]


def _layout_params():
    for tiles in TILES:
        for bias, (_, grad) in BIASES.items():
            for causal in (False, True):
                for what in ("out", "dq", "dk", "dv") + \
                        (("dbias",) if grad else ()):
                    yield pytest.param(
                        tiles, bias, causal, what,
                        id="-".join((tiles, bias,
                                     "causal" if causal else "full", what)))


@pytest.mark.parametrize("tiles,bias,causal,what", list(_layout_params()))
def test_every_layout_matches_the_reference(tiles, bias, causal, what):
    """Forward (through the loss), dQ, dK, dV and dbias through the op
    against ``_reference_attention``'s vjp: row statistics where a head is
    one tile and columns where it is several, with no bias, a bias every
    head of a sequence shares (read at block row ``i // H`` when no
    gradient is wanted, a head's own copy when one is) and a bias a head;
    and at a length the kernels have no tile for, where the composition
    takes the shared bias as the kernels would."""
    got, want = _layout_case(tiles, bias, causal)
    assert got[what].shape == want[what].shape
    np.testing.assert_allclose(got[what], want[what], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("S", [128, 192])
def test_flash_attention_takes_a_bias_a_sequence_shares(S):
    """``flash_attention`` called as the op (and the sequence-parallel
    gather island) calls it, with a ``[B, S_q, S_kv]`` bias under ``B * H``
    heads: forward and every gradient, the bias's summed over a sequence's
    heads, where the kernels run and where the shape has no tile."""
    rng = np.random.RandomState(S)
    q, k, v, w = (jnp.asarray(rng.randn(B * H, S, D).astype(np.float32) * 0.5)
                  for _ in range(4))
    bias = jnp.asarray(rng.randn(B, S, S).astype(np.float32) * 0.3)

    def loss(fn, q, k, v, b):
        return jnp.sum(fn(q, k, v, b) * w)
    got = jax.value_and_grad(functools.partial(
        loss, lambda *a: pallas_ops.flash_attention(*a, 0.25)),
        argnums=(0, 1, 2, 3))(q, k, v, bias)
    want = jax.value_and_grad(functools.partial(
        loss, lambda q, k, v, b: _reference_attention(
            q, k, v, jnp.repeat(b, H, axis=0), 0.25)),
        argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_for_test_clone_takes_a_shared_mask_at_a_length_without_a_tile():
    shape = (B, 1, 1, 192)
    main, startup, fetches = _program(S_q=192, S_kv=192, bias_shape=shape,
                                      bias_grad=False, backward=False)
    feed = _feed(192, 192, shape)
    train, = _run(main, startup, fetches, feed)
    infer, = _run(main.clone(for_test=True), startup, fetches, feed)
    np.testing.assert_allclose(infer, train, rtol=1e-6)
    np.testing.assert_allclose(train, _reference_grads(feed, False)[0],
                               rtol=2e-4)


@pytest.fixture
def kernel_operands(monkeypatch):
    """``{kernel name: [shapes of every operand and result of a call]}``
    of the kernel calls traced."""
    seen = collections.defaultdict(list)
    real = pallas_ops._pallas_call

    def spy(kernel, name, **kwargs):
        call = real(kernel, name, **kwargs)
        outs = [tuple(o.shape) for o in jax.tree.leaves(kwargs["out_shape"])]

        def traced(*args):
            seen[name].append([tuple(a.shape) for a in args] + outs)
            return call(*args)
        return traced

    monkeypatch.setattr(pallas_ops, "_pallas_call", spy)
    return seen


@pytest.mark.parametrize("bias", ["no_bias", "sequence_mask",
                                  "sequence_mask_grad", "head_bias"])
def test_row_layout_calls_carry_no_size_one_minor_dimension(kernel_operands,
                                                            bias):
    """Where a head is one tile no operand or result of a kernel call ends
    in a dimension of size 1 (which XLA:TPU pads to 128 lanes): the
    statistics are ``[BH, 1, S_q]``; and a mask that wants no gradient
    enters every call as ``[B, S_q, S_kv]``, never a copy a head."""
    S = 128
    shape, grad = BIASES[bias]
    shape = shape and tuple(S if d == "S" else d for d in shape)
    _run(*_program(S_q=S, S_kv=S, bias_shape=shape, bias_grad=grad),
         _feed(S, S, shape))
    assert set(kernel_operands) == {"flash_fwd", "flash_bwd"} | (
        {"flash_dbias"} if grad else set())
    shapes = [s for calls in kernel_operands.values() for c in calls
              for s in c]
    assert all(s[-1] != 1 for s in shapes), shapes
    assert (B * H, 1, S) in kernel_operands["flash_fwd"][0]
    assert (B * H, 1, S) in kernel_operands["flash_bwd"][0]
    if bias == "sequence_mask":
        for calls in kernel_operands.values():
            assert (B, S, S) in calls[0] and (B * H, S, S) not in calls[0]
    elif shape:
        # a gradient is wanted, or the bias has a head dimension: the
        # backward reads (and flash_dbias writes) a head's own
        assert (B * H, S, S) in kernel_operands["flash_bwd"][0]


@pytest.mark.parametrize("causal", [False, True],
                         ids=["unrolled", "looped"])
def test_multi_pass_calls_trace_what_they_traced_before(kernel_operands,
                                                        causal):
    """A shape of several tiles keeps the program it had before the row
    layout existed (its step's memory is the long-sequence cells':
    ``_row_stats``): the forward writes and both passes read ``[BH, S_q,
    1]`` columns, and the dQ pass hands its delta to the dK/dV pass as
    one."""
    S = 384
    _run(*_program(S_q=S, S_kv=S, causal=causal), _feed(S, S))
    BH, col = B * H, (B * H, S, 1)
    x = (BH, S, D)
    assert dict(kernel_operands) == {
        "flash_fwd": [[x, x, x, x, col]],
        # looped: delta comes in, from ``Out``; unrolled: the pass forms it
        "flash_dq": [[x, x, x, x, col, col, x] if causal
                     else [x, x, x, x, col, x, col]],
        "flash_dkv": [[x, x, x, x, col, col, x, x]]}
    for kernel in ("fwd", "dq", "dkv", "bwd", "dbias"):
        assert not pallas_ops._row_stats(kernel, S, S, D, D, 0, False,
                                         causal, 4, 1)
        # where a head is one tile: the three kernels that run there
        assert pallas_ops._row_stats(
            kernel, 128, 128, D, D, 0, False, causal, 4, 1) == \
            (kernel not in ("dq", "dkv"))


# (query heads, key/value heads, S, D, D_v, rotary R, rows of bias, causal)
# of a layer's kernels, all bfloat16 -> sha1 of the jaxpr of forward and
# backward (``flash_attention_lse`` under ``jax.grad``)
MULTI_PASS_PROGRAMS = {
    # the three long-sequence cells' kernel shapes
    "moonlight": ((16, 16, 4096, 128, 128, 64, 0, True),
                  "2bacd1f5fa16440ef76516f157aa9d4cdb34c25a"),
    "ouro": ((16, 16, 4096, 128, 128, 0, 0, True),
             "1b4617ca9bb2d2ec3b124ec595bbcc7dea9f893a"),
    "lfm2": ((32, 8, 8192, 64, 64, 0, 0, True),
             "5f72f28afe5da057affb83d70ff7a102f8f644b6"),
    # unrolled: the dQ pass forms delta; with a bias a head
    "s384": ((4, 4, 384, 64, 64, 0, 0, False),
             "7cfe88f16680b390785c11c101ee5a8b0c308c81"),
    "s1024_head_bias": ((8, 8, 1024, 64, 64, 0, 8, False),
                        "1de7130c68ff644a9696edfa12abcec6b7e6a159"),
}


@pytest.mark.parametrize("name", sorted(MULTI_PASS_PROGRAMS))
def test_multi_pass_programs_are_the_ones_pinned(name):
    """THE PIN for the cells whose backward is two passes: the whole traced
    program of a layer's attention, forward and backward, kernel bodies,
    grids, index maps and VMEM limits included, is the text it was when
    the cells' ``hbm_peak_gb`` was last read on the chip (PERF.md section
    6, PR 36).  The unrolled two are the text of the tree before the row
    layout existed; the looped three are that text with the logsumexp's
    ``broadcast_in_dim`` three equations later, and compile to the same
    Moonlight step instruction for instruction.  XLA schedules a whole
    step anew around a changed custom call (254 MB more of temporaries in
    the Moonlight cell, one bound's worth: ledger, PR 35), so a hash that
    moves means: compile the step (``tools/step_memory.py``), read the
    three cells on the chip, then pin the new one."""
    import hashlib
    (BH, BH_kv, S, D_qk, D_v, R, bias_rows, causal), want = \
        MULTI_PASS_PROGRAMS[name]

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    bias = arr(bias_rows, S, S) if bias_rows else None
    rope = (arr(BH, S, R), arr(1, S, R)) if R else None

    def loss(q, k, v, bias, rope):
        out, lse = pallas_ops.flash_attention_lse(q, k, v, bias, 0.125,
                                                  causal, rope)
        return out.astype(jnp.float32).sum() + lse.sum() * 0
    wanted = (0, 1, 2) + ((3,) if bias_rows else ()) + ((4,) if R else ())
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=wanted))(
        arr(BH, S, D_qk), arr(BH_kv, S, D_qk), arr(BH_kv, S, D_v), bias,
        rope))
    assert "f32[%d,1,%d]" % (BH, S) not in text          # no row layout
    assert hashlib.sha1(text.encode()).hexdigest() == want


def test_a_shared_mask_is_never_copied_a_head():
    """The step lowered for a mask every head of a sequence shares holds
    the ``[B, S_q, S_kv]`` mask and no ``[B * H, S_q, S_kv]`` array, in
    ``fused_attention`` and in its grad op alike."""
    from paddle_tpu.fluid import executor

    S_q, S_kv = 128, 256
    shape = (B, 1, S_q, S_kv)
    main, startup, fetches = _program(S_q=S_q, S_kv=S_kv, bias_shape=shape,
                                      bias_grad=False)
    feed = _feed(S_q, S_kv, shape)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        compiled, feed_vals = exe._resolve_compiled(main, feed, fetches,
                                                    scope, None)
        text = compiled._jitted.lower(
            executor._scope_state(scope, compiled.state_mut),
            executor._scope_state(scope, compiled.state_ro),
            tuple(feed_vals), np.int32(0)).as_text()
    assert "tensor<%dx%dx%dx" % (B, S_q, S_kv) in text
    assert "tensor<%dx%dx%dx" % (B * H, S_q, S_kv) not in text
    assert "tensor<%dx%dx%dx%dx" % (B, H, S_q, S_kv) not in text


# -- heads in the minor dimension: ``num_heads`` ------------------------------

def _minor(x):
    """``[B, H, S, D]`` -> ``[B, S, H * D]``, on the host."""
    b, h, s, d = x.shape
    return np.ascontiguousarray(x.transpose(0, 2, 1, 3)).reshape(b, s, h * d)


def _layout_program(minor, heads=2, kv_heads=None, head=64, S=128,
                    bias_shape=None, bias_grad=False, causal=False,
                    dropout=0.0, rope=0, backward=True, lse=True):
    """ONE attention op under ``sum(out * w)``, its operands in the
    heads-minor layout (``minor``: Q ``[B, S, H * D]``, ``num_heads=H``) or
    as ``[B, H, S, D]``; K and V at ``kv_heads`` heads, with ``rope`` a
    rotary pair of that size.  Fetches: loss, ``LSE`` (``lse``: the
    composition writes none), then the gradients of q, k, v, the rotary
    pair and (``bias_grad``) the bias."""
    kv_heads = kv_heads or heads

    def operand(name, n, width, grad=True):
        return _data(name, (B, S, n * width) if minor else (B, n, S, width),
                     grad=grad)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ins = [operand("q", heads, head), operand("k", kv_heads, head),
               operand("v", kv_heads, head)]
        pair = {}
        if rope:
            pair = dict(q_rope=operand("qr", heads, rope),
                        k_rope=_data("kr", (B, S, rope) if minor
                                     else (B, 1, S, rope)))
            ins += list(pair.values())
        b = None
        if bias_shape is not None:
            b = _data("b", bias_shape, grad=bias_grad)
            ins.append(b)
        w = operand("w", heads, head, grad=False)
        out = layers.fused_attention(
            *ins[:3], b, scale=head ** -0.5, causal=causal,
            dropout_prob=dropout, num_heads=heads if minor else None, **pair)
        assert tuple(out.shape) == tuple(w.shape)
        loss = layers.reduce_sum(out * w)
        stat = main.global_block().var(next(
            op for op in main.global_block().ops
            if op.type == "fused_attention").output("LSE")[0])
        assert tuple(stat.shape) == (B, heads, S)
        wanted = [x for x in ins if not x.stop_gradient]
        grads = fluid.gradients(loss, wanted) if backward else []
    return main, startup, [loss] + [stat] * lse + grads


def _layout_feed(heads=2, kv_heads=None, head=64, S=128, bias_shape=None,
                 rope=0, seed=3, **_):
    """The ``[B, H, S, D]`` feed and the same numbers heads-minor."""
    kv_heads = kv_heads or heads
    rng = np.random.RandomState(seed)

    def arr(*dims, scale=0.5):
        return (rng.randn(*dims) * scale).astype(np.float32)
    major = {"q": arr(B, heads, S, head), "k": arr(B, kv_heads, S, head),
             "v": arr(B, kv_heads, S, head), "w": arr(B, heads, S, head)}
    if rope:
        major.update(qr=arr(B, heads, S, rope), kr=arr(B, 1, S, rope))
    minor = {n: _minor(x) for n, x in major.items()}
    if bias_shape is not None:
        major["b"] = minor["b"] = arr(*bias_shape, scale=0.3)
    return major, minor


def _tiles_by_layout(dropout=False):
    """``flash_tiles_total`` by (layout, kernel), and with ``dropout`` by
    (layout, kernel, dropout)."""
    c = telemetry.counter("flash_tiles_total")
    if dropout:
        return {(layout, kernel, d): c.value(layout=layout, kernel=kernel,
                                             dropout=d)
                for layout in ("bshd", "bhsd")
                for kernel in ("fwd", "bwd", "dq", "dkv", "dbias")
                for d in ("none", "in_kernel")}
    return {(layout, kernel): c.value(layout=layout, kernel=kernel)
            for layout in ("bshd", "bhsd")
            for kernel in ("fwd", "bwd", "dq", "dkv", "dbias")}


# the seed every dropout op of a test draws from, where a test fixes it
SEED = 20240
_HEAD_MASKS = {}


def _the_kernels_mask(shape, rate):
    """bool ``[B, H, S_q, S_kv]``: what the in-place kernels draw from
    ``SEED`` for heads laid out so (``_rebuilt_mask``)."""
    key = (tuple(shape), rate)
    if key not in _HEAD_MASKS:
        b, h, S_q, S_kv = shape
        _HEAD_MASKS[key] = _rebuilt_mask(SEED, b * h, S_q, S_kv,
                                         rate).reshape(shape)
    return _HEAD_MASKS[key]


@pytest.fixture
def one_mask(monkeypatch):
    """Every dropout op draws what the in-place kernels draw from ``SEED``:
    the kernels' seed is fixed, and the composition's ``bernoulli`` is
    handed the same mask, so a 4-D op and the in-place kernels drop the
    same probabilities."""
    monkeypatch.setattr(pallas_ops, "_dropout_seed",
                        lambda ctx: jnp.array([SEED], jnp.int32))
    monkeypatch.setattr(jax.random, "bernoulli",
                        lambda key, p, shape: jnp.asarray(
                            _the_kernels_mask(shape, 1.0 - p)))


def _lowered_by_layout():
    c = telemetry.counter("fused_attention_lowered_total")
    return {(layout, path): c.value(layout=layout, path=path)
            for layout in ("bshd", "bhsd")
            for path in ("flash", "composition")}


def _moved(before, after):
    return {k: after[k] - n for k, n in before.items() if after[k] != n}


# case -> (program keywords, the kernels its step traces with each call's
# number of outputs, flash_tiles_total's and fused_attention_lowered_total's
# increments by (layout, ...))
ROUTES = {
    # read in place: a pair of heads of 64 a cell, the mask once a cell
    "pairs_mask": (
        dict(bias_shape=(B, 1, 128, 128)),
        {"flash_fwd": [2], "flash_bwd": [3]},
        {("bshd", "fwd"): 1, ("bshd", "bwd"): 1}, {("bshd", "flash"): 1}),
    "pairs_causal": (
        dict(causal=True),
        {"flash_fwd": [2], "flash_bwd": [3]},
        {("bshd", "fwd"): 1, ("bshd", "bwd"): 1}, {("bshd", "flash"): 1}),
    "one_head_of_128": (
        dict(heads=3, head=128, bias_shape=(B, 1, 1, 128)),
        {"flash_fwd": [2], "flash_bwd": [3]},
        {("bshd", "fwd"): 1, ("bshd", "bwd"): 1}, {("bshd", "flash"): 1}),
    "head_bias_no_grad": (
        dict(heads=4, bias_shape=(B, 4, 128, 128)),
        {"flash_fwd": [2], "flash_bwd": [3]},
        {("bshd", "fwd"): 1, ("bshd", "bwd"): 1}, {("bshd", "flash"): 1}),
    # a wanted bias gradient: the forward in place, the backward and its
    # dbias pass (which writes a head's [BH, S_q, S_kv]) on split heads
    "head_bias_grad": (
        dict(bias_shape=(B, 2, 128, 128), bias_grad=True),
        {"flash_fwd": [2], "flash_bwd": [4], "flash_dbias": [1]},
        {("bshd", "fwd"): 1, ("bhsd", "bwd"): 1, ("bhsd", "dbias"): 1},
        {("bshd", "flash"): 1}),
    # split inside the lowering: what the 4-D op traces
    "odd_heads_of_64": (
        dict(heads=3, bias_shape=(B, 1, 128, 128)),
        {"flash_fwd": [2], "flash_bwd": [3]},
        {("bhsd", "fwd"): 1, ("bhsd", "bwd"): 1}, {("bhsd", "flash"): 1}),
    "eight_heads_of_16": (                   # eight heads a cell
        dict(heads=8, head=16),
        {"flash_fwd": [2], "flash_bwd": [3]},
        {("bshd", "fwd"): 1, ("bshd", "bwd"): 1}, {("bshd", "flash"): 1}),
    "four_heads_of_16": (                    # half a block of 128 lanes
        dict(heads=4, head=16),
        {"flash_fwd": [2], "flash_bwd": [3]},
        {("bhsd", "fwd"): 1, ("bhsd", "bwd"): 1}, {("bhsd", "flash"): 1}),
    "s1024": (
        dict(S=1024),
        {"flash_fwd": [2], "flash_dq": [2], "flash_dkv": [2]},
        {("bhsd", "fwd"): 1, ("bhsd", "dq"): 1, ("bhsd", "dkv"): 1},
        {("bhsd", "flash"): 1}),
    "grouped_heads": (
        dict(heads=4, kv_heads=2, causal=True),
        {"flash_fwd": [2], "flash_dq": [1], "flash_dkv": [2]},
        {("bhsd", "fwd"): 1, ("bhsd", "dq"): 1, ("bhsd", "dkv"): 1},
        {("bhsd", "flash"): 1}),
    "rotary_pair": (
        dict(causal=True, rope=32),
        {"flash_fwd": [2], "flash_dq": [2], "flash_dkv": [3]},
        {("bhsd", "fwd"): 1, ("bhsd", "dq"): 1, ("bhsd", "dkv"): 1},
        {("bhsd", "flash"): 1}),
    # attention dropout at a shape the kernels read in place and whose
    # scores outnumber what they keep (``_drop_in_kernels``: S=256 at D=64):
    # they draw the mask themselves (``dropout="in_kernel"``), with a mask
    # that wants no gradient or without one (the 4-D op composes and
    # writes no ``LSE``)
    "dropout": (
        dict(dropout=0.1, S=256, bias_shape=(B, 1, 256, 256), lse=False),
        {"flash_fwd": [2], "flash_bwd": [3]},
        {("bshd", "fwd", "in_kernel"): 1, ("bshd", "bwd", "in_kernel"): 1},
        {("bshd", "flash"): 1}),
    "dropout_causal": (
        dict(dropout=0.1, S=256, causal=True, lse=False),
        {"flash_fwd": [2], "flash_bwd": [3]},
        {("bshd", "fwd", "in_kernel"): 1, ("bshd", "bwd", "in_kernel"): 1},
        {("bshd", "flash"): 1}),
    # every other op with dropout composes, forward and replay: one tile
    # whose scores are fewer than the operands the kernels would keep
    # (S=128 at D=64), several tiles a head, no tile, a bias whose
    # gradient is wanted (the dbias pass has no mask)
    "dropout_s128": (
        dict(dropout=0.1, bias_shape=(B, 1, 128, 128), lse=False), {}, {},
        {("bhsd", "composition"): 2}),
    "dropout_s1024": (
        dict(dropout=0.1, S=1024, lse=False), {}, {},
        {("bhsd", "composition"): 2}),
    "dropout_s192": (
        dict(dropout=0.1, S=192, lse=False), {}, {},
        {("bhsd", "composition"): 2}),
    "dropout_bias_grad": (
        dict(dropout=0.1, bias_shape=(B, 1, 128, 128), bias_grad=True,
             lse=False), {}, {}, {("bhsd", "composition"): 2}),
    "no_tile_s192": (
        dict(S=192, lse=False), {}, {}, {("bhsd", "composition"): 2}),
}


@pytest.mark.parametrize("case", sorted(ROUTES))
def test_heads_minor_op_takes_its_route_and_gives_the_4d_ops_numbers(
        kernel_calls, one_mask, case):
    """An op with ``num_heads`` runs the kernels on its operands in place
    where a head is one tile and the heads pack whole into 128 lanes, and
    is the 4-D op between a split and a merge everywhere else; either way
    the loss, ``LSE`` and every gradient are the 4-D op's on the same
    numbers (with dropout: the same mask, ``one_mask``, whether the
    kernels draw it or the composition is handed it)."""
    kw, kernels, tiles, lowered = ROUTES[case]
    by_dropout = bool(kw.get("dropout")) and bool(tiles)
    major, minor = _layout_feed(**kw)
    want = _run(*_layout_program(False, **kw), major)
    kernel_calls.clear()
    before = _tiles_by_layout(by_dropout), _lowered_by_layout()
    got = _run(*_layout_program(True, **kw), minor)
    assert dict(kernel_calls) == kernels
    assert _moved(before[0], _tiles_by_layout(by_dropout)) == tiles
    assert _moved(before[1], _lowered_by_layout()) == lowered
    names = ["loss"] + ["lse"] * kw.get("lse", True) + ["dq", "dk", "dv"] + \
        (["dqr", "dkr"] if kw.get("rope") else []) + \
        (["dbias"] if kw.get("bias_grad") else [])
    assert len(got) == len(want) == len(names)
    for name, a, b in zip(names, got, want):
        if name == "dkr":
            b = b[:, 0]
        elif name[0] == "d" and name != "dbias":
            b = _minor(b)
        assert a.shape == b.shape, name
        # ``LSE`` is the same array under both layouts
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)


def test_heads_minor_for_test_clone_reads_in_place_and_asks_for_no_lse(
        kernel_calls):
    kw = dict(bias_shape=(B, 1, 128, 128), backward=False)
    major, minor = _layout_feed(**kw)
    main, startup, fetches = _layout_program(True, **kw)
    train = _run(main, startup, fetches[:1], minor)[0]
    assert kernel_calls.pop("flash_fwd") == [2]
    before = _tiles_by_layout()
    infer = _run(main.clone(for_test=True), startup, fetches[:1], minor)[0]
    assert dict(kernel_calls) == {"flash_fwd": [1]}
    assert _moved(before, _tiles_by_layout()) == {("bshd", "fwd"): 1}
    want = _run(*_layout_program(False, **kw)[:2],
                _layout_program(False, **kw)[2][:1], major)[0]
    np.testing.assert_allclose(infer, train, rtol=1e-6)
    np.testing.assert_allclose(infer, want, rtol=1e-5)   # another sum order


def test_heads_minor_program_without_the_lse_slot_replays_in_place(
        kernel_calls):
    """The grad op of a program built before the ``LSE`` slot existed
    differentiates a second forward, in place too: ``jax.vjp`` of
    ``flash_attention_in_place``."""
    kw = dict()
    major, minor = _layout_feed(**kw)
    main, startup, fetches = _layout_program(True, **kw)
    new = _run(main, startup, [fetches[0]] + fetches[2:], minor)
    kernel_calls.clear()
    main, startup, fetches = _layout_program(True, **kw)
    for op in main.global_block().ops:
        if op.type in ("fused_attention", "fused_attention_grad"):
            op.outputs.pop("LSE", None)
            op.inputs.pop("LSE", None)
            (op.attrs.get("__fwd_outputs__") or {}).pop("LSE", None)
    before = _grad_paths()
    old = _run(main, startup, [fetches[0]] + fetches[2:], minor)
    assert _paths_taken(before) == (0, 1)
    assert {n: len(c) for n, c in kernel_calls.items()} == {
        "flash_fwd": 2, "flash_bwd": 1}
    for a, b in zip(old, new):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def _tiny_fused_bert(attn_dropout=0.0, layers_=2, heads=2, head=64, S=128):
    from paddle_tpu import models

    cfg = models.bert.BertConfig(
        vocab_size=128, hidden_size=heads * head, num_layers=layers_,
        num_heads=heads, ffn_size=128, max_position=S, type_vocab_size=2,
        hidden_dropout=0.0, attn_dropout=attn_dropout, max_seq_len=S)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = models.bert.build_pretrain(
            cfg, optimizer=fluid.optimizer.SGD(0.01),
            max_pred_per_seq=4)["loss"]
    rng = np.random.default_rng(0)
    feed = {
        "src_ids": rng.integers(0, 128, (B, S, 1), dtype=np.int64),
        "pos_ids": np.tile(np.arange(S, dtype=np.int64)[None, :, None],
                           (B, 1, 1)),
        "sent_ids": np.zeros((B, S, 1), np.int64),
        "input_mask": np.ones((B, S, 1), np.float32),
        "mask_pos": (rng.integers(0, S, (B, 4)) + np.arange(B)[:, None] * S)
        .reshape(-1, 1).astype(np.int32),
        "mask_label": rng.integers(0, 128, (B * 4, 1), dtype=np.int64),
        "nsp_label": rng.integers(0, 2, (B, 1), dtype=np.int64),
    }
    return main, startup, loss, feed, (heads, head, S)


def test_tiny_fused_bert_step_holds_no_head_split_or_merge(kernel_calls):
    """The program of a fused BERT has no ``transpose2`` around attention,
    its step traces one ``flash_fwd`` and one ``flash_bwd`` a layer at
    ``layout=bshd``, and nothing in the lowered step has the shape of a
    split head (``[B, H, S, D]``, or ``[B, S, H, D]`` on the way there):
    Q, K, V, the output and their gradients stay ``[B, S, H * D]``."""
    from paddle_tpu.fluid import executor

    main, startup, loss, feed, (heads, head, S) = _tiny_fused_bert()
    kinds = [op.type for op in main.global_block().ops]
    assert kinds.count("fused_attention") == 2
    assert "transpose2" not in kinds and "transpose2_grad" not in kinds
    before = _tiles_by_layout(), _lowered_by_layout()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        compiled, feed_vals = exe._resolve_compiled(main, feed, [loss],
                                                    scope, None)
        text = compiled._jitted.lower(
            executor._scope_state(scope, compiled.state_mut),
            executor._scope_state(scope, compiled.state_ro),
            tuple(feed_vals), np.int32(0)).as_text()
        assert _moved(before[0], _tiles_by_layout()) == {
            ("bshd", "fwd"): 2, ("bshd", "bwd"): 2}
        assert _moved(before[1], _lowered_by_layout()) == {
            ("bshd", "flash"): 2}
        assert {n: len(c) for n, c in kernel_calls.items()} == {
            "flash_fwd": 2, "flash_bwd": 2}
        losses = [float(np.asarray(exe.run(main, feed=feed,
                                           fetch_list=[loss])[0]).reshape(()))
                  for _ in range(3)]
    assert "tensor<%dx%dx%dx%dx" % (B, heads, S, head) not in text
    assert "tensor<%dx%dx%dx%dx" % (B, S, heads, head) not in text
    assert "tensor<%dx%dx%dx" % (B * heads, S, head) not in text
    assert "tensor<%dx%dx%dxf32>" % (B, S, heads * head) in text
    assert losses[-1] < losses[0]


def test_tiny_fused_bert_with_attention_dropout_draws_it_in_the_kernels():
    """BERT as published (attention dropout 0.1) at a shape a head is one
    tile (S=256): the kernels read Q, K, V in place and draw the mask
    themselves, one ``flash_fwd`` and one ``flash_bwd`` a layer at
    ``dropout="in_kernel"``, with no composition and no transpose; the
    loss falls."""
    main, startup, loss, feed, _ = _tiny_fused_bert(attn_dropout=0.1, S=256)
    kinds = [op.type for op in main.global_block().ops]
    assert "transpose2" not in kinds
    before = _lowered_by_layout(), _tiles_by_layout(dropout=True)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [float(np.asarray(exe.run(main, feed=feed,
                                           fetch_list=[loss])[0]).reshape(()))
                  for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert _moved(before[0], _lowered_by_layout()) == {("bshd", "flash"): 2}
    assert _moved(before[1], _tiles_by_layout(dropout=True)) == {
        ("bshd", "fwd", "in_kernel"): 2, ("bshd", "bwd", "in_kernel"): 2}


# -- attention dropout inside the in-place kernels (PR 40) -------------------

# B=2, S=128, four heads of 64 (two cells of a pair a sequence)
DROP_HEADS, DROP_S, RATE = 4, 128, 0.1
_drop_runs = {}


def _dropout_case(bias, causal):
    """``(got, want)`` by name (out, lse, dq, dk, dv): the in-place kernels
    with dropout from ``SEED``, and ``_attn_core`` (the composition the
    4-D op runs) handed their mask as its ``bernoulli``, its logsumexp
    that of the undropped scores; cached."""
    key = (bias, causal)
    if key in _drop_runs:
        return _drop_runs[key]
    rng = np.random.RandomState(17 + 2 * bias + causal)
    shape = (B, DROP_S, DROP_HEADS * 64)
    q, k, v, g = (jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.5)
                  for _ in range(4))
    mask = jnp.asarray(rng.randn(B, DROP_S, DROP_S).astype(np.float32)
                       * 0.3) if bias else None
    seed = jnp.array([SEED], jnp.int32)
    out, lse = pallas_ops._flash_fwd_in_place(q, k, v, mask, 0.125,
                                              DROP_HEADS, causal, True,
                                              RATE, seed)
    got = dict(zip(("out", "lse", "dq", "dk", "dv"), (out, lse) + tuple(
        pallas_ops._backward_in_place(q, k, v, mask, 0.125, causal,
                                      DROP_HEADS, lse, g, RATE, seed))))
    kept = jnp.asarray(_the_kernels_mask((B, DROP_HEADS, DROP_S, DROP_S),
                                         RATE))

    def split(x):
        return pallas_ops._heads_major(x, DROP_HEADS)

    def core(q, k, v):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax.random, "bernoulli", lambda key, p, sh: kept)
            o = pallas_ops._attn_core(
                split(q), split(k), split(v),
                None if mask is None else mask[:, None], 0.125, causal, 0,
                RATE, jax.random.PRNGKey(0))
        return pallas_ops._heads_minor(o)
    ref_out, vjp = jax.vjp(core, q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", split(q), split(k)) * 0.125
    if mask is not None:
        s = s + mask[:, None]
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((DROP_S, DROP_S), bool)), s,
                      pallas_ops._NEG)
    want = dict(zip(("out", "lse", "dq", "dk", "dv"),
                    (ref_out, jax.nn.logsumexp(s, -1).reshape(-1, DROP_S))
                    + tuple(vjp(g))))
    _drop_runs[key] = got, want
    return got, want


@pytest.mark.parametrize("what", ["out", "lse", "dq", "dk", "dv"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "mask"])
def test_in_kernel_dropout_is_the_composition_fed_the_kernels_mask(
        bias, causal, what):
    """``flash_fwd`` and ``flash_bwd`` in place with the keep mask drawn
    inside them: Out, ``LSE``, dQ, dK and dV are ``_attn_core``'s (the
    4-D op's composition) fed the same mask, rebuilt outside the kernels
    from ``_keep_bits``' interpreted branch; ``LSE`` is the undropped
    scores'."""
    got, want = _dropout_case(bias, causal)
    assert got[what].shape == want[what].shape
    np.testing.assert_allclose(np.asarray(got[what]), np.asarray(want[what]),
                               rtol=2e-5, atol=2e-5)


def test_forward_and_grad_op_draw_one_mask_and_two_steps_two(monkeypatch):
    """The op's seed comes from its own key (``ctx.rng()``): the grad op
    remakes the forward's, so its gradients are those of the mask the
    forward drew (the composition fed that mask agrees), and the next step
    draws another."""
    seen = []
    real = pallas_ops._dropout_seed

    def spy(ctx):
        seed = real(ctx)
        jax.debug.callback(lambda s: seen.append(int(np.asarray(s)[0])),
                           seed)
        return seed
    monkeypatch.setattr(pallas_ops, "_dropout_seed", spy)
    kw = dict(dropout=RATE, S=256, bias_shape=(B, 1, 256, 256), lse=False)
    major, minor = _layout_feed(**kw)
    main, startup, fetches = _layout_program(True, **kw)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        steps = [[np.asarray(x) for x in exe.run(main, feed=minor,
                                                  fetch_list=fetches)]
                 for _ in range(2)]
    assert len(seen) == 4 and seen[0] == seen[1] and seen[2] == seen[3]
    assert seen[0] != seen[2]
    assert not np.allclose(steps[0][0], steps[1][0])
    # the first step against the 4-D composition fed that step's mask
    monkeypatch.setattr(pallas_ops, "_dropout_seed", real)
    kept = jnp.asarray(_rebuilt_mask(seen[0], B * 2, 256, 256, RATE)
                       .reshape(B, 2, 256, 256))
    monkeypatch.setattr(jax.random, "bernoulli", lambda key, p, sh: kept)
    want = _run(*_layout_program(False, **kw), major)
    for name, a, b in zip(("loss", "dq", "dk", "dv"), steps[0], want):
        b = b if name == "loss" else _minor(b)
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)


def test_for_test_clone_of_a_dropout_op_runs_rate_0(kernel_calls):
    """Under ``is_test`` the rate is 0: the clone reads in place, draws no
    mask (``dropout="none"``) and gives what the op without dropout
    gives."""
    kw = dict(dropout=RATE, bias_shape=(B, 1, 128, 128), backward=False,
              lse=False)
    major, minor = _layout_feed(**kw)
    main, startup, fetches = _layout_program(True, **kw)
    before = _tiles_by_layout(dropout=True)
    infer, = _run(main.clone(for_test=True), startup, fetches, minor)
    assert _moved(before, _tiles_by_layout(dropout=True)) == {
        ("bshd", "fwd", "none"): 1}
    assert dict(kernel_calls) == {"flash_fwd": [1]}
    plain, = _run(*_layout_program(True, **dict(kw, dropout=0.0)), minor)
    np.testing.assert_allclose(infer, plain, rtol=1e-6)


# (B, heads, S, D, rows of bias) of the op in place -> sha1 of the jaxpr of
# what its lowering and its grad op's trace: ``flash_attention_in_place``
# and ``_backward_in_place``, float32 as the BERT cells hand them over
ONE_TILE_PROGRAMS = {
    # bert_base_s512_flash: 12 heads of 64, S=512, the padding mask a
    # sequence, no dropout; the hash of the tree before in-kernel dropout
    "flash_cell": ((32, 12, 512, 64, 32),
                   "3a8cc238167f4204c304c65d854896f9d3ca770f"),
}


@pytest.mark.parametrize("name", sorted(ONE_TILE_PROGRAMS))
def test_one_tile_programs_at_rate_0_are_the_ones_pinned(name):
    """THE PIN for the flash cell: at rate 0 the in-place program, kernel
    bodies, grids, index maps and VMEM limits included, is the text it was
    before dropout could be drawn in the kernels (the rate is static: no
    seed operand, no draw, no other limit)."""
    import hashlib
    (batch, heads, S, D, bias_rows), want = ONE_TILE_PROGRAMS[name]

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def step(q, k, v, bias, g):
        out, lse = pallas_ops.flash_attention_in_place(q, k, v, bias, 0.125,
                                                       False, heads, True)
        return (out,) + tuple(pallas_ops._backward_in_place(
            q, k, v, bias, 0.125, False, heads, lse, g))
    x = arr(batch, S, heads * D)
    text = str(jax.make_jaxpr(step)(x, x, x, arr(bias_rows, S, S), x))
    assert "prng" not in text
    assert hashlib.sha1(text.encode()).hexdigest() == want
