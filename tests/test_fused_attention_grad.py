"""``fused_attention_grad`` runs the flash backward kernels on the forward
op's ``LSE`` instead of replaying the forward lowering (which traced a
second ``flash_fwd`` Mosaic call XLA cannot merge with the first).

CPU, interpret-mode kernels: counts of kernel calls per traced step, which
grad ops take which path, and gradient parity through the Fluid op against
``_reference_attention``'s vjp at test_pallas_attention.py's tolerances.
"""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, telemetry
from paddle_tpu.fluid.ops import pallas_ops
from paddle_tpu.fluid.ops.pallas_ops import _reference_attention

B, H, D = 2, 2, 16


def _data(name, shape, dtype="float32", grad=True):
    v = layers.data(name=name, shape=list(shape), dtype=dtype,
                    append_batch_size=False)
    v.stop_gradient = not grad
    return v


def _program(S_q=128, S_kv=128, bias_shape=None, causal=False, dropout=0.0,
             n_ops=1, with_lse=True, cast=None, backward=True):
    """``n_ops`` chained attention ops (each one's output is the next
    one's Q) under the loss ``sum(out * w)``; the fetch list is the loss
    and the gradients of q, k, v and the bias.  ``with_lse=False`` builds
    the op as a program from before the ``LSE`` slot existed."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ins = [_data("q", (B, H, S_q, D)), _data("k", (B, H, S_kv, D)),
               _data("v", (B, H, S_kv, D))]
        if bias_shape is not None:
            ins.append(_data("b", bias_shape))
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
        grads = fluid.gradients(loss, ins) if backward else []
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
    want = pallas_ops._row_delta(g, out)
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
    dq, dk, dv, formed = pallas_ops._flash_bwd(
        q, k, v, bias, 0.25, lse, g, causal, delta, delta_out=True)
    want_dq, want_delta = pallas_ops._flash_dq(q, k, v, bias, 0.25, lse, g,
                                               causal, delta)
    want_dk, want_dv = pallas_ops._flash_dkv(q, k, v, bias, 0.25, lse, g,
                                             causal, want_delta)
    if passed:
        assert formed is delta
    for name, a, b in (("dq", dq, want_dq), ("dk", dk, want_dk),
                       ("dv", dv, want_dv), ("delta", formed, want_delta)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7, err_msg=name)
    # a delta nothing reads is not written
    assert pallas_ops._flash_bwd(q, k, v, bias, 0.25, lse, g, causal,
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


def test_dropout_and_untileable_shapes_keep_the_replay(kernel_calls):
    before = _grad_paths()
    feed = _feed()
    # attention dropout: the exact composition, replayed with its key
    _run(*_program(dropout=0.1), feed)
    assert _paths_taken(before) == (0, 1)
    assert not kernel_calls
    # S = 192 does not tile into 128-row blocks: the reference composition
    before = _grad_paths()
    feed = _feed(192, 192, seed=2)
    got = _run(*_program(S_q=192, S_kv=192), feed)
    assert _paths_taken(before) == (0, 1)
    assert not kernel_calls
    for name, a, b in zip(("loss", "dq", "dk", "dv"), got,
                          _reference_grads(feed, False)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


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
