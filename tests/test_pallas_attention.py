"""Pallas flash-attention kernel vs the XLA composition oracle.

Runs the kernel in interpret mode on the CPU mesh (identical numerics to
the TPU path); checks forward parity, bias handling, and exact gradient
agreement with the composed softmax(QK^T)V.
"""

import math

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.ops.pallas_ops import (flash_attention,
                                             _reference_attention)

B, H, S, D = 2, 3, 128, 16


def _qkvb(seed=0, bias=True):
    rng = np.random.RandomState(seed)
    q = rng.randn(B * H, S, D).astype(np.float32)
    k = rng.randn(B * H, S, D).astype(np.float32)
    v = rng.randn(B * H, S, D).astype(np.float32)
    b = None
    if bias:
        b = np.where(rng.rand(B * H, S, S) < 0.1, -1e4,
                     0.0).astype(np.float32)
    return q, k, v, b


def test_flash_forward_matches_reference():
    import jax.numpy as jnp
    q, k, v, b = _qkvb()
    scale = 1.0 / math.sqrt(D)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          jnp.asarray(b), scale)
    ref = _reference_attention(q, k, v, b, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_no_bias_and_grads():
    import jax
    import jax.numpy as jnp
    q, k, v, _ = _qkvb(seed=1, bias=False)
    scale = 0.2

    def loss_flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, None, scale).sum()

    def loss_ref(q_, k_, v_):
        return _reference_attention(q_, k_, v_, None, scale).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


def test_fused_attention_op_in_program():
    rng = np.random.RandomState(2)
    q = rng.randn(B, H, S, D).astype(np.float32)
    k = rng.randn(B, H, S, D).astype(np.float32)
    v = rng.randn(B, H, S, D).astype(np.float32)
    bias = np.zeros((B, 1, S, S), np.float32)
    bias[:, :, :, S // 2:] = -1e4          # mask the second half of keys
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            qv = layers.data(name="q", shape=[B, H, S, D], dtype="float32",
                             append_batch_size=False)
            kv = layers.data(name="k", shape=[B, H, S, D], dtype="float32",
                             append_batch_size=False)
            vv = layers.data(name="v", shape=[B, H, S, D], dtype="float32",
                             append_batch_size=False)
            bv = layers.data(name="b", shape=[B, 1, S, S], dtype="float32",
                             append_batch_size=False)
            out = layers.fused_attention(qv, kv, vv, bv,
                                         scale=1.0 / math.sqrt(D))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = np.asarray(exe.run(main, feed={"q": q, "k": k, "v": v,
                                             "b": bias},
                                 fetch_list=[out])[0])
    ref = _reference_attention(
        q.reshape(B * H, S, D), k.reshape(B * H, S, D),
        v.reshape(B * H, S, D),
        np.broadcast_to(bias, (B, H, S, S)).reshape(B * H, S, S),
        1.0 / math.sqrt(D)).reshape(B, H, S, D)
    np.testing.assert_allclose(got, np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_bert_fused_vs_composed_parity():
    """BERT encoder with the pallas core == matmul+softmax composition."""
    from paddle_tpu import models

    rng = np.random.RandomState(3)
    Bz = 2
    outs = []
    for fused in (True, False):
        cfg = models.bert.tiny_config(attn_dropout=0.0, hidden_dropout=0.0,
                                      use_fused_attention=fused)
        Ssz = cfg.max_seq_len
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                src = layers.data(name="src", shape=[Ssz, 1], dtype="int64")
                pos = layers.data(name="pos", shape=[Ssz, 1], dtype="int64")
                sent = layers.data(name="sent", shape=[Ssz, 1],
                                   dtype="int64")
                mask = layers.data(name="mask", shape=[Ssz, 1],
                                   dtype="float32")
                enc = models.bert.bert_encoder(src, pos, sent, mask, cfg)
        kinds = [op.type for op in main.global_block().ops]
        assert ("fused_attention" in kinds) == fused
        feed = {
            "src": np.random.RandomState(7).randint(
                0, cfg.vocab_size, (Bz, Ssz, 1)).astype(np.int64),
            "pos": np.tile(np.arange(Ssz)[None, :, None], (Bz, 1, 1))
            .astype(np.int64),
            "sent": np.zeros((Bz, Ssz, 1), np.int64),
            "mask": np.ones((Bz, Ssz, 1), np.float32),
        }
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            outs.append(np.asarray(exe.run(main, feed=feed,
                                           fetch_list=[enc])[0]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)


def test_fused_layer_norm_matches_and_grads():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fluid.ops.pallas_ops import (fused_layer_norm,
                                                 _reference_layer_norm)
    rng = np.random.RandomState(4)
    x = rng.randn(64, 96).astype(np.float32) * 3 + 1
    scale = rng.rand(96).astype(np.float32) + 0.5
    bias = rng.randn(96).astype(np.float32)
    out = fused_layer_norm(jnp.asarray(x), jnp.asarray(scale),
                           jnp.asarray(bias), 1e-5)
    ref = _reference_layer_norm(x, scale, bias, 1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_f(a, s, b):
        return (fused_layer_norm(a, s, b, 1e-5) ** 2).sum()

    def loss_r(a, s, b):
        return (_reference_layer_norm(a, s, b, 1e-5) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(scale), jnp.asarray(bias))
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(scale), jnp.asarray(bias))
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_fused_layer_norm_op_in_program():
    rng = np.random.RandomState(5)
    x = rng.randn(4, 8, 32).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            xv = layers.data(name="x", shape=[4, 8, 32], dtype="float32",
                             append_batch_size=False)
            blk = main.global_block()
            y = blk.create_var(name="ln_y")
            mean = blk.create_var(name="ln_m")
            var = blk.create_var(name="ln_v")
            blk.append_op("fused_layer_norm", inputs={"X": [xv]},
                          outputs={"Y": [y], "Mean": [mean],
                                   "Variance": [var]},
                          attrs={"begin_norm_axis": 2, "epsilon": 1e-5})
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = np.asarray(exe.run(main, feed={"x": x},
                                 fetch_list=[y])[0])
    mu = x.mean(-1, keepdims=True)
    want = (x - mu) / np.sqrt(x.var(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_forward_bf16_matches_reference():
    """bf16 inputs exercise the input-dtype dot path (bf16 QK^T and the
    bf16 p-cast before the PV dot, fp32 accumulation + softmax state);
    parity vs the fp32 composed oracle within bf16 tolerances."""
    import jax.numpy as jnp
    q, k, v, b = _qkvb(seed=3)
    scale = 1.0 / math.sqrt(D)
    out = flash_attention(jnp.asarray(q, jnp.bfloat16),
                          jnp.asarray(k, jnp.bfloat16),
                          jnp.asarray(v, jnp.bfloat16),
                          jnp.asarray(b, jnp.bfloat16), scale)
    assert out.dtype == jnp.bfloat16
    ref = _reference_attention(q, k, v, np.where(b < 0, -1e4, 0.0), scale)
    # bf16 mantissa is 8 bits: elementwise agreement to ~1e-2 relative
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=3e-2, atol=3e-2)


def test_flash_backward_bf16_runs_and_matches_fp32_grads():
    """The custom_vjp backward (reference recompute) under bf16 inputs:
    grads agree in direction/magnitude with the fp32 grads."""
    import jax
    import jax.numpy as jnp
    q, k, v, b = _qkvb(seed=4)
    scale = 1.0 / math.sqrt(D)

    def loss32(q_, k_, v_):
        return flash_attention(q_, k_, v_, jnp.asarray(b), scale).sum()

    def loss16(q_, k_, v_):
        return flash_attention(q_, k_, v_, jnp.asarray(b, jnp.bfloat16),
                               scale).astype(jnp.float32).sum()

    g32 = jax.grad(loss32, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g16 = jax.grad(loss16, argnums=(0, 1, 2))(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16))
    for a, bgrad in zip(g32, g16):
        an = np.asarray(a, np.float32).ravel()
        bn = np.asarray(bgrad, np.float32).ravel()
        cos = an @ bn / (np.linalg.norm(an) * np.linalg.norm(bn) + 1e-12)
        assert cos > 0.99, cos


def test_tiled_backward_matches_reference_grads():
    """The r3 tiled FlashAttention-2 backward (no [S,S] in HBM) against
    jax.vjp of the composed reference, S=256 so tiling engages."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    S2 = 256
    q = rng.randn(2, S2, 32).astype(np.float32)
    k = rng.randn(2, S2, 32).astype(np.float32)
    v = rng.randn(2, S2, 32).astype(np.float32)
    g = rng.randn(2, S2, 32).astype(np.float32)
    scale = 1.0 / math.sqrt(32)

    _, vjp = jax.vjp(lambda a, b_, c: _reference_attention(a, b_, c, None,
                                                           scale),
                     jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    ref_dq, ref_dk, ref_dv = vjp(jnp.asarray(g))

    _, fvjp = jax.vjp(lambda a, b_, c: flash_attention(a, b_, c, None,
                                                       scale),
                      jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    dq, dk, dv = fvjp(jnp.asarray(g))
    np.testing.assert_allclose(np.asarray(dq), np.asarray(ref_dq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(ref_dk),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(ref_dv),
                               rtol=2e-4, atol=2e-4)


def test_tiled_backward_with_bias_grads():
    """Bias participates in p recomputation; dq/dk/dv AND dbias (the
    separate tiled pass) stay exact vs the composition vjp — a trainable
    relative-position bias must keep training under the tiled path."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(8)
    S2 = 256
    q = rng.randn(2, S2, 16).astype(np.float32)
    k = rng.randn(2, S2, 16).astype(np.float32)
    v = rng.randn(2, S2, 16).astype(np.float32)
    bias = (rng.randn(2, S2, S2) * 0.3).astype(np.float32)
    g = rng.randn(2, S2, 16).astype(np.float32)
    scale = 0.25

    _, vjp = jax.vjp(lambda a, b_, c, bb: _reference_attention(
        a, b_, c, bb, scale),
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(bias))
    ref_dq, ref_dk, ref_dv, ref_db = vjp(jnp.asarray(g))

    _, fvjp = jax.vjp(lambda a, b_, c, bb: flash_attention(
        a, b_, c, bb, scale),
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(bias))
    dq, dk, dv, db = fvjp(jnp.asarray(g))
    np.testing.assert_allclose(np.asarray(dq), np.asarray(ref_dq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(ref_dk),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(ref_dv),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(ref_db),
                               rtol=2e-4, atol=2e-4)


def test_causal_flash_forward_and_grads():
    """Causal masking inside the kernels (static block indices): fwd and
    all grads match the masked composition at S=256 (tiled path)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    S2 = 256
    q = rng.randn(2, S2, 16).astype(np.float32) * 0.5
    k = rng.randn(2, S2, 16).astype(np.float32) * 0.5
    v = rng.randn(2, S2, 16).astype(np.float32) * 0.5
    g = rng.randn(2, S2, 16).astype(np.float32)
    scale = 0.25

    ref_out, vjp = jax.vjp(
        lambda a, b_, c: _reference_attention(a, b_, c, None, scale,
                                              causal=True),
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    ref_dq, ref_dk, ref_dv = vjp(jnp.asarray(g))

    out, fvjp = jax.vjp(
        lambda a, b_, c: flash_attention(a, b_, c, None, scale, True),
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    dq, dk, dv = fvjp(jnp.asarray(g))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(ref_dq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(ref_dk),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(ref_dv),
                               rtol=2e-4, atol=2e-4)


def test_causal_fused_attention_layer():
    """The op surface: layers.fused_attention(causal=True) equals the
    masked composition."""
    import jax.numpy as jnp

    rng = np.random.RandomState(12)
    Bq, Hh, S2, Dd = 2, 2, 128, 8
    q = rng.randn(Bq, Hh, S2, Dd).astype(np.float32)
    import paddle_tpu.fluid as fl
    main, startup = fl.Program(), fl.Program()
    with fl.program_guard(main, startup), fl.unique_name.guard():
        qv = fl.layers.data(name="q", shape=[Hh, S2, Dd], dtype="float32")
        out = layers.fused_attention(qv, qv, qv, scale=Dd ** -0.5,
                                     causal=True)
    with fl.scope_guard(fl.Scope()):
        exe = fl.Executor(fl.CPUPlace())
        exe.run(startup)
        got, = exe.run(main, feed={"q": q}, fetch_list=[out])
    ref = _reference_attention(
        jnp.asarray(q.reshape(Bq * Hh, S2, Dd)),
        jnp.asarray(q.reshape(Bq * Hh, S2, Dd)),
        jnp.asarray(q.reshape(Bq * Hh, S2, Dd)), None, Dd ** -0.5,
        causal=True)
    np.testing.assert_allclose(np.asarray(got).reshape(Bq * Hh, S2, Dd),
                               np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_causal_with_bias_all_grads():
    """causal=True combined with an additive bias: fwd, dq/dk/dv AND the
    tiled dbias pass all match the masked composition."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(13)
    S2 = 256
    q = rng.randn(2, S2, 16).astype(np.float32) * 0.5
    k = rng.randn(2, S2, 16).astype(np.float32) * 0.5
    v = rng.randn(2, S2, 16).astype(np.float32) * 0.5
    bias = (rng.randn(2, S2, S2) * 0.3).astype(np.float32)
    g = rng.randn(2, S2, 16).astype(np.float32)
    scale = 0.25

    ref_out, vjp = jax.vjp(
        lambda a, b_, c, bb: _reference_attention(a, b_, c, bb, scale,
                                                  causal=True),
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(bias))
    refs = vjp(jnp.asarray(g))

    out, fvjp = jax.vjp(
        lambda a, b_, c, bb: flash_attention(a, b_, c, bb, scale, True),
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(bias))
    got = fvjp(jnp.asarray(g))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-4)
    for name, a, b_ in zip(("dq", "dk", "dv", "dbias"), got, refs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_cross_attention_distinct_lengths():
    """Decoder cross-attention shape (S_q != S_kv) through the tiled
    kernels: fwd and all grads (incl. dbias) match the composition."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(14)
    S_q, S_kv = 128, 256
    q = rng.randn(2, S_q, 16).astype(np.float32) * 0.5
    k = rng.randn(2, S_kv, 16).astype(np.float32) * 0.5
    v = rng.randn(2, S_kv, 16).astype(np.float32) * 0.5
    bias = (rng.randn(2, S_q, S_kv) * 0.3).astype(np.float32)
    g = rng.randn(2, S_q, 16).astype(np.float32)
    scale = 0.25

    ref_out, vjp = jax.vjp(
        lambda a, b_, c, bb: _reference_attention(a, b_, c, bb, scale),
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(bias))
    refs = vjp(jnp.asarray(g))
    out, fvjp = jax.vjp(
        lambda a, b_, c, bb: flash_attention(a, b_, c, bb, scale, False),
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(bias))
    got = fvjp(jnp.asarray(g))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-4)
    for name, a, b_ in zip(("dq", "dk", "dv", "dbias"), got, refs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


# -- the one-tile kernels on [B, S, H * D], read and written in place --------

import pytest                                               # noqa: E402
import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from paddle_tpu.fluid.ops import pallas_ops                 # noqa: E402

# (D, H, S, causal, bias: None | "sequence" | "head", dtype)
IN_PLACE = {
    "pairs_mask_s128": (64, 4, 128, False, "sequence", "float32"),
    "pairs_plain_s128": (64, 2, 128, False, None, "float32"),
    "pairs_causal_s128": (64, 2, 128, True, None, "float32"),
    "pairs_causal_mask_s128": (64, 2, 128, True, "sequence", "float32"),
    "pairs_head_bias_s128": (64, 4, 128, False, "head", "float32"),
    "pairs_mask_s512": (64, 2, 512, False, "sequence", "float32"),
    "pairs_mask_s512_bf16": (64, 2, 512, False, "sequence", "bfloat16"),
    "pairs_causal_s128_bf16": (64, 2, 128, True, None, "bfloat16"),
    "one_head_mask_s128": (128, 2, 128, False, "sequence", "float32"),
    "one_head_causal_s512": (128, 1, 512, True, None, "float32"),
    "one_head_plain_s128_bf16": (128, 3, 128, False, None, "bfloat16"),
    "quads_mask_s128": (32, 4, 128, False, "sequence", "float32"),
}


def _in_place_case(name, seed=5):
    """Operands ``[B, S, H * D]`` and the same heads as ``[B * H, S, D]``."""
    D_, H_, S_, causal, bias, dtype = IN_PLACE[name]
    B_ = 2
    rng = np.random.RandomState(seed)

    def arr(*dims, scale=0.5):
        return jnp.asarray(rng.randn(*dims).astype(np.float32) * scale) \
            .astype(dtype)
    minor = [arr(B_, S_, H_ * D_) for _ in range(4)]            # q, k, v, g
    flat = [pallas_ops._flat(pallas_ops._heads_major(x, H_)) for x in minor]
    b = None if bias is None else \
        arr(B_ if bias == "sequence" else B_ * H_, S_, S_, scale=0.3)
    assert pallas_ops._in_place(H_, *pallas_ops._in_place_shape(
        minor[0], minor[1], b, causal, H_))
    return minor, flat, b, causal, H_


def _as_minor(x, heads):
    return pallas_ops._heads_minor(x.reshape(-1, heads, *x.shape[1:]))


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("name", sorted(IN_PLACE))
def test_in_place_kernels_are_the_flat_kernels_bit_for_bit(name):
    """``flash_fwd`` and ``flash_bwd`` over ``[B, S, H * D]`` (``128 // D``
    heads a grid cell, each on its lanes) against the same kernels over
    ``[B * H, S, D]``: the per-head body is the same, so the output, the
    logsumexp and the three gradients are equal to the bit, in float32
    and in bfloat16, with the mask of a sequence read once a cell, a bias
    a head, or none, causal or not."""
    (q, k, v, g), (qf, kf, vf, gf), bias, causal, heads = \
        _in_place_case(name)
    out, lse = pallas_ops._flash_fwd_in_place(q, k, v, bias, 0.125, heads,
                                              causal)
    want_out, want_lse = pallas_ops._flash_forward(
        qf, kf, vf, bias, 0.125, with_lse=True, causal=causal)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_array_equal(_f32(out), _f32(_as_minor(want_out, heads)))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(want_lse))
    # inference asks for no statistic, and gets the same output
    infer, none = pallas_ops._flash_fwd_in_place(q, k, v, bias, 0.125, heads,
                                                 causal, with_lse=False)
    assert none is None
    np.testing.assert_array_equal(_f32(infer), _f32(out))
    got = pallas_ops._backward_in_place(q, k, v, bias, 0.125, causal, heads,
                                        lse, g)
    want = pallas_ops._flash_backward(qf, kf, vf, bias, 0.125, want_lse, gf,
                                      causal=causal, bias_grad=False)[:3]
    for what, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == q.shape and a.dtype == q.dtype, what
        np.testing.assert_array_equal(_f32(a), _f32(_as_minor(b, heads)),
                                      err_msg=what)


@pytest.mark.parametrize("name", sorted(
    n for n in IN_PLACE if IN_PLACE[n][5] == "float32"))
def test_in_place_kernels_match_the_reference(name):
    (q, k, v, g), (qf, kf, vf, gf), bias, causal, heads = \
        _in_place_case(name)
    out, lse = pallas_ops._flash_fwd_in_place(q, k, v, bias, 0.125, heads,
                                              causal)
    want_out, vjp = jax.vjp(
        lambda *a: _reference_attention(*a, bias, 0.125, causal=causal),
        qf, kf, vf)
    np.testing.assert_allclose(_f32(out), _f32(_as_minor(want_out, heads)),
                               rtol=2e-4, atol=2e-5)
    got = pallas_ops._backward_in_place(q, k, v, bias, 0.125, causal, heads,
                                        lse, g)
    for what, a, b in zip(("dq", "dk", "dv"), got, vjp(gf)):
        np.testing.assert_allclose(_f32(a), _f32(_as_minor(b, heads)),
                                   rtol=2e-4, atol=2e-4, err_msg=what)


@pytest.mark.parametrize("name", ["pairs_mask_s128", "pairs_causal_s128",
                                  "one_head_mask_s128",
                                  "pairs_mask_s512_bf16"])
def test_in_place_backward_takes_a_passed_delta(name):
    """With delta passed in (rows ``[B * H, 1, S_q]``, as the logsumexp)
    the in-place ``flash_bwd`` is the flat one with the same delta, bit
    for bit, and agrees with the delta it forms itself; asked to, it
    writes the one it formed, a row a head."""
    (q, k, v, g), (qf, kf, vf, gf), bias, causal, heads = \
        _in_place_case(name)
    out, lse = pallas_ops._flash_forward(qf, kf, vf, bias, 0.125,
                                         with_lse=True, causal=causal)
    delta = pallas_ops._row_delta(gf, out)[:, None]
    rows = lse[:, None]
    got = pallas_ops._flash_bwd(q, k, v, bias, 0.125, rows, g, causal, delta,
                                heads=heads)
    want = pallas_ops._flash_bwd(qf, kf, vf, bias, 0.125, rows, gf, causal,
                                 delta)
    assert got[3] is delta
    formed = pallas_ops._flash_bwd(q, k, v, bias, 0.125, rows, g, causal,
                                   None, delta_out=True, heads=heads)
    tol = dict(rtol=2e-2, atol=2e-2) if q.dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(formed[3]), np.asarray(delta),
                               **tol)
    for what, a, b, c in zip(("dq", "dk", "dv"), got, want, formed):
        np.testing.assert_array_equal(_f32(a), _f32(_as_minor(b, heads)),
                                      err_msg=what)
        np.testing.assert_allclose(_f32(a), _f32(c), err_msg=what, **tol)


@pytest.mark.parametrize("bias", [None, "sequence", "head"])
def test_in_place_attention_is_differentiated_by_jax(bias):
    """``flash_attention_in_place`` under ``jax.grad`` (an op inside a
    recompute span, a replayed forward): the gradients of Q, K, V and,
    where there is a bias, of the bias are the reference's."""
    B_, H_, S_, D_ = 2, 2, 128, 64
    rng = np.random.RandomState(9)

    def arr(*dims, scale=0.5):
        return jnp.asarray(rng.randn(*dims).astype(np.float32) * scale)
    q, k, v, w = (arr(B_, S_, H_ * D_) for _ in range(4))
    b = None if bias is None else \
        arr(B_ if bias == "sequence" else B_ * H_, S_, S_, scale=0.3)

    def loss(q, k, v, b):
        return (pallas_ops.flash_attention_in_place(
            q, k, v, b, 0.125, False, H_)[0] * w).sum()

    def want_loss(q, k, v, b):
        flat = [pallas_ops._flat(pallas_ops._heads_major(x, H_))
                for x in (q, k, v)]
        return (_as_minor(_reference_attention(*flat, b, 0.125), H_)
                * w).sum()
    wrt = (0, 1, 2) + ((3,) if bias else ())
    for what, a, c in zip(("dq", "dk", "dv", "dbias"),
                          jax.grad(loss, argnums=wrt)(q, k, v, b),
                          jax.grad(want_loss, argnums=wrt)(q, k, v, b)):
        assert a.shape == c.shape, what
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-4,
                                   atol=2e-4, err_msg=what)
