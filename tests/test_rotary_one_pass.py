"""The rotary embedding in one pass (``ops/decoder_ops.rotary``): the
rotate-half and the de-interleave as products by constant signed
permutations, forward and backward, against the slice-and-concatenate
formula it replaced; compiled for a v5e, no float32 half-head array."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, telemetry
from paddle_tpu.fluid.ops import decoder_ops


def sliced(x, theta, interleaved=True):
    """The formula the lowering had before: the rotate-half as slices of a
    float32 copy, concatenated."""
    B, S, N, D = x.shape
    xf = x.astype(jnp.float32)
    if interleaved:
        xf = xf.reshape(B, S, N, D // 2, 2).swapaxes(-1, -2) \
            .reshape(B, S, N, D)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    half = jnp.concatenate([-xf[..., D // 2:], xf[..., :D // 2]], axis=-1)
    return (xf * jnp.cos(angles) + half * jnp.sin(angles)).astype(x.dtype)


def grad_of_weighted_sum(f, x, w):
    return jax.jit(jax.grad(
        lambda a, w: (f(a).astype(jnp.float32) * w).sum()))(x, w)


def pair_bound(w, interleaved):
    """``|g_j| + |g_partner(j)|`` on the INPUT's lanes: the size of the two
    products a gradient lane sums (|cos|, |sin| <= 1)."""
    g = np.abs(np.asarray(w, np.float64))
    h = g.shape[-1] // 2
    both = g + np.roll(g, h, axis=-1)
    return np.repeat(both[..., :h], 2, axis=-1) if interleaved else both


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("interleaved", [False, True])
def test_one_pass_against_the_sliced_formula(interleaved, head_dim, dtype):
    """The forward is the sliced formula's to the bit, in both layouts and
    both dtypes.  The gradient of a weighted sum: bfloat16 within one
    bfloat16 ulp (the backward's sum is float32, rounded once); float32 to
    one rounding of the two products it sums (XLA:CPU contracts the
    sliced formula's multiply-add into a fused one on half the lanes and
    not on the other, so no order of operations equals it to the bit)."""
    rng = np.random.default_rng(head_dim + interleaved)
    shape = (2, 37, 3, head_dim)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    w = jnp.asarray(rng.normal(size=shape), dtype)
    theta = 1e4 if interleaved else 1e6

    got = jax.jit(lambda a: decoder_ops.rotary(a, theta, interleaved))(x)
    want = jax.jit(lambda a: sliced(a, theta, interleaved))(x)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))

    dx = grad_of_weighted_sum(
        lambda a: decoder_ops.rotary(a, theta, interleaved), x, w)
    dref = grad_of_weighted_sum(lambda a: sliced(a, theta, interleaved), x, w)
    assert dx.dtype == x.dtype
    if dtype == "bfloat16":
        np.testing.assert_array_max_ulp(np.asarray(dx, np.float32),
                                        np.asarray(dref, np.float32),
                                        maxulp=1 << 16)   # 1 bf16 ulp
    else:
        err = np.abs(np.asarray(dx, np.float64) - np.asarray(dref))
        assert (err <= 2.0 ** -22 * pair_bound(w, interleaved)).all(), \
            err.max()
    # the planted fault: the other pairing is another result
    other = jax.jit(lambda a: decoder_ops.rotary(a, theta, not interleaved))
    assert np.abs(np.asarray(other(x), np.float32)
                  - np.asarray(want, np.float32)).max() > 0.1


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


@pytest.mark.parametrize("head_dim,interleaved", [(128, False), (64, True)])
def test_compiled_step_holds_no_float32_half_head(one_chip, head_dim,
                                                  interleaved):
    """``rotary`` and its vjp at ``[1, 2048, 4, D]`` bf16, compiled for a
    v5e (no chip needed): the optimized module names no
    ``f32[1,2048,4,D/2]`` array (the sliced formula names one 24 times at
    D=128 and 30 times at D=64, interleaved) and holds no slice or
    concatenate of the operand."""
    arg = jax.ShapeDtypeStruct((1, 2048, 4, head_dim), jnp.bfloat16,
                               sharding=one_chip)

    def forward_and_back(x, g):
        y, vjp = jax.vjp(lambda a: decoder_ops.rotary(a, 1e4, interleaved),
                         x)
        return y, vjp(g)[0]

    text = jax.jit(forward_and_back).lower(arg, arg).compile().as_text()
    assert "f32[1,2048,4,%d]" % (head_dim // 2) not in text
    assert not re.search(r"= \S*\[1,2048,4,\d+\]\S* (slice|concatenate)\(",
                         text)


def test_the_counter_names_pairing_and_head_size():
    """``rotary_lowered_total{interleaved, head_dim}``: one count a
    lowering traced, the forward op's and the grad op's replay."""
    counter = telemetry.registry().get("rotary_lowered_total")
    before = {(il, d): counter.value(interleaved=il, head_dim=d)
              for il in (False, True) for d in (8, 16)}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xs = []
        for d in (8, 16):
            xv = layers.data(name="x%d" % d, shape=[2, 4, 2, d],
                             dtype="float32", append_batch_size=False)
            xv.stop_gradient = False
            xs.append(xv)
        ys = [layers.rotary_embedding(xs[0], theta=1e6, interleaved=False),
              layers.rotary_embedding(xs[1], theta=1e4)]
        loss = layers.reduce_sum(ys[0]) + layers.reduce_sum(ys[1])
        fluid.backward.append_backward(loss)
    feed = {"x8": np.ones((2, 4, 2, 8), np.float32),
            "x16": np.ones((2, 4, 2, 16), np.float32)}
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(
            main, feed=feed, fetch_list=[loss, "x8@GRAD", "x16@GRAD"])
    delta = {k: counter.value(interleaved=k[0], head_dim=k[1]) - v
             for k, v in before.items()}
    assert delta == {(False, 8): 2, (True, 16): 2, (False, 16): 0,
                     (True, 8): 0}, delta
