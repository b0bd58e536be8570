"""DLPack interop through the fluid.core shim (reference
framework/dlpack_tensor.cc + pybind dlpack support).

Runs under the CPU-pinned conftest: all arrays here are CPU-resident.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")

import paddle_tpu.fluid as fluid  # noqa: E402


def test_to_dlpack_feeds_torch():
    import jax.numpy as jnp
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    t = torch.from_dlpack(fluid.core.to_dlpack(x))
    assert t.shape == (3, 4)
    np.testing.assert_allclose(t.numpy(), np.asarray(x))


def test_from_dlpack_protocol_object():
    back = fluid.core.from_dlpack(torch.arange(6, dtype=torch.float32))
    np.testing.assert_allclose(np.asarray(back), np.arange(6))


def test_from_dlpack_raw_capsule_roundtrip():
    import jax.numpy as jnp
    x = jnp.linspace(0, 1, 5)
    cap = fluid.core.to_dlpack(x)
    back = fluid.core.from_dlpack(cap)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x))


def test_from_dlpack_torch_capsule():
    t = torch.arange(8, dtype=torch.float32) * 0.5
    cap = torch.utils.dlpack.to_dlpack(t)
    back = fluid.core.from_dlpack(cap)
    np.testing.assert_allclose(np.asarray(back), t.numpy())
