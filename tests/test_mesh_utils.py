"""Topology-aware mesh construction (fluid/mesh_utils.py) — VERDICT r2
item 7: one shared helper, deterministic device order, correct axis
assignment on the virtual 8-device mesh."""

import numpy as np
import pytest
import jax

from paddle_tpu.fluid.mesh_utils import build_mesh, ordered_devices


def test_single_axis_defaults_to_all_devices():
    m = build_mesh(("dp",), platform="cpu")
    assert m.axis_names == ("dp",)
    assert m.devices.shape == (len(jax.devices("cpu")),)


def test_two_axis_shape_and_inference():
    m = build_mesh(("dp", "mp"), (-1, 2), platform="cpu")
    assert m.axis_names == ("dp", "mp")
    assert m.devices.shape == (len(jax.devices("cpu")) // 2, 2)
    m2 = build_mesh(("dcn", "ici"), (2, -1), platform="cpu")
    assert m2.devices.shape == (2, len(jax.devices("cpu")) // 2)


def test_deterministic_order():
    devs = ordered_devices("cpu")
    assert devs == sorted(devs, key=lambda d: (d.process_index, d.id))
    # order is stable across calls and covers every device exactly once
    m = build_mesh(("dp", "mp"), (-1, 4), platform="cpu")
    ids = sorted(d.id for d in m.devices.flat)
    assert ids == sorted(d.id for d in jax.devices("cpu"))
    m2 = build_mesh(("dp", "mp"), (-1, 4), platform="cpu")
    assert [d.id for d in m.devices.flat] == [d.id for d in m2.devices.flat]


def test_size_validation():
    n = len(jax.devices("cpu"))
    with pytest.raises(ValueError):
        build_mesh(("dp", "mp"), (n, 2), platform="cpu")
    with pytest.raises(ValueError):
        build_mesh(("dp", "mp"), (-1, -1), platform="cpu")
    with pytest.raises(ValueError):
        build_mesh(("dp", "mp"), None, platform="cpu")


def test_explicit_device_subset():
    devs = jax.devices("cpu")[:4]
    m = build_mesh(("mp",), devices=devs)
    assert m.devices.shape == (4,)
    assert {d.id for d in m.devices.flat} == {d.id for d in devs}


def test_framework_paths_use_helper():
    """The executor (TP path), compiler, and pipeline all construct their
    meshes through build_mesh — the single-helper requirement."""
    import inspect
    from paddle_tpu.fluid import executor, compiler, pipeline
    for mod in (executor, compiler, pipeline):
        src = inspect.getsource(mod)
        assert "build_mesh" in src, mod.__name__
    # compiler produces the (dp, mp) mesh for a TP-annotated program
    import paddle_tpu.fluid as fluid
    prog = fluid.Program()
    prog._mp_degree = 2
    cp = fluid.CompiledProgram(prog).with_data_parallel(loss_name=None)

    import jax
    m = cp._mesh(jax.devices("cpu")[0])
    assert m.axis_names == ("dp", "mp")
    assert m.devices.shape[1] == 2
