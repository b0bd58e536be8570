"""Python-free AOT deployment (VERDICT r2 item 5).

export_aot_model writes an HLO module + manifest; pjrt_demo.cc compiles
and runs it through the XLA native runtime in libtensorflow_cc with NO
libpython linked — the reference's pure-C++ deployment contract
(train/demo/demo_trainer.cc, inference/api/demo_ci)."""

import os
import subprocess
import tempfile

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import aot

_DEPLOY = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "paddle_tpu", "native", "deploy")
_TF = "/opt/venv/lib/python3.12/site-packages/tensorflow"


def _build_demo(exe_path):
    cmd = [
        "g++", "-std=c++17", "-O1",
        os.path.join(_DEPLOY, "pjrt_demo.cc"),
        "-I" + _TF + "/include",
        "-I" + _TF + "/include/tensorflow/compiler",
        "-I" + _TF + "/include/external/highwayhash",
        "-I" + _TF + "/include/external/farmhash_archive/src",
        _TF + "/libtensorflow_cc.so.2",
        _TF + "/libtensorflow_framework.so.2",
        "-Wl,-rpath," + _TF,
        "-o", exe_path,
    ]
    cp = subprocess.run(cmd, capture_output=True, text=True, timeout=560)
    assert cp.returncode == 0, cp.stderr[-3000:]


@pytest.mark.skipif(not os.path.isdir(_TF), reason="no tensorflow libs")
def test_aot_export_and_cpp_run():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[6], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        y = fluid.layers.fc(h, size=3)

    rng = np.random.RandomState(0)
    feed = rng.normal(0, 1, (4, 6)).astype(np.float32)
    scope = fluid.Scope()
    with tempfile.TemporaryDirectory() as td:
        model_dir = os.path.join(td, "model")
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            ref, = exe.run(main, feed={"x": feed}, fetch_list=[y])
            aot.export_aot_model(model_dir, {"x": feed}, [y], exe,
                                 main_program=main, scope=scope)
        assert os.path.exists(os.path.join(model_dir, "__model__.hlo.pb"))
        manifest = open(os.path.join(model_dir, "__manifest__")).read()
        assert "input x f32 2 4 6" in manifest
        feed.tofile(os.path.join(model_dir, "x.bin"))

        demo = os.path.join(td, "pjrt_demo")
        _build_demo(demo)

        # the binary must not link libpython — that is the whole point
        ldd = subprocess.run(["ldd", demo], capture_output=True, text=True)
        assert "libpython" not in ldd.stdout, ldd.stdout

        rp = subprocess.run([demo, model_dir], capture_output=True,
                            text=True, timeout=300)
        assert rp.returncode == 0, rp.stderr[-2000:]
        assert "pjrt_demo ok" in rp.stdout
        out_line = [l for l in rp.stdout.splitlines()
                    if l.startswith("output ")][0]
        vals = [float(v) for v in out_line.split()[3:]]
        np.testing.assert_allclose(
            vals, np.asarray(ref).ravel()[:len(vals)], rtol=1e-5,
            atol=1e-6)


def test_export_requires_initialized_scope():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, size=2)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        with tempfile.TemporaryDirectory() as td:
            with pytest.raises(RuntimeError, match="startup"):
                aot.export_aot_model(td, {"x": ((1, 4), "float32")}, [y],
                                     exe, main_program=main)


@pytest.mark.skipif(not os.path.isdir(_TF), reason="no tensorflow libs")
def test_aot_train_cpp_loop():
    """The exported TRAIN step iterated from C++ (demo_trainer.cc
    contract): loss falls, no libpython linked."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
    rng = np.random.RandomState(0)
    xs = rng.normal(0, 1, (32, 8)).astype(np.float32)
    ys = (xs @ rng.normal(0, 1, (8, 1))).astype(np.float32)
    scope = fluid.Scope()
    with tempfile.TemporaryDirectory() as td:
        model_dir = os.path.join(td, "train_model")
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            state = aot.export_aot_train(model_dir, {"x": xs, "y": ys},
                                         loss, exe, main_program=main,
                                         scope=scope)
        assert state, "no state tensors exported"
        xs.tofile(os.path.join(model_dir, "x.bin"))
        ys.tofile(os.path.join(model_dir, "y.bin"))

        demo = os.path.join(td, "pjrt_train_demo")
        cmd = [
            "g++", "-std=c++17", "-O1",
            os.path.join(_DEPLOY, "pjrt_train_demo.cc"),
            "-I" + _TF + "/include",
            "-I" + _TF + "/include/tensorflow/compiler",
            "-I" + _TF + "/include/external/highwayhash",
            "-I" + _TF + "/include/external/farmhash_archive/src",
            _TF + "/libtensorflow_cc.so.2",
            _TF + "/libtensorflow_framework.so.2",
            "-Wl,-rpath," + _TF, "-o", demo]
        cp = subprocess.run(cmd, capture_output=True, text=True,
                            timeout=560)
        assert cp.returncode == 0, cp.stderr[-3000:]
        ldd = subprocess.run(["ldd", demo], capture_output=True, text=True)
        assert "libpython" not in ldd.stdout

        rp = subprocess.run([demo, model_dir, "12"], capture_output=True,
                            text=True, timeout=300)
        assert rp.returncode == 0, (rp.stdout, rp.stderr[-1500:])
        assert "pjrt_train_demo ok" in rp.stdout


def test_aot_name_whitelist_and_collision():
    # names outside [A-Za-z0-9_.@/-] break the whitespace-tokenized
    # manifest; '/'-mangling collisions would silently overwrite .bin
    # files — both must be rejected up front
    aot._check_names(["w", "scope/w", "a.b@c-d"], "state")
    with pytest.raises(ValueError, match="whitespace-tokenized"):
        aot._check_names(["bad name"], "input")
    with pytest.raises(ValueError, match="collision"):
        aot._check_names(["a/b", "a__b"], "state")
