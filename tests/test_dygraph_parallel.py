"""Eager DataParallel across processes (reference dygraph/parallel.py:84).

2 procs x 1 CPU device each: scale_loss + apply_collective_grads over a
process mesh must reproduce single-process big-batch training exactly.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import dygraph

_WORKER = os.path.join(os.path.dirname(__file__), "dist_dygraph_worker.py")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _single_reference():
    rng = np.random.RandomState(21)
    xs = rng.normal(size=(16, 6)).astype(np.float32)
    ws = rng.normal(size=(6, 1)).astype(np.float32)
    ys = (xs @ ws).astype(np.float32)
    losses = []
    with dygraph.guard():
        fc = dygraph.nn.FC(
            size=1, input_dim=6,
            param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.ConstantInitializer(0.2)),
            bias_attr=fluid.ParamAttr(
                initializer=fluid.initializer.ConstantInitializer(0.0)))
        opt = fluid.optimizer.SGD(learning_rate=0.1)
        for _ in range(4):
            x = dygraph.to_variable(xs)
            y = dygraph.to_variable(ys)
            pred = fc(x)
            diff = pred - y
            loss_vec = diff * diff
            loss, = dygraph.trace_op(
                "reduce_mean", {"X": [loss_vec]}, {"Out": 1},
                {"dim": None, "keep_dim": False, "reduce_all": True})["Out"]
            losses.append(float(np.asarray(loss.numpy()).reshape(-1)[0]))
            loss.backward()
            opt.minimize(loss, parameter_list=fc.parameters())
            for p in fc.parameters():
                p.clear_gradient()
    return losses


def test_dygraph_data_parallel_two_procs():
    port = 22000 + (os.getpid() % 2000)
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ)
        env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "JAX_PLATFORMS": "cpu",
            "MESH_TEST_OUT": td,
            "PYTHONPATH": os.pathsep.join(
                [_REPO] + env.get("PYTHONPATH", "").split(os.pathsep)),
        })
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--started_port", str(port),
             "--log_dir", td, _WORKER],
            env=env, timeout=240, capture_output=True, text=True)
        logs = ""
        for r in (0, 1):
            lp = os.path.join(td, "workerlog.%d" % r)
            if os.path.exists(lp):
                logs += open(lp).read()
        assert proc.returncode == 0, (proc.stdout, proc.stderr, logs)
        ranks = []
        for r in (0, 1):
            with open(os.path.join(td, "rank%d.json" % r)) as f:
                ranks.append(json.load(f)["losses"])
    multi = np.mean(ranks, axis=0)          # mean of local means
    single = _single_reference()
    np.testing.assert_allclose(multi, single, rtol=1e-5, atol=1e-6)
