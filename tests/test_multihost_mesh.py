"""Multi-host mesh: 2 processes × 4 CPU devices = one 8-device dp mesh.

Reference analogue: ``python/paddle/distributed/launch.py`` spawning
NCCL-connected trainers across nodes (test_dist_base.py:362 pattern).
Here launch.py exports the PADDLE_* identity env plus the rendezvous
coordinator; init_parallel_env → jax.distributed.initialize; the same
GradAllReduce program then runs across processes with Gloo/ICI
collectives.  Oracle: per-step losses must match a single-process 8-device
run on the identical global batch to float tolerance.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.transpiler import GradAllReduce

_WORKER = os.path.join(os.path.dirname(__file__), "dist_mesh_worker.py")


def _single_process_reference():
    rng = np.random.RandomState(11)
    xs = rng.normal(size=(16, 6)).astype(np.float32)
    ws = rng.normal(size=(6, 1)).astype(np.float32)
    ys = (xs @ ws).astype(np.float32)
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[6], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(
                x, size=1,
                param_attr=fluid.ParamAttr(
                    initializer=fluid.initializer.ConstantInitializer(0.5)),
                bias_attr=fluid.ParamAttr(
                    initializer=fluid.initializer.ConstantInitializer(0.0)))
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
    GradAllReduce().transpile(startup_program=startup_p,
                              main_program=main_p, rank=0,
                              endpoints=[], nranks=0)
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup_p)
        for _ in range(4):
            lv = exe.run(main_p, feed={"x": xs, "y": ys},
                         fetch_list=[loss])[0]
            losses.append(float(np.mean(np.asarray(lv))))
    return losses


def _run_two_process(worker_path, json_pattern, port_base, timeout=300):
    """Launch ``worker_path`` as a 2-process x 4-device pack via
    paddle_tpu.distributed.launch and return the per-rank result JSONs
    (shared harness for the dp / mp / sp multihost tests)."""
    port = port_base + (os.getpid() % 2000)
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ)
        env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "JAX_PLATFORMS": "cpu",
            "MESH_TEST_OUT": td,
            "PYTHONPATH": os.pathsep.join(
                [os.path.dirname(os.path.dirname(__file__)),
                 os.path.dirname(__file__)] +
                env.get("PYTHONPATH", "").split(os.pathsep)),
        })
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--started_port", str(port),
             "--log_dir", td, worker_path],
            env=env, timeout=timeout, capture_output=True, text=True)
        logs = ""
        for r in (0, 1):
            lp = os.path.join(td, "workerlog.%d" % r)
            if os.path.exists(lp):
                logs += open(lp).read()
        assert proc.returncode == 0, (proc.stdout, proc.stderr, logs)
        ranks = []
        for r in (0, 1):
            with open(os.path.join(td, json_pattern % r)) as f:
                ranks.append(json.load(f))
    return ranks


def test_two_process_mesh_matches_single_process():
    ranks = _run_two_process(_WORKER, "rank%d.json", 20000, timeout=240)
    # global loss per step = mean of the two hosts' local means
    multi = np.mean([r["losses"] for r in ranks], axis=0)
    single = _single_process_reference()
    np.testing.assert_allclose(multi, single, rtol=1e-5, atol=1e-6)


def test_two_process_tensor_parallel_matches_single_process():
    """mp=8 Megatron sharding ACROSS 2 processes (GSPMD collectives over
    the process boundary) == the untranspiled single-process program,
    step for step (r4: multi-host coverage for the model-parallel tier)."""
    import dist_mp_worker

    single = dist_mp_worker.run_steps(
        *dist_mp_worker.build(mp=1), dist_mp_worker.make_feeds())
    worker = os.path.join(os.path.dirname(__file__), "dist_mp_worker.py")
    ranks = _run_two_process(worker, "mp_rank%d.json", 22000)

    # the loss is replicated: both processes must report the same curve,
    # and it must equal the single-process untranspiled run
    np.testing.assert_allclose(ranks[0]["losses"], ranks[1]["losses"],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(ranks[0]["losses"], single,
                               rtol=2e-5, atol=2e-6)


def test_two_process_sequence_parallel_matches_single_process():
    """sp=8 ring attention ACROSS 2 processes: the ring's
    collective-permutes cross the process boundary every step (the
    multi-host form of context parallelism) == the untranspiled
    single-process program, step for step (r5)."""
    import dist_sp_worker

    single = dist_sp_worker.run_steps(
        *dist_sp_worker.build(sp=1), dist_sp_worker.make_feeds())
    worker = os.path.join(os.path.dirname(__file__), "dist_sp_worker.py")
    ranks = _run_two_process(worker, "sp_rank%d.json", 24000)

    np.testing.assert_allclose(ranks[0]["losses"], ranks[1]["losses"],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(ranks[0]["losses"], single,
                               rtol=2e-5, atol=2e-6)


def test_two_process_gspmd_dp_matches_single_process():
    """CompiledProgram.with_data_parallel ACROSS 2 processes: the GSPMD
    dp feed carries a non-trivial P('dp') sharding, exercising the
    executor's numpy-feed globalization on the compiler path (r5)."""
    import dist_dp_gspmd_worker

    single = dist_dp_gspmd_worker.run_steps(
        *dist_dp_gspmd_worker.build(), dist_dp_gspmd_worker.make_feeds(),
        data_parallel=False)
    worker = os.path.join(os.path.dirname(__file__),
                          "dist_dp_gspmd_worker.py")
    ranks = _run_two_process(worker, "dp_rank%d.json", 26000)

    np.testing.assert_allclose(ranks[0]["losses"], ranks[1]["losses"],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(ranks[0]["losses"], single,
                               rtol=2e-5, atol=2e-6)
