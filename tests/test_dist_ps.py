"""PS training without a cluster: subprocess-on-localhost with loss-parity
assertions — the reference's test_dist_base.py:362 TestDistBase pattern
(_run_local vs _run_cluster over 127.0.0.1 with PADDLE_* wiring).
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RUNNER = os.path.join(HERE, "dist_ps_runner.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn(args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(HERE) + os.pathsep + \
        env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen([sys.executable, "-u", RUNNER] + args,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, env=env, text=True)


def _losses(out):
    for line in out.splitlines():
        if line.startswith("LOSSES:"):
            return [float(v) for v in line[len("LOSSES:"):].split(",")]
    raise AssertionError("no LOSSES line in output:\n" + out)


def test_ps_cluster_matches_local(tmp_path):
    # shared initial weights so the parity oracle is exact
    rng = np.random.RandomState(0)
    init = {"w0": rng.randn(8, 16).astype(np.float32) * 0.2,
            "b0": np.zeros(16, np.float32),
            "w1": rng.randn(16, 1).astype(np.float32) * 0.2,
            "b1": np.zeros(1, np.float32)}
    init_npz = str(tmp_path / "init.npz")
    np.savez(init_npz, **init)

    endpoint = "127.0.0.1:%d" % _free_port()

    local = _spawn(["local", endpoint, init_npz])
    local_out, _ = local.communicate(timeout=240)
    assert local.returncode == 0, local_out
    local_losses = _losses(local_out)

    ps = _spawn(["pserver", endpoint, init_npz])
    # wait for readiness
    line = ps.stdout.readline()
    assert "PSERVER-READY" in line, line
    t0 = _spawn(["trainer", endpoint, init_npz, "0"])
    t1 = _spawn(["trainer", endpoint, init_npz, "1"])
    out0, _ = t0.communicate(timeout=240)
    out1, _ = t1.communicate(timeout=240)
    ps.terminate()
    ps.wait(timeout=30)
    assert t0.returncode == 0, out0
    assert t1.returncode == 0, out1
    l0, l1 = _losses(out0), _losses(out1)

    # both trainers feed the same fixed batch, so sync-PS training must
    # track the local run step for step (the reference's loss-delta
    # assertion, test_dist_base.py)
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    np.testing.assert_allclose(l0, local_losses, rtol=1e-4, atol=1e-6)
    assert l0[-1] < l0[0]  # it actually learned


def test_async_communicator_converges():
    """Async (Hogwild-style) PS: background send/recv threads, no barrier
    (reference AsyncCommunicator, communicator.h:160)."""
    import time
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.distributed.ps import ParameterServer, stop_servers
    from paddle_tpu.distributed.communicator import AsyncCommunicator

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="w_in", shape=[4], dtype="float32")
            y = layers.data(name="w_y", shape=[1], dtype="float32")
            pred = layers.fc(input=x, size=1, bias_attr=False,
                             param_attr=fluid.ParamAttr(name="pw"))
            loss = layers.reduce_mean(layers.square_error_cost(pred, y))
            fluid.optimizer.SGDOptimizer(0.05).minimize(loss)

    endpoint = "127.0.0.1:%d" % _free_port()
    t = fluid.transpiler.DistributeTranspiler(
        config=fluid.transpiler.DistributeTranspilerConfig())
    t.transpile(0, program=main, pservers=endpoint, trainers=1,
                sync_mode=False, startup_program=startup)
    ps_prog = t.get_pserver_program(endpoint)
    ps_start = t.get_startup_program(endpoint, ps_prog)
    w0 = np.ones((4, 1), np.float32) * 0.1
    server = ParameterServer(endpoint, ps_prog, ps_start, trainers=1,
                             sync_mode=False, init_weights={"pw": w0})
    try:
        comm = AsyncCommunicator({"pw": endpoint}, {"pw@GRAD": "pw"},
                                 recv_interval_s=0.01)
        comm.start()
        rng = np.random.RandomState(0)
        x_np = rng.randn(64, 4).astype(np.float32)
        target = np.array([[0.5], [-1.0], [2.0], [0.25]], np.float32)
        y_np = x_np @ target
        w = w0.copy()
        for _ in range(150):
            g = 2 * x_np.T @ (x_np @ w - y_np) / len(x_np)
            comm.push({"pw@GRAD": g})
            time.sleep(0.02)
            latest = comm.pull(["pw"])["pw"]
            if latest is not None:
                w = latest
        comm.stop()
        final = np.asarray(server._scope.find_var_numpy("pw"))
        np.testing.assert_allclose(final, target, atol=0.1)
    finally:
        stop_servers([endpoint])


def test_multi_pserver_with_regularization(tmp_path):
    """Each pserver gets only ITS params' clip/reg chain — an L2Decay op
    for 'w' must not land on the server that owns only 'b'."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.distributed.ps import ParameterServer, stop_servers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="rx", shape=[4], dtype="float32")
            y = layers.data(name="ry", shape=[1], dtype="float32")
            pred = layers.fc(input=x, size=1,
                             param_attr=fluid.ParamAttr(name="rw"),
                             bias_attr=fluid.ParamAttr(name="rb"))
            loss = layers.reduce_mean(layers.square_error_cost(pred, y))
            fluid.optimizer.SGDOptimizer(
                0.1, regularization=fluid.regularizer.L2Decay(0.01)
            ).minimize(loss)

    eps = ["127.0.0.1:%d" % _free_port(), "127.0.0.1:%d" % _free_port()]
    t = fluid.transpiler.DistributeTranspiler()
    t.transpile(0, program=main, pservers=",".join(eps), trainers=1,
                startup_program=startup)
    servers = []
    try:
        for ep in eps:
            prog = t.get_pserver_program(ep)
            # no op on this server may read a grad of a foreign param
            own_grads = set(prog._ps_grad_to_param)
            for op in prog.global_block().ops:
                for n in op.input_arg_names():
                    if n.endswith("@GRAD"):
                        assert n in own_grads, (ep, op.type, n)
            servers.append(ParameterServer(
                ep, prog, t.get_startup_program(ep, prog), trainers=1))
        # one full round end-to-end
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
            lv, = exe.run(t.get_trainer_program(),
                          feed={"rx": np.ones((8, 4), np.float32),
                                "ry": np.ones((8, 1), np.float32)},
                          fetch_list=[loss])
        assert np.isfinite(np.asarray(lv)).all()
    finally:
        stop_servers(eps)


def test_transpiler_rejects_double_transpile():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[4], dtype="float32")
            y = layers.data(name="y", shape=[1], dtype="float32")
            loss = layers.reduce_mean(layers.square_error_cost(
                layers.fc(input=x, size=1), y))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    t = fluid.transpiler.DistributeTranspiler()
    t.transpile(0, program=main, pservers="127.0.0.1:7199", trainers=1,
                startup_program=startup)
    t2 = fluid.transpiler.DistributeTranspiler()
    with pytest.raises(ValueError, match="already transpiled"):
        t2.transpile(0, program=main, pservers="127.0.0.1:7199",
                     trainers=1, startup_program=startup)


def test_transpiler_program_structure():
    """Transpile-and-inspect (reference test_dist_transpiler.py): trainer
    program ends with send+recv, pserver program holds the sgd ops."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[4], dtype="float32")
            y = layers.data(name="y", shape=[1], dtype="float32")
            pred = layers.fc(input=x, size=1,
                             param_attr=fluid.ParamAttr(name="w"),
                             bias_attr=fluid.ParamAttr(name="b"))
            loss = layers.reduce_mean(layers.square_error_cost(pred, y))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)

    eps = "127.0.0.1:7164,127.0.0.1:7165"
    t = fluid.transpiler.DistributeTranspiler()
    t.transpile(0, program=main, pservers=eps, trainers=2,
                startup_program=startup)

    types = [op.type for op in main.global_block().ops]
    assert "sgd" not in types
    assert types[-2:] == ["send", "recv"]
    # startup gained the initial param fetch
    assert startup.global_block().ops[-1].type == "recv"

    # params round-robin across both endpoints; each pserver program has
    # exactly its own params' sgd ops
    progs = [t.get_pserver_program(e) for e in eps.split(",")]
    sgd_counts = [sum(1 for op in p.global_block().ops
                      if op.type == "sgd") for p in progs]
    assert sorted(sgd_counts) == [1, 1]
    all_params = set()
    for p in progs:
        all_params |= set(p._ps_grad_to_param.values())
    assert all_params == {"w", "b"}
    # pserver startup initializes its params
    st = t.get_startup_program(eps.split(",")[0], progs[0])
    assert len(st.global_block().ops) >= 1
