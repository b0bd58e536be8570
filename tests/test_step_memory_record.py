"""The step's memory, accounted where it is compiled and held (PR 37):
``step_memory_bytes`` / ``step_resident_bytes`` stamped by the introspection
calls and by nothing else, ``feed_staged_bytes`` of a program-bound loader,
``device_memory_bytes`` sampled on a pull (docs/observability.md "Does it
fit")."""

import itertools
import time
import types

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import costmodel, telemetry

BATCH = 16
MEMORY_KINDS = {"argument", "output", "alias", "temp", "code"}
RESIDENT_KINDS = {"parameter", "optimizer_state", "other_state", "feed"}
OPTIMIZERS = {
    "momentum": lambda: fluid.optimizer.Momentum(0.01, momentum=0.9),
    "adam": lambda: fluid.optimizer.Adam(1e-3),
}


def _build(optimizer, loader_capacity=None):
    """A step with every kind of state: two fc layers (parameters), their
    accumulators, batch-norm statistics and the learning rate."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    loader = None
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[32], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        if loader_capacity:
            loader = fluid.DataLoader.from_generator(
                feed_list=[x, y], capacity=loader_capacity, iterable=False)
        h = fluid.layers.batch_norm(fluid.layers.fc(x, size=48, act="relu"))
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(h, size=1), y))
        OPTIMIZERS[optimizer]().minimize(loss)
    return main, startup, loss, loader


def _feed(rng=None):
    rng = rng or np.random.default_rng(0)
    return {"x": rng.standard_normal((BATCH, 32)).astype("float32"),
            "y": rng.standard_normal((BATCH, 1)).astype("float32")}


def _nbytes(program, names):
    block = program.global_block()
    return sum(int(np.prod(block.var(n).shape)) * 4 for n in names)


def _gauge(name, sig):
    gauge = telemetry.registry().get(name)
    return {ls["kind"]: gauge.value(**ls) for ls in gauge.labelsets()
            if ls["sig"] == sig}


def _introspected(optimizer, program_of=lambda main, loss: main):
    main, startup, loss, _ = _build(optimizer)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = _feed()
    prog = program_of(main, loss)
    exe.compiled_hlo(prog, feed=feed, fetch_list=[loss])
    compiled, _ = exe._resolve_compiled(prog, feed, [loss], None)
    sig = costmodel.signature(compiled.program_fingerprint)
    return main, compiled, _gauge("step_memory_bytes", sig), \
        _gauge("step_resident_bytes", sig)


@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
def test_compiled_hlo_stamps_every_kind(optimizer):
    _, _, memory, resident = _introspected(optimizer)
    assert MEMORY_KINDS <= set(memory)      # and `peak` where XLA fills it
    assert set(resident) == RESIDENT_KINDS
    assert memory["temp"] > 0 and memory["argument"] > 0
    assert all(v > 0 for v in resident.values()), resident


@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
def test_resident_bytes_are_the_shapes_bytes(optimizer):
    main, compiled, memory, resident = _introspected(optimizer)
    params = [p.name for p in main.global_block().all_parameters()]
    state = list(compiled.state_mut) + list(compiled.state_ro)
    other = [n for n in state
             if n not in params and n not in main._opt_state_of]
    assert resident["parameter"] == _nbytes(main, params) == \
        4 * (32 * 48 + 48 + 48 + 48 + 48 + 1)
    assert resident["optimizer_state"] == _nbytes(main, main._opt_state_of)
    # Momentum: a velocity a parameter; Adam: two moments and two powers
    per_param = {"momentum": lambda n: n, "adam": lambda n: 2 * n + 8}
    assert resident["optimizer_state"] == sum(
        per_param[optimizer](_nbytes(main, [p])) for p in params)
    # the learning rate and batch norm's two running statistics
    assert resident["other_state"] == _nbytes(main, other) == 4 + 2 * 48 * 4
    assert resident["feed"] == BATCH * (32 + 1) * 4
    # every argument is one of the four
    assert memory["argument"] == sum(resident.values())


@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
def test_alias_is_the_donated_mutable_state(optimizer):
    main, compiled, memory, _ = _introspected(optimizer)
    assert memory["alias"] == _nbytes(main, compiled.state_mut)
    assert 0 < memory["alias"] < memory["argument"]


def test_a_sharded_feed_counts_one_shard():
    import jax

    n = len(jax.devices())
    assert n > 1 and BATCH % n == 0
    main, compiled, memory, resident = _introspected(
        "momentum", lambda main, loss: fluid.CompiledProgram(
            main).with_data_parallel(loss_name=loss.name))
    assert resident["feed"] == BATCH // n * (32 + 1) * 4
    # state is replicated: whole on every device
    params = [p.name for p in main.global_block().all_parameters()]
    assert resident["parameter"] == _nbytes(main, params)


def test_device_nbytes_of_a_placed_array_is_its_shard():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.fluid.executor import device_nbytes

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    host = np.zeros((BATCH, 8), "float32")
    assert device_nbytes(host) == host.nbytes
    assert device_nbytes(host, NamedSharding(mesh, P("dp"))) == \
        host.nbytes // mesh.size
    assert device_nbytes(jax.device_put(
        host, NamedSharding(mesh, P("dp")))) == host.nbytes // mesh.size
    assert device_nbytes(jax.device_put(
        host, NamedSharding(mesh, P()))) == host.nbytes
    assert device_nbytes(np.float32(1)) == 4


def test_run_alone_stamps_nothing_and_compiles_no_introspection():
    main, startup, loss, _ = _build("adam")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    compiles = telemetry.registry().get("xla_backend_compiles_total")
    before = compiles.value(why="introspection")
    # an earlier test's copy of this program has the signature this has
    for name in ("step_memory_bytes", "step_resident_bytes"):
        telemetry.registry().get(name).reset()
    feed = _feed()
    for _ in range(100):
        exe.run(main, feed=feed, fetch_list=[loss])
    compiled, _ = exe._resolve_compiled(main, feed, [loss], None)
    sig = costmodel.signature(compiled.program_fingerprint)
    assert compiles.value(why="introspection") == before
    assert _gauge("step_memory_bytes", sig) == {}
    assert _gauge("step_resident_bytes", sig) == {}
    # asked for, the record is there, at one introspection compile; asked
    # again, the executable and its analysis are the ones in hand
    first = exe.compiled_memory(main, feed=feed, fetch_list=[loss])
    assert compiles.value(why="introspection") == before + 1
    assert exe.compiled_memory(main, feed=feed, fetch_list=[loss]) is first
    assert compiles.value(why="introspection") == before + 1
    memory = _gauge("step_memory_bytes", sig)
    assert memory["temp"] == first.temp_size_in_bytes
    assert memory["alias"] == first.alias_size_in_bytes


def test_memory_record_reads_the_analysis_once_over():
    analysis = types.SimpleNamespace(
        argument_size_in_bytes=10, output_size_in_bytes=8,
        alias_size_in_bytes=6, temp_size_in_bytes=4,
        generated_code_size_in_bytes=2, peak_memory_in_bytes=0)
    assert costmodel.memory_record(analysis) == {
        "argument": 10, "output": 8, "alias": 6, "temp": 4, "code": 2}
    analysis.peak_memory_in_bytes = 16
    assert costmodel.memory_record(analysis)["peak"] == 16


def test_staged_feed_bytes_of_a_loader_of_capacity_two():
    main, startup, loss, loader = _build("momentum", loader_capacity=2)
    pool = [_feed(np.random.default_rng(i)) for i in range(3)]
    loader.set_batch_generator(lambda: itertools.cycle(pool))
    staged = telemetry.registry().get("feed_staged_bytes")
    staged.reset()
    batch = BATCH * (32 + 1) * 4
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    loader.start()
    try:
        for _ in range(8):
            exe.run(main, fetch_list=[loss])
        # the worker fills up behind the consumer: the queue's two, the
        # lookahead's one and the one it is handing to the full queue
        deadline = time.monotonic() + 20
        while staged.value(stat="now") != 4 * batch and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        assert staged.value(stat="now") == 4 * batch
    finally:
        loader.reset()
    peak = staged.value(stat="peak")
    assert peak >= batch and peak % batch == 0 and peak <= 4 * batch
    assert staged.value(stat="now") == 0
    # a second pass starts from nothing held
    loader.start()
    try:
        exe.run(main, fetch_list=[loss])
    finally:
        loader.reset()
    assert staged.value(stat="now") == 0
    assert staged.value(stat="peak") == peak


def test_sample_device_memory_on_the_cpu_sets_nothing():
    import jax

    jax.devices()      # a backend is up: the sampler does ask it
    gauge = telemetry.registry().get("device_memory_bytes")
    gauge.reset()
    telemetry.sample_device_memory()
    telemetry.metrics_snapshot()
    assert gauge.labelsets() == []


def test_sample_device_memory_sets_what_the_backend_gives(monkeypatch):
    import jax
    from paddle_tpu.fluid import core_shim

    jax.devices()      # a process with no backend up is not sampled
    books = {"bytes_in_use": 5, "peak_bytes_in_use": 7,
             "peak_bytes_reserved": 11, "num_allocs": 3}
    monkeypatch.setattr(core_shim, "get_mem_usage", lambda i: dict(books))
    gauge = telemetry.registry().get("device_memory_bytes")
    gauge.reset()
    try:
        telemetry.sample_device_memory()
        sets = gauge.labelsets()
        assert {ls["stat"] for ls in sets} == {
            "bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved"}
        assert len({ls["device"] for ls in sets}) == 8
        assert gauge.value(device=0, stat="peak_bytes_reserved") == 11
        assert 'device_memory_bytes{device="0",stat="peak_bytes_in_use"} 7' \
            in telemetry.prometheus_text()
    finally:
        gauge.reset()


def test_prometheus_text_names_the_new_gauge_and_not_the_old():
    _introspected("momentum")
    text = telemetry.prometheus_text()
    assert 'step_memory_bytes{kind="temp",sig="' in text
    assert 'step_resident_bytes{kind="optimizer_state",sig="' in text
    assert "hlo_peak" + "_bytes" not in text     # the gauge that went


def test_a_scrape_starts_no_backend():
    """A metrics server beside a trainer must not take the trainer's chip:
    a process that never ran JAX samples nothing and stays off it."""
    import os
    import subprocess
    import sys

    code = (
        "from paddle_tpu.fluid import telemetry\n"
        "telemetry.prometheus_text(); telemetry.metrics_snapshot()\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, list(xb._backends)\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
