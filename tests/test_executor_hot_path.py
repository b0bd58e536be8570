"""Executor hot-path tests: cached dispatch plans, async fetches, the
introspection-cache aval key, and the train_from_dataset no-sync contract.

The dispatch plan (executor.py _DispatchPlan) makes the steady-state
``run()`` one dict lookup plus the jitted call; these tests pin the cache
semantics (reuse, invalidation) and the async dispatch contract
(``return_numpy=False`` fetches are live jax.Arrays; train_from_dataset
syncs the host only at print_period boundaries and the final drain).
"""

import functools

import numpy as np
import jax
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import flags, profiler


def _scale_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[3], dtype="float32")
            y = fluid.layers.scale(x, scale=2.0, bias=1.0)
    return main, startup, y


def _train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.fc(x, size=4, act=None)
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_second_run_reuses_cached_plan():
    """Same (program, feed signature, fetches): no recompile, plan hit."""
    main, startup, y = _scale_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        compiles_after_startup = exe._compile_count
        xs = np.arange(6, dtype=np.float32).reshape(2, 3)
        r1, = exe.run(main, feed={"x": xs}, fetch_list=[y])
        assert exe._compile_count == compiles_after_startup + 1
        hits0 = exe._plan_hits
        r2, = exe.run(main, feed={"x": xs + 1}, fetch_list=[y])
        # the second run is a cached-hit dispatch: no recompile, and the
        # plan cache (not just the executable cache) served it
        assert exe._compile_count == compiles_after_startup + 1
        assert exe._plan_hits == hits0 + 1
        np.testing.assert_allclose(r1, xs * 2 + 1)
        np.testing.assert_allclose(r2, (xs + 1) * 2 + 1)


def test_changed_feed_shape_compiles_new_plan():
    main, startup, y = _scale_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 3), np.float32)},
                fetch_list=[y])
        n = exe._compile_count
        res, = exe.run(main, feed={"x": np.ones((5, 3), np.float32)},
                       fetch_list=[y])
        assert exe._compile_count == n + 1   # new shape -> new executable
        assert res.shape == (5, 3)


def test_plan_reused_across_device_and_numpy_feeds():
    """A device-resident jax.Array feed and a numpy feed of the same
    shape/dtype share ONE compiled executable (the plan key is raw-value
    keyed but the executable cache is coerced-signature keyed)."""
    main, startup, y = _scale_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        xs = np.ones((2, 3), np.float32)
        exe.run(main, feed={"x": xs}, fetch_list=[y])
        n = exe._compile_count
        xd = jax.device_put(xs, exe._device)
        res, = exe.run(main, feed={"x": xd}, fetch_list=[y])
        assert exe._compile_count == n     # no new executable
        np.testing.assert_allclose(res, xs * 2 + 1)


def test_return_numpy_false_fetches_are_jax_arrays():
    """Async fetch contract: return_numpy=False hands back live jax.Array
    futures (no host sync) that materialize to the right values."""
    main, startup, loss = _train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        xs = np.ones((2, 4), np.float32)
        profiler.reset_host_sync_count()
        out = exe.run(main, feed={"x": xs}, fetch_list=[loss],
                      return_numpy=False)
        assert isinstance(out[0], jax.Array)
        # the async path recorded no executor-side host sync
        assert profiler.host_sync_count() == 0
        val = np.asarray(out[0])
        assert np.isfinite(val).all()
        # numpy fetch of the same step matches the materialized future
        ref, = exe.run(main, feed={"x": xs}, fetch_list=[loss])
        assert np.isfinite(ref).all()
        assert profiler.host_sync_count("fetch_numpy") == 1


def test_state_dtype_change_invalidates_introspection_cache():
    """compiled_hlo is cached per scope-state AVALS: reinitializing the
    scope with a different state shape/dtype must re-lower, not return the
    first call's stale analysis (ADVICE r5)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            c = fluid.layers.tensor.create_global_var(
                shape=[2], value=0.0, dtype="float32", persistable=True,
                name="c_state")
            x = fluid.layers.data(name="x", shape=[2], dtype="float32")
            y = fluid.layers.elementwise_add(x, c)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((1, 2), np.float32)}
    scope_a = fluid.Scope()
    scope_a.set_var("c_state", np.zeros((2,), np.float32))
    hlo_a = exe.compiled_hlo(main, feed=feed, fetch_list=[y], scope=scope_a)
    assert "f32[2]" in hlo_a
    # same program/feed/fetches, different state dtype: must re-lower
    scope_b = fluid.Scope()
    scope_b.set_var("c_state", np.zeros((2,), np.int32))
    hlo_b = exe.compiled_hlo(main, feed=feed, fetch_list=[y], scope=scope_b)
    assert hlo_b != hlo_a
    assert "s32[2]" in hlo_b
    # and the first key still serves from cache (one executable each)
    hlo_a2 = exe.compiled_hlo(main, feed=feed, fetch_list=[y], scope=scope_a)
    assert hlo_a2 == hlo_a


def test_compiled_hlo_works_under_check_nan_inf():
    """compiled_hlo/compiled_memory/compiled_cost must not crash when
    FLAGS_check_nan_inf wraps the step in checkify (ADVICE r5: .fn is a
    plain closure there; the block's _jitted carries the lowerable)."""
    main, startup, y = _scale_program()
    exe = fluid.Executor(fluid.CPUPlace())
    flags.set_flag("check_nan_inf", True)
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            feed = {"x": np.ones((2, 3), np.float32)}
            hlo = exe.compiled_hlo(main, feed=feed, fetch_list=[y])
            assert hlo
            cost = exe.compiled_cost(main, feed=feed, fetch_list=[y])
            assert cost is not None
    finally:
        flags.set_flag("check_nan_inf", False)


_ROUTE_BATCHES = [np.random.RandomState(i).rand(16, 8).astype(np.float32)
                  for i in range(4)]


@functools.lru_cache(maxsize=None)   # the reference route runs once
def _route_losses(route, source, K):
    """Train 4 steps of one small program down one route of
    ``Executor.run`` / ``run_window`` and check, on the way, what every
    route owes: ONE compile, ONE plan miss then hits, and introspection
    resolving the executable the step ran."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.fc(x, size=8)))
        fluid.optimizer.SGD(0.1).minimize(loss)
        if source == "loader":
            loader = fluid.DataLoader.from_generator(
                feed_list=[x], capacity=4, iterable=False,
                steps_per_run=K)
            loader.set_batch_generator(
                lambda: ({"x": b} for b in _ROUTE_BATCHES))
    target = main
    if route != "program":
        target = fluid.CompiledProgram(main)
    if route.startswith("data_parallel"):
        bs = fluid.BuildStrategy()
        bs.zero_shard_optimizer_state = route.endswith("zero")
        target = target.with_data_parallel(loss_name=loss.name,
                                           build_strategy=bs)
    if K is None:
        feeds = [{"x": b} for b in _ROUTE_BATCHES]
    else:
        feeds = [{"x": np.stack(_ROUTE_BATCHES[i:i + K])}
                 for i in range(0, len(_ROUTE_BATCHES), K)]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        compiles0, hits0 = exe.compile_count(), exe._plan_hits
        if source == "loader":
            loader.start()
        out = []
        for feed in feeds:
            if source == "loader":
                got = exe.run(target, fetch_list=[loss])
            elif K is None:
                got = exe.run(target, feed=feed, fetch_list=[loss])
            else:
                got = exe.run_window(target, feed=feed, fetch_list=[loss],
                                     steps_per_run=K)
            out.extend(np.asarray(got[0]).reshape(-1).tolist())
        assert exe.compile_count() == compiles0 + 1
        assert exe._plan_hits == hits0 + len(feeds) - 1
        ran = exe._last_compiled
        assert ran.steps_per_run == (K or 1) and ran.is_window == bool(K)
        hlo = exe.compiled_hlo(target, feed=feeds[0], fetch_list=[loss],
                               steps_per_run=K)
        assert ("all-reduce" in hlo) == route.startswith("data_parallel")
        assert exe._resolve_compiled(target, feeds[0], [loss], scope,
                                     K)[0] is ran
        assert exe.compile_count() == compiles0 + 1
    return out


@pytest.mark.parametrize("K", [None, 2], ids=["step", "window2"])
@pytest.mark.parametrize("source", ["fed", "loader"])
@pytest.mark.parametrize("route", ["program", "plain_compiled",
                                   "data_parallel", "data_parallel_zero"])
def test_every_route_is_the_one_dispatch(route, source, K):
    """{Program, plain CompiledProgram, with_data_parallel, + ZeRO} x
    {fed, program-bound loader} x {step, window of 2} all go through
    Executor._run -> _resolve -> _run_plan and train alike."""
    want = _route_losses("program", "fed", None)
    assert want[-1] < want[0]
    np.testing.assert_allclose(_route_losses(route, source, K), want,
                               rtol=1e-5)


def _write_dataset(tmp_path, n_lines):
    # one dense int64 slot, one value per instance
    p = str(tmp_path / "shard.txt")
    with open(p, "w") as f:
        for i in range(n_lines):
            f.write("1 %d\n" % (i + 1))
    return [p]


def test_train_from_dataset_syncs_only_at_print_period_and_drain(tmp_path):
    """The streaming loop must not sync the host between batches: the
    recorded host syncs are exactly the print_period loss pulls plus the
    final drain (the acceptance-criteria sync-counting hook)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            slot = fluid.layers.data(name="slot1", shape=[1], dtype="int64")
            xf = fluid.layers.cast(slot, "float32")
            y = fluid.layers.fc(xf, size=3, act=None)
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)

    ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    ds.set_batch_size(2)
    ds.set_thread(1)
    ds.set_filelist(_write_dataset(tmp_path, 12))   # 6 batches
    ds.set_use_var([slot])
    ds.load_into_memory()

    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        profiler.reset_host_sync_count()
        exe.train_from_dataset(main, ds, fetch_list=[loss], print_period=3)
        # 6 batches, print_period=3 -> pulls at batch 3 and 6, + 1 drain
        assert profiler.host_sync_count("print_period") == 2
        assert profiler.host_sync_count("drain") == 1
        assert profiler.host_sync_count() == 3


def test_train_from_dataset_prefetch_feeds_device_arrays(tmp_path):
    """The dataset path prefetches feeds to the device: inside run() the
    feed values are already jax.Arrays (H2D issued ahead of consumption),
    so the step pays no per-batch host coercion."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            slot = fluid.layers.data(name="slot1", shape=[1], dtype="int64")
            xf = fluid.layers.cast(slot, "float32")
            loss = fluid.layers.mean(xf)

    ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    ds.set_batch_size(2)
    ds.set_thread(1)
    ds.set_filelist(_write_dataset(tmp_path, 6))
    ds.set_use_var([slot])
    ds.load_into_memory()

    exe = fluid.Executor(fluid.CPUPlace())
    seen = []
    orig_run = exe.run

    def spy_run(program=None, feed=None, **kw):
        if feed:
            seen.append(all(isinstance(v, jax.Array) for v in feed.values()))
        return orig_run(program, feed=feed, **kw)

    exe.run = spy_run
    with fluid.scope_guard(fluid.Scope()):
        orig_run(startup)
        exe.train_from_dataset(main, ds, fetch_list=[loss], print_period=100)
    assert seen and all(seen)


def test_noniterable_loader_prefetches_to_consumer_device():
    """A program-bound DataLoader with no explicit places device_puts
    batches to the CONSUMING executor's device once Executor.run has
    bound it (reader.py _consumer_device wiring)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[2], dtype="float32")
            y = fluid.layers.scale(x, scale=2.0)
            loader = fluid.DataLoader.from_generator(
                feed_list=[x], capacity=2, iterable=False)

    def gen():
        for i in range(4):
            yield {"x": np.full((2, 2), float(i), np.float32)}
    loader.set_batch_generator(gen)

    exe = fluid.Executor(fluid.CPUPlace())
    # deterministic: bind the device BEFORE the producer starts (the
    # in-band binding on first run() is racy to observe from a test)
    loader._consumer_device = exe._device
    loader.start()
    try:
        batch = loader.next_feed()
        assert isinstance(batch["x"], jax.Array)
        assert batch["x"].devices() == {exe._device}
    finally:
        loader.reset()


def test_plan_cache_cleared_on_close():
    main, startup, y = _scale_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 3), np.float32)},
                fetch_list=[y])
        assert exe._plans
        exe.close()
        assert not exe._plans and not exe._cache
