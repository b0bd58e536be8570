"""Unified runtime telemetry (fluid/telemetry.py): registry instrument
types, the step-event ring buffer, all three exporters, the legacy
profiler APIs as registry views, and the hot-path zero-sync contract."""

import json

import numpy as np
import jax
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import flags, profiler, telemetry


# ---------------------------------------------------------------------------
# Instrument types
# ---------------------------------------------------------------------------

def test_counter_labels_and_total():
    c = telemetry.counter("t_unit_counter")
    c.reset()
    c.inc(tag="a")
    c.inc(2, tag="b")
    c.inc()                       # unlabeled set is its own series
    assert c.value(tag="a") == 1
    assert c.value(tag="b") == 2
    assert c.value() == 4         # no labels: sum across label sets
    assert {"tag": "a"} in c.labelsets()


def test_gauge_last_write_and_none_until_set():
    g = telemetry.gauge("t_unit_gauge")
    g.reset()
    assert g.value() is None
    g.set(3.5)
    g.set(1.25)
    assert g.value() == 1.25
    g.inc()
    assert g.value() == 2.25


def test_histogram_buckets_sum_count():
    h = telemetry.histogram("t_unit_hist", buckets=(0.1, 1.0, 10.0))
    h.reset()
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    val = h.value()
    assert val["count"] == 4
    assert val["sum"] == pytest.approx(55.55)
    snap = telemetry.registry().snapshot()["t_unit_hist"]
    buckets = snap["values"][0]["value"]["buckets"]
    # one observation per bucket incl. the +Inf overflow
    assert buckets == {"0.1": 1, "1.0": 1, "10.0": 1, "+Inf": 1}


def test_registry_get_or_create_and_type_conflict():
    c1 = telemetry.counter("t_unit_same")
    c2 = telemetry.counter("t_unit_same")
    assert c1 is c2
    with pytest.raises(TypeError):
        telemetry.gauge("t_unit_same")


def test_reset_keeps_instrument_objects():
    """Producers hold module-level references; reset must zero values
    without invalidating them."""
    c = telemetry.counter("t_unit_reset")
    c.inc(5)
    telemetry.reset_metrics()
    assert c.value() == 0
    assert telemetry.counter("t_unit_reset") is c
    c.inc()
    assert c.value() == 1


# ---------------------------------------------------------------------------
# Step-event ring
# ---------------------------------------------------------------------------

def test_step_event_ring_is_bounded():
    prev = flags.get_flag("metrics_ring")
    flags.set_flag("metrics_ring", 4)
    telemetry.reset_step_events()      # re-sized from the flag
    try:
        for i in range(10):
            telemetry.record_step_event(step=i, k=1, dur_ns=100)
        evs = telemetry.step_events()
        assert len(evs) == 4                       # bounded
        assert [e["step"] for e in evs] == [6, 7, 8, 9]   # newest kept
        assert telemetry.step_events_recorded() == 10     # total tracked
    finally:
        flags.set_flag("metrics_ring", prev)
        telemetry.reset_step_events()


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def test_metrics_snapshot_is_plain_dict():
    c = telemetry.counter("t_unit_snap")
    c.reset()
    c.inc(3, site="x")
    snap = telemetry.metrics_snapshot()
    ent = snap["t_unit_snap"]
    assert ent["type"] == "counter"
    assert {"labels": {"site": "x"}, "value": 3} in ent["values"]
    assert "_step_events" in snap
    json.dumps(snap)    # snapshot must be JSON-serializable as-is


def test_jsonl_exporter_appends_one_line_per_event(tmp_path):
    path = str(tmp_path / "events.jsonl")
    telemetry.reset_step_events()
    flags.set_flag("metrics_jsonl", path)
    try:
        telemetry.record_step_event(step=0, k=1, dur_ns=10, plan_hit=False)
        telemetry.record_step_event(step=1, k=4, dur_ns=40, plan_hit=True)
    finally:
        flags.set_flag("metrics_jsonl", "")
        telemetry.close_jsonl()
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert len(lines) == 2
    assert lines[0]["step"] == 0 and lines[0]["plan_hit"] is False
    assert lines[1]["k"] == 4 and lines[1]["plan_hit"] is True


def test_jsonl_handles_numpy_scalars(tmp_path):
    path = str(tmp_path / "np.jsonl")
    flags.set_flag("metrics_jsonl", path)
    try:
        telemetry.record_step_event(step=np.int32(3), k=1, dur_ns=1)
    finally:
        flags.set_flag("metrics_jsonl", "")
        telemetry.close_jsonl()
    assert json.loads(open(path).read())["step"] == 3


def test_dump_prometheus_text_format(tmp_path):
    c = telemetry.counter("t_unit_prom")
    c.reset()
    c.inc(7, tag="fetch")
    h = telemetry.histogram("t_unit_prom_hist", buckets=(1.0, 2.0))
    h.reset()
    h.observe(1.5)
    path = str(tmp_path / "metrics.prom")
    text = telemetry.dump_prometheus(path)
    assert open(path).read() == text
    assert "# TYPE t_unit_prom counter" in text
    assert 't_unit_prom{tag="fetch"} 7' in text
    # histogram: cumulative buckets + sum + count
    assert 't_unit_prom_hist_bucket{le="1.0"} 0' in text
    assert 't_unit_prom_hist_bucket{le="2.0"} 1' in text
    assert 't_unit_prom_hist_bucket{le="+Inf"} 1' in text
    assert "t_unit_prom_hist_count 1" in text


# ---------------------------------------------------------------------------
# Legacy profiler APIs as registry views
# ---------------------------------------------------------------------------

def test_host_sync_counter_is_registry_backed():
    profiler.reset_host_sync_count()
    profiler.record_host_sync("fetch_numpy")
    profiler.record_host_sync("drain")
    assert profiler.host_sync_count() == 2
    assert profiler.host_sync_count("drain") == 1
    reg = telemetry.registry().counter("host_syncs_total")
    assert reg.value(tag="fetch_numpy") == 1
    assert reg.value() == 2


def test_window_stats_registry_backed():
    profiler.reset_window_stats()
    profiler.record_window(8)
    profiler.record_window(4)
    assert profiler.window_stats() == {
        "windows": 2, "inner_steps": 12, "last_k": 4}
    assert telemetry.registry().counter(
        "window_inner_steps_total").value() == 12


def test_checkpoint_stats_registry_backed():
    profiler.reset_checkpoint_stats()
    assert profiler.checkpoint_stats()["last_step"] is None
    profiler.record_checkpoint_save(0.25, 1000, 16)
    s = profiler.checkpoint_stats()
    assert s["saves"] == 1 and s["last_step"] == 16
    assert s["total_bytes"] == 1000 and s["last_save_s"] == 0.25
    assert profiler.steps_since_checkpoint(20) == 4
    profiler.reset_checkpoint_stats()


def test_benchmark_stats_window_aware():
    """ROADMAP PR-4 follow-on: one fused K-step timing entry attributes
    window_s / K to each inner step, so mean_s is comparable across K,
    and the stats dict reports K."""
    profiler.reset_benchmark_stats()
    profiler.record_benchmark_step(0.016, 16)    # one K=16 window
    profiler.record_benchmark_step(0.001)        # one plain step
    s = profiler.benchmark_stats()
    assert s["steps"] == 17
    assert s["total_s"] == pytest.approx(0.017)
    assert s["mean_s"] == pytest.approx(0.017 / 17)
    assert s["last_k"] == 1
    profiler.reset_benchmark_stats()
    assert profiler.benchmark_stats() == {
        "steps": 0, "total_s": 0.0, "mean_s": 0.0, "last_k": 0}


def test_bad_step_pool_stays_lazy():
    """The registry only sees bad-step counts at read time — verdict
    arrays pool unmaterialized (the lazy/device-resident pattern)."""
    profiler.reset_bad_step_count()
    profiler.record_bad_step(np.array([True, False, False]))
    assert profiler.pending_bad_step_verdicts() == 1
    assert telemetry.registry().counter("bad_steps_total").value() == 0
    assert profiler.bad_step_count() == 2        # read drains the pool
    assert profiler.pending_bad_step_verdicts() == 0
    assert telemetry.registry().counter("bad_steps_total").value() == 2
    profiler.reset_bad_step_count()


# ---------------------------------------------------------------------------
# Executor step-events + the hot-path contract
# ---------------------------------------------------------------------------

def _train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.fc(x, size=4, act=None)
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_step_events_record_dispatches_without_syncs():
    """The acceptance contract: with FLAGS_metrics_jsonl unset, a
    cached-hit run()/run_window() records a full step-event and ZERO
    host syncs (asserted via the PR-2 record_host_sync counters)."""
    main, startup, loss = _train_program()
    telemetry.reset_step_events()
    exe = fluid.Executor(fluid.CPUPlace())
    xs = np.ones((2, 4), np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": xs}, fetch_list=[loss],
                return_numpy=False)
        profiler.reset_host_sync_count()
        exe.run(main, feed={"x": xs}, fetch_list=[loss],
                return_numpy=False)       # cached-hit step
        stacked = {"x": np.stack([xs] * 4)}
        exe.run_window(main, feed=stacked, fetch_list=[loss],
                       steps_per_run=4)
        exe.run_window(main, feed=stacked, fetch_list=[loss],
                       steps_per_run=4)   # cached-hit window
    assert profiler.host_sync_count() == 0
    evs = [e for e in telemetry.step_events()
           if not e.get("kind") and e["fetch_count"]]
    assert len(evs) == 4
    first, hit, w_first, w_hit = evs
    assert first["plan_hit"] is False and first["compile_s"] is not None
    assert hit["plan_hit"] is True and hit["compile_s"] is None
    assert hit["syncs"] == 0 and hit["k"] == 1 and not hit["window"]
    assert w_first["window"] and w_first["k"] == 4
    assert w_hit["plan_hit"] is True and w_hit["syncs"] == 0
    # feed bytes from attribute reads: 4 stacked (2,4) f32 batches
    assert w_hit["feed_bytes"] == 4 * 2 * 4 * 4
    assert all(e["verdicts"] == 0 for e in evs)   # nan_inf policy off
    assert all(e["ckpt_overlap"] is False for e in evs)
    assert all(e["dur_ns"] > 0 and e["ts_ns"] > 0 for e in evs)


def test_step_event_counts_fetch_numpy_sync():
    main, startup, loss = _train_program()
    telemetry.reset_step_events()
    exe = fluid.Executor(fluid.CPUPlace())
    xs = np.ones((2, 4), np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": xs}, fetch_list=[loss])   # numpy fetch
    ev = [e for e in telemetry.step_events()
           if not e.get("kind") and e["fetch_count"]][-1]
    assert ev["syncs"] == 1


def test_skip_policy_step_events_count_verdicts_lazily():
    main, startup, loss = _train_program()
    flags.set_flag("check_nan_inf", "skip")
    profiler.reset_bad_step_count()
    telemetry.reset_step_events()
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        xs = np.ones((2, 4), np.float32)
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main, feed={"x": xs}, fetch_list=[loss],
                    return_numpy=False)
        ev = [e for e in telemetry.step_events()
           if not e.get("kind") and e["fetch_count"]][-1]
        assert ev["verdicts"] == 1     # counted, never materialized here
        # startup + train step each pooled one unmaterialized verdict
        assert profiler.pending_bad_step_verdicts() == 2
        assert profiler.bad_step_count() == 0     # all steps were finite
    finally:
        flags.set_flag("check_nan_inf", "off")
        profiler.reset_bad_step_count()


def test_executor_jsonl_integration(tmp_path):
    """FLAGS_metrics_jsonl exporter fed by real dispatches: one line per
    step/window event, parseable, carrying the schema fields."""
    main, startup, loss = _train_program()
    path = str(tmp_path / "run.jsonl")
    telemetry.reset_step_events()
    flags.set_flag("metrics_jsonl", path)
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        xs = np.ones((2, 4), np.float32)
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(3):
                exe.run(main, feed={"x": xs}, fetch_list=[loss],
                        return_numpy=False)
    finally:
        flags.set_flag("metrics_jsonl", "")
        telemetry.close_jsonl()
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    steps = [e for e in lines
             if not e.get("kind") and e["fetch_count"]]
    assert len(steps) == 3
    for key in ("ts_ns", "dur_ns", "step", "k", "window", "plan_hit",
                "compile_s", "feed_bytes", "syncs", "verdicts",
                "ckpt_overlap"):
        assert key in steps[0]
    assert [e["plan_hit"] for e in steps] == [False, True, True]


def test_checkpoint_async_overlap_gauge(tmp_path):
    """checkpoint_async_in_flight rises while the background save runs
    and clears when it commits — the step-event ckpt_overlap source."""
    import threading
    from paddle_tpu.fluid import checkpoint as ckpt

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            fluid.layers.tensor.create_global_var(
                shape=[2], value=1.0, dtype="float32", persistable=True,
                name="w")
    scope = fluid.Scope()
    scope.set_var("w", np.ones((2,), np.float32))
    gauge = telemetry.registry().gauge("checkpoint_async_in_flight")

    release = threading.Event()
    started = threading.Event()

    def hook(point):
        if point == "manifest_begin":
            started.set()
            release.wait(timeout=10)

    prev = ckpt.set_fault_hook(hook)
    try:
        mgr = ckpt.CheckpointManager(str(tmp_path), async_save=True,
                                     scope=scope, main_program=main)
        mgr.save(step=1)
        assert started.wait(timeout=10)
        assert gauge.value() == 1          # save in flight
        release.set()
        mgr.wait()
        assert gauge.value() == 0
    finally:
        ckpt.set_fault_hook(prev)
        release.set()


def test_compile_and_cache_counters():
    main, startup, loss = _train_program()
    reg = telemetry.registry()
    compiles = reg.counter("executor_compiles_total")
    cache = reg.counter("executor_executable_cache_total")
    c0, hit0 = compiles.value(), cache.value(result="hit")
    exe = fluid.Executor(fluid.CPUPlace())
    xs = np.ones((2, 4), np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": xs}, fetch_list=[loss],
                return_numpy=False)
        # a second raw dtype is a second plan over the SAME executable
        # (the plan keys on the raw dtype, the executable on the one the
        # program declares): a plan miss that hits the executable cache
        exe.run(main, feed={"x": xs.astype(np.float64)}, fetch_list=[loss],
                return_numpy=False)
    assert compiles.value() == c0 + 2          # startup + main
    assert cache.value(result="hit") == hit0 + 1
    # compile durations landed in the histogram
    h = reg.histogram("executor_compile_seconds")
    assert h.value(kind="dispatch")["count"] >= 2


def test_lowering_trace_counters_only_grow_on_compile():
    main, startup, loss = _train_program()
    blocks = telemetry.registry().counter("lowering_blocks_traced_total")
    exe = fluid.Executor(fluid.CPUPlace())
    xs = np.ones((2, 4), np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": xs}, fetch_list=[loss],
                return_numpy=False)
        n = blocks.value()
        exe.run(main, feed={"x": xs}, fetch_list=[loss],
                return_numpy=False)   # cached hit: NO retrace
    assert blocks.value() == n


def test_loader_batch_and_wait_metrics():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[2], dtype="float32")
            fluid.layers.scale(x, scale=2.0)
            loader = fluid.DataLoader.from_generator(
                feed_list=[x], capacity=2, iterable=False)

    def gen():
        for i in range(3):
            yield {"x": np.full((2, 2), float(i), np.float32)}
    loader.set_batch_generator(gen)

    batches = telemetry.registry().counter("loader_batches_total")
    waits = telemetry.registry().counter("data_wait_seconds_total")
    b0, w0 = batches.value(), waits.value()
    loader.start()
    try:
        loader.next_feed()
        loader.next_feed()
    finally:
        loader.reset()
    assert batches.value() >= b0 + 2
    assert waits.value() >= w0
    assert telemetry.registry().gauge(
        "data_wait_last_seconds").value() is not None


def test_window_flush_reasons_counted():
    from paddle_tpu.fluid.dataset import stack_batch_windows
    flushes = telemetry.registry().counter("window_flushes_total")
    full0 = flushes.value(reason="full")
    trail0 = flushes.value(reason="trailing")
    shape0 = flushes.value(reason="shape_change")
    batches = [{"x": np.zeros((2, 3), np.float32)} for _ in range(5)]
    batches.insert(2, {"x": np.zeros((1, 3), np.float32)})  # ragged
    list(stack_batch_windows(iter(batches), 2))
    assert flushes.value(reason="shape_change") >= shape0 + 1
    assert flushes.value(reason="full") >= full0 + 1
    assert flushes.value(reason="trailing") >= trail0


def test_metrics_report_optimizer_memory_and_overlap_section():
    """tools/metrics_report.py aggregates the opt_state_bytes /
    comm_buckets step-event fields into an optimizer-memory + overlap
    section: bytes/device and the 1 - 1/buckets schedulable-overlap
    bound (weight-update sharding PR)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "metrics_report", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "metrics_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    events = [
        {"ts_ns": 1, "dur_ns": 1000, "step": 1, "k": 1,
         "comm_bytes": 100, "comm_by": {"reducescatter_fp32": 50,
                                        "allgather_fp32": 50},
         "comm_buckets": 4, "opt_state_bytes": 4096},
        {"ts_ns": 2, "dur_ns": 1000, "step": 2, "k": 1,
         "comm_bytes": 100, "comm_by": {"reducescatter_fp32": 50,
                                        "allgather_fp32": 50},
         "comm_buckets": 2, "opt_state_bytes": 4096},
        {"ts_ns": 3, "dur_ns": 900, "step": 3, "k": 1},  # eval: no comm
    ]
    rows = mod.summarize(events)
    opt = rows["optimizer"]
    assert opt["opt_state_bytes"] == 4096
    assert opt["buckets_per_dispatch"] == 3.0
    # mean of (1 - 1/4, 1 - 1/2)
    assert abs(opt["overlap_frac"] - 0.625) < 1e-9
    text = mod.format_report(rows)
    assert "optimizer: 4096 state bytes/device" in text
    assert "overlap 0.62" in text

    # events without the fields (older runs) produce no section
    assert "optimizer" not in mod.summarize(
        [{"ts_ns": 1, "dur_ns": 1, "step": 1, "k": 1}])


def test_metrics_report_serving_section():
    """tools/metrics_report.py aggregates kind="serving" batch records
    (one per padded dispatch) into a serving section: per-request
    p50/p99 queue wait (flattened qwaits_us lists) split from per-batch
    compute, occupancy, batches-by-bucket, recompiles, and the
    cumulative reject total — without polluting the per-step timing
    rows (serving PR)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "metrics_report", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "metrics_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    events = [
        {"kind": "serving", "ts_ns": 1, "dur_ns": 400_000, "k": 0,
         "bucket": 4, "rows": 3, "occupancy": 0.75,
         "qwaits_us": [100.0, 200.0, 300.0], "recompiled": 0,
         "rejects_total": 0},
        {"kind": "serving", "ts_ns": 2, "dur_ns": 600_000, "k": 0,
         "bucket": 8, "rows": 8, "occupancy": 1.0,
         "qwaits_us": [50.0] * 8, "recompiled": 1, "rejects_total": 2},
        {"ts_ns": 3, "dur_ns": 900, "step": 3, "k": 1},  # a train step
    ]
    rows = mod.summarize(events)
    srv = rows["serving"]
    assert srv["batches"] == 2 and srv["requests"] == 11
    assert srv["rows"] == 11 and srv["padded_rows"] == 1
    assert srv["by_bucket"] == {"4": 1, "8": 1}
    assert srv["recompiles"] == 1 and srv["rejects"] == 2
    assert srv["p50_queue_wait_us"] == 50.0
    assert srv["p99_queue_wait_us"] == 300.0
    assert srv["p50_compute_us"] == 400.0
    assert srv["p99_compute_us"] == 600.0
    assert abs(srv["occupancy_mean"] - 0.875) < 1e-9
    # serving records never leak into the per-step timing rows
    assert rows["all"]["dispatches"] == 1
    text = mod.format_report(rows)
    assert "serving: 11 request(s) in 2 batch(es)" in text
    assert "batches by bucket: 4=1, 8=1" in text

    # rejects_total is a cumulative per-EXECUTOR sample (records carry
    # the instance's sid): two instances at 2 rejects each SUM to 4 —
    # a plain max over the mixed stream would under-report 2
    multi = [dict(events[0], sid=1, rejects_total=2),
             dict(events[1], sid=2, rejects_total=2),
             dict(events[0], sid=1, rejects_total=1)]  # stale sample
    assert mod.summarize(multi)["serving"]["rejects"] == 4

    # no serving records -> no section
    assert "serving" not in mod.summarize(
        [{"ts_ns": 1, "dur_ns": 1, "step": 1, "k": 1}])
