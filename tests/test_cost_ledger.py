"""Device-cost ledger (paddle_tpu/fluid/costmodel.py + tools/
cost_ledger.py): normalized per-executable HLO cost records, Fluid-op
attribution via lowering's named scopes, the checked-in baseline diff
gate, the roofline estimate, and the ledger-off bit-exactness contract.

Covers the PR's satellites too: compiled_cost per-inner-step window
normalization (XLA visits a scan body ONCE — a K window must NOT read
as a Kx regression), compiled_cost/compiled_memory coverage on the
explicit-collective path, the hlo_* gauges through dump_prometheus and
/aggregate, and FLAGS_device_profile trace capture.
"""

import glob
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import costmodel, flags, profiler, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build_train(seed=1):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        h = fluid.layers.fc(x, size=64, act="relu")
        loss = fluid.layers.mean(fluid.layers.fc(h, size=16,
                                                 act="tanh"))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    return main, startup, loss


_FEED = {"x": np.linspace(0, 1, 16 * 64, dtype=np.float32)
         .reshape(16, 64)}


def _stack(feed, k):
    return {n: np.stack([v] * k) for n, v in feed.items()}


def _compile_records():
    return [e for e in telemetry.step_events()
            if e.get("kind") == "compile"]


# ---------------------------------------------------------------------------
# compiled_cost normalization (satellite: K-window per-inner-step)
# ---------------------------------------------------------------------------

def test_compiled_cost_returns_flat_dict_and_raw_escape_hatch():
    """``compiled_cost()`` returns one flat {'flops', 'bytes accessed',
    ...} dict regardless of the backend's list-of-properties return;
    ``normalize=False`` hands back the raw backend object."""
    main, startup, loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    cost = exe.compiled_cost(main, feed=_FEED, fetch_list=[loss])
    assert isinstance(cost, dict)
    assert cost["flops"] > 0
    assert cost["bytes accessed"] > 0
    raw = exe.compiled_cost(main, feed=_FEED, fetch_list=[loss],
                            normalize=False)
    # whatever the backend shape, the normalizer must reproduce the dict
    assert costmodel.normalize_cost(raw) == cost


def test_window_cost_is_per_inner_step_not_k_times():
    """THE normalization pin: a steps_per_run=K window's cost figures
    are PER INNER STEP — XLA's analysis visits the scan body once, so
    K=16 must report ~the K=1 step's FLOPs, never 16x them (a K=64
    window must not read as a 64x regression)."""
    main, startup, loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    c1 = exe.compiled_cost(main, feed=_FEED, fetch_list=[loss])
    cK = exe.compiled_cost(main, feed=_stack(_FEED, 16),
                           fetch_list=[loss], steps_per_run=16)
    assert cK["flops"] == pytest.approx(c1["flops"], rel=0.15)
    # bytes get loop-carry overhead but must stay nowhere near 16x
    assert cK["bytes accessed"] < 2.0 * c1["bytes accessed"]
    # and the ledger record keeps the window size explicit
    rec = exe.cost_record(main, feed=_stack(_FEED, 16),
                          fetch_list=[loss], steps_per_run=16,
                          stamp=False)
    assert rec["k"] == 16
    assert rec["sig"].endswith(":k16")
    assert rec["window_flops"] == pytest.approx(16 * rec["flops"])


# ---------------------------------------------------------------------------
# Full records, attribution, gauges, /aggregate (satellite 6)
# ---------------------------------------------------------------------------

def test_cost_record_fields_attribution_and_gauges(tmp_path):
    """``Executor.cost_record`` produces the full normalized record, the
    HLO attribution names the Fluid ops that produced the cost, and the
    hlo_* and step_memory_bytes gauges surface through prometheus_text, dump_prometheus, and
    the /aggregate merge with the executable signature as label."""
    main, startup, loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rec = exe.cost_record(main, feed=_FEED, fetch_list=[loss])
    for f in ("flops", "transcendentals", "bytes_accessed",
              "argument_bytes", "output_bytes", "temp_bytes",
              "peak_bytes", "instructions", "fusions", "collectives",
              "estimated_step_s", "sig", "k"):
        assert f in rec, f
    assert rec["flops"] > 0 and rec["instructions"] > 0
    assert rec["peak_bytes"] == (rec["argument_bytes"] +
                                 rec["output_bytes"] +
                                 rec["temp_bytes"])
    assert rec["estimated_step_s"] > 0
    # attribution: the fc matmuls must be named fluid_mul/fluid_mul_grad
    hlo = exe.compiled_hlo(main, feed=_FEED, fetch_list=[loss])
    att = costmodel.op_attribution(hlo)
    assert any(op.startswith("fluid_mul") for op in att), sorted(att)
    top = costmodel.top_ops(att)
    assert top[0]["op"].startswith("fluid_"), top
    assert top[0]["flops_est"] > 0
    # gauges, labeled by signature
    txt = telemetry.prometheus_text()
    assert 'hlo_flops_total{sig="%s"}' % rec["sig"] in txt
    assert 'step_memory_bytes{kind="temp",sig="%s"}' % rec["sig"] in txt
    assert 'hlo_fusion_count{sig="%s"}' % rec["sig"] in txt
    # dump_prometheus -> /aggregate (tools/metrics_server.py)
    telemetry.dump_prometheus(str(tmp_path / "m.p7.prom"))
    srv = _load_tool("metrics_server")
    body = srv.aggregate_body(str(tmp_path))
    assert "hlo_flops_total" in body
    assert 'sig="%s"' % rec["sig"] in body
    assert 'process="7"' in body


def test_dispatch_stamps_lightweight_compile_record():
    """A fresh executable's first dispatch stamps a kind="compile"
    ledger record (signature, window size, compile seconds — host
    scalars only); cached-hit dispatches stamp nothing; the flag turns
    it off entirely."""
    main, startup, loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    # from an empty ring: once other tests of this worker have filled its
    # 1024 slots, its length stops growing and a slice by it is empty
    telemetry.reset_step_events()
    n0 = len(_compile_records())
    exe.run(main, feed=_FEED, fetch_list=[loss])
    recs = _compile_records()[n0:]
    assert len(recs) == 1, recs
    rec = recs[0]
    assert rec["sig"].endswith(":k1")
    assert rec["source"] == "dispatch"
    assert rec["compile_s"] > 0
    assert rec["window"] is False
    # cached hit: no new record
    exe.run(main, feed=_FEED, fetch_list=[loss])
    assert len(_compile_records()) == n0 + 1
    # ledger off: a fresh executable stamps nothing
    flags.set_flag("cost_ledger", False)
    try:
        main2, startup2, loss2 = _build_train(seed=2)
        exe.run(startup2)
        exe.run(main2, feed=_FEED, fetch_list=[loss2])
        assert len(_compile_records()) == n0 + 1
        assert exe.cost_record(main2, feed=_FEED,
                               fetch_list=[loss2]) is None
    finally:
        flags.set_flag("cost_ledger", True)


def test_ledger_off_bit_exact_with_zero_added_syncs():
    """FLAGS_cost_ledger=0 acceptance pin: losses are bit-exact with the
    ledger on, and the on-path adds ZERO host syncs over the off-path
    (profiler.record_host_sync counters)."""
    def run(n=4):
        main, startup, loss = _build_train()
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            s0 = profiler.host_sync_count()
            losses = [exe.run(main, feed=_FEED, fetch_list=[loss])[0]
                      for _ in range(n)]
            return np.asarray(losses), profiler.host_sync_count() - s0

    on_losses, on_syncs = run()
    flags.set_flag("cost_ledger", False)
    try:
        off_losses, off_syncs = run()
    finally:
        flags.set_flag("cost_ledger", True)
    np.testing.assert_array_equal(on_losses, off_losses)
    assert on_syncs == off_syncs


# ---------------------------------------------------------------------------
# Explicit-collective path (satellite: introspection test coverage)
# ---------------------------------------------------------------------------

def test_explicit_collective_cost_memory_and_wire_crosscheck():
    """``compiled_cost``/``compiled_memory`` work on the explicit-
    collective (shard_map ensure_built) path, the ledger record carries
    the static collective species + wire bytes, and the static per-step
    bytes CROSS-CHECK against the runtime collective_bytes_total{axis}
    counter: N dispatches move exactly N * static bytes."""
    from paddle_tpu.fluid.transpiler import GradAllReduce

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        y = fluid.layers.data(name="y", shape=[64], dtype="float32")
        pred = fluid.layers.fc(x, size=64)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    GradAllReduce().transpile(startup_program=startup,
                              main_program=main, rank=0,
                              endpoints=[], nranks=0)
    feed = {"x": np.zeros((16, 64), np.float32),
            "y": np.zeros((16, 64), np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    cost = exe.compiled_cost(main, feed=feed, fetch_list=[loss])
    assert cost["flops"] > 0
    mem = exe.compiled_memory(main, feed=feed, fetch_list=[loss])
    assert mem.argument_size_in_bytes > 0
    rec = exe.cost_record(main, feed=feed, fetch_list=[loss],
                          stamp=False)
    # static HLO carries the gradient all-reduce...
    assert rec["collectives"].get("all-reduce", 0) >= 1, \
        rec["collectives"]
    # ...and the trace-time wire accounting resolved it to the dp axis
    per_step = rec["collective_bytes_per_step"]
    assert per_step > 0
    assert any(k.endswith("@dp") for k in rec["collective_bytes"]), rec
    m = telemetry.counter("collective_bytes_total")
    base = m.value(axis="dp")
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss])
    assert m.value(axis="dp") - base == 3 * per_step


# ---------------------------------------------------------------------------
# Serving warmup ledger capture
# ---------------------------------------------------------------------------

def test_serving_warmup_ledger_records_per_bucket():
    """``warmup(ledger=True)`` captures one full ledger record per
    serving bucket, tagged ``serving:b<bucket>`` — the per-bucket
    FLOPs/memory ladder in the JSONL."""
    from paddle_tpu.fluid.serving import ServingExecutor

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        out = fluid.layers.softmax(fluid.layers.fc(x, size=8))
    infer = main.clone(for_test=True)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    telemetry.reset_step_events()      # a full ring's length cannot grow
    n0 = len(_compile_records())
    sv = ServingExecutor(infer, scope=scope,
                         feed_specs={"x": ((16,), "float32")},
                         fetch_list=[out], place=fluid.CPUPlace(),
                         max_batch=4)
    try:
        sv.warmup(ledger=True)
        tags = set()
        for e in _compile_records()[n0:]:
            if str(e.get("tag", "")).startswith("serving:b"):
                tags.add(e["tag"])
                assert e["flops"] > 0
        assert tags == {"serving:b%d" % b for b in sv.buckets}, tags
    finally:
        sv.close()


# ---------------------------------------------------------------------------
# The baseline diff gate (tools/cost_ledger.py)
# ---------------------------------------------------------------------------

def test_injected_regression_flags_probe_and_responsible_ops():
    """Acceptance pin: recompiling with a cost-changing knob
    (FLAGS_check_nan_inf=skip — per-op finite guards inflate the
    artifact) produces a diff the gate flags, naming the changed probe
    AND the responsible Fluid ops."""
    tool = _load_tool("cost_ledger")
    baseline = tool.collect(["mlp_k1"])
    flags.set_flag("check_nan_inf", "skip")
    try:
        current = tool.collect(["mlp_k1"])
    finally:
        flags.set_flag("check_nan_inf", "off")
    regressions, _notes = tool.diff(current, baseline)
    assert regressions, "nan-guard recompile must regress the artifact"
    assert any("mlp_k1" in r for r in regressions)
    assert any("responsible ops" in r for r in regressions)
    # and the clean recompile passes against itself
    clean, notes = tool.diff(baseline, baseline)
    assert not clean, clean


def test_cost_ledger_cli_check_exits_nonzero_on_regression(tmp_path):
    """End-to-end CLI pin: ``tools/cost_ledger.py --check`` against the
    CHECKED-IN baseline exits 1 under an injected cost-changing knob and
    names the probe; the same invocation passes clean env."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               FLAGS_check_nan_inf="skip")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "cost_ledger.py"),
         "--check", "--only", "mlp_k1"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "REGRESSION mlp_k1" in proc.stdout, proc.stdout


def test_checked_in_baseline_matches_probe_fleet():
    """The checked-in tests/cost_baseline.json stays in sync with the
    probe fleet: every probe has an entry with the gated fields (a
    probe rename without --update would silently skip the gate)."""
    with open(os.path.join(REPO, "tests", "cost_baseline.json")) as f:
        baseline = json.load(f)
    tool = _load_tool("cost_ledger")
    assert set(baseline) == set(tool.PROBES)
    for name, rec in baseline.items():
        for f in tool.RATIO_FIELDS:
            assert rec.get(f) is not None, (name, f)


# ---------------------------------------------------------------------------
# Roofline + report + device profile
# ---------------------------------------------------------------------------

def test_roofline_estimate_uses_configured_peaks():
    """estimated_step_s = max(flops/peak_flops, bytes/peak_bw), from the
    FLAGS_roofline_* knobs."""
    flags.set_flag("roofline_peak_flops", 1e6)
    flags.set_flag("roofline_peak_bytes_per_s", 1e9)
    try:
        # compute-bound: 2e6 flops / 1e6 = 2.0 s > 1e3 B / 1e9
        assert costmodel.roofline_seconds(2e6, 1e3) == \
            pytest.approx(2.0)
        # memory-bound
        assert costmodel.roofline_seconds(1e3, 5e9) == \
            pytest.approx(5.0)
    finally:
        flags.set_flag("roofline_peak_flops", 197e12)
        flags.set_flag("roofline_peak_bytes_per_s", 819e9)


def test_metrics_report_cost_section_and_roofline_line():
    """tools/metrics_report.py aggregates kind="compile" ledger records
    into a device-cost section (one row per signature, full captures
    overwrite dispatch stamps) plus the roofline-vs-measured line —
    without polluting the per-step timing rows."""
    mod = _load_tool("metrics_report")
    events = [
        {"ts_ns": 1, "dur_ns": 50_000, "step": 1, "k": 1},
        {"kind": "compile", "ts_ns": 2, "dur_ns": 0, "k": 1,
         "sig": "abc:k1", "source": "dispatch", "compile_s": 0.5},
        {"kind": "compile", "ts_ns": 3, "dur_ns": 0, "k": 1,
         "sig": "abc:k1", "source": "full", "flops": 1e6,
         "bytes_accessed": 2e5, "peak_bytes": 4096, "fusions": 3,
         "instructions": 40, "estimated_step_s": 1e-5,
         "tag": "train"},
        {"ts_ns": 4, "dur_ns": 50_000, "step": 2, "k": 1},
    ]
    rows = mod.summarize(events)
    cost = rows["cost"]
    assert cost["records"] == 2
    ent = cost["by_sig"]["abc:k1"]
    assert ent["records"] == 2
    assert ent["flops"] == 1e6 and ent["fusions"] == 3
    assert ent["compile_s"] == 0.5
    # ledger records never count as dispatches
    assert rows["all"]["dispatches"] == 2
    text = mod.format_report(rows)
    assert "device-cost ledger (2 compile record(s))" in text
    assert "abc:k1" in text and "roofline:" in text
    # streams without ledger records produce no section
    assert "cost" not in mod.summarize(
        [{"ts_ns": 1, "dur_ns": 1, "step": 1, "k": 1}])


def test_device_profile_flag_captures_trace_artifact(tmp_path):
    """FLAGS_device_profile=N brackets the next N dispatched steps in a
    jax.profiler trace written under FLAGS_device_profile_dir — the
    measured half of the roofline comparison."""
    out = str(tmp_path / "prof")
    flags.set_flag("device_profile", 2)
    flags.set_flag("device_profile_dir", out)
    profiler.device_profile_reset()
    try:
        main, startup, loss = _build_train()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=_FEED, fetch_list=[loss])
        assert not profiler._device_profile["active"]
        files = glob.glob(os.path.join(out, "**", "*"), recursive=True)
        assert any(os.path.isfile(f) for f in files), files
        assert profiler.device_profile_dir() == out
    finally:
        flags.set_flag("device_profile", 0)
        flags.set_flag("device_profile_dir", "")
        profiler.device_profile_reset()
