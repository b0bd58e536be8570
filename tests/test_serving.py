"""Continuous-batching serving executor (fluid/serving.py).

Acceptance matrix (ISSUE 12): zero steady-state recompiles after
warmup() over the bucket ladder (telemetry-pinned across 1000+
randomized-batch requests); padding isolation — a request's response is
bit-identical served alone vs packed into any bucket alongside
arbitrary other requests; graceful drain — SIGTERM mid-load exits 0
with every accepted request answered, metrics flushed, and no orphaned
serving threads; backpressure rejects are counted; the
save_inference_model → load_inference_model → ServingExecutor round
trip follows the saved manifest's feed order for positional requests.
"""

import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import flags, layers, preemption, serving, telemetry
from paddle_tpu.fluid.serving import (ServingClosedError, ServingError,
                                      ServingExecutor, ServingRejectedError,
                                      bucket_ladder)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.fixture(autouse=True)
def _clean_preemption_state():
    preemption.clear()
    yield
    preemption.clear()


def _build_infer(in_dim=16, out_dim=10):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[in_dim], dtype="float32")
        h = layers.fc(x, size=32, act="relu")
        out = layers.softmax(layers.fc(h, size=out_dim))
    return main.clone(for_test=True), startup, out


def _serving(infer, out, scope, **kw):
    kw.setdefault("feed_specs", {"x": ((16,), "float32")})
    kw.setdefault("fetch_list", [out])
    kw.setdefault("place", fluid.CPUPlace())
    return ServingExecutor(infer, scope=scope, **kw)


@pytest.fixture()
def served():
    """(infer_program, out_var, scope with initialized params)."""
    infer, startup, out = _build_infer()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return infer, out, scope


# ---------------------------------------------------------------------------
# Bucket ladder
# ---------------------------------------------------------------------------

def test_bucket_ladder_defaults_and_overrides():
    assert bucket_ladder(8) == [1, 2, 4, 8]
    # a non-power-of-two cap becomes the top bucket
    assert bucket_ladder(12) == [1, 2, 4, 8, 12]
    assert bucket_ladder(1) == [1]
    # explicit buckets win, get sorted and de-duplicated
    assert bucket_ladder(64, buckets=(8, 2, 8, 32)) == [2, 8, 32]
    with pytest.raises(ValueError):
        bucket_ladder(64, buckets=(0, 4))
    with pytest.raises(ValueError):
        bucket_ladder(0)


def test_bucket_ladder_flag():
    flags.set_flag("serving_buckets", "4, 16 2")
    try:
        assert bucket_ladder(64) == [2, 4, 16]
        # explicit argument still beats the flag
        assert bucket_ladder(64, buckets=(3,)) == [3]
    finally:
        flags.set_flag("serving_buckets", "")


# ---------------------------------------------------------------------------
# Core serve loop
# ---------------------------------------------------------------------------

def test_serve_parity_and_per_request_slicing(served):
    """Responses match a direct executor run of the same rows, request
    boundaries are respected, and shapes carry each request's own row
    count."""
    infer, out, scope = served
    exe = fluid.Executor(fluid.CPUPlace())
    sv = _serving(infer, out, scope, max_batch=8, max_wait_ms=2.0)
    sv.warmup()
    rng = np.random.RandomState(0)
    reqs = [rng.randn(int(rng.randint(1, 6)), 16).astype(np.float32)
            for _ in range(24)]
    futs = [sv.submit({"x": a}) for a in reqs]
    for a, f in zip(reqs, futs):
        got, = f.result(timeout=60)
        assert got.shape == (a.shape[0], 10)
        want, = exe.run(infer, feed={"x": a}, fetch_list=[out],
                        scope=scope, return_numpy=False)
        np.testing.assert_allclose(got, np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    st = sv.stats()
    assert st["responses"] == len(reqs)
    # continuous batching actually batched: fewer dispatches than
    # requests once the queue had depth
    assert st["batches"] < len(reqs)
    assert 0.0 < st["occupancy_mean"] <= 1.0
    sv.close()
    assert sv.drained()


def test_zero_steady_state_recompiles_across_randomized_batches(served):
    """The headline shape-discipline pin: after warmup() over the
    ladder, 1000+ requests with randomized batch sizes leave
    ``serving_recompiles_total`` exactly where it was."""
    infer, out, scope = served
    sv = _serving(infer, out, scope, max_batch=8, max_wait_ms=1.0,
                  max_queue=100000)
    warm = sv.warmup()
    assert sorted(warm) == [1, 2, 4, 8]
    c0 = int(telemetry.registry()
             .counter("serving_recompiles_total").value())
    rng = np.random.RandomState(7)
    futs = [sv.submit({"x": rng.randn(int(rng.randint(1, 9)), 16)
                       .astype(np.float32)})
            for _ in range(1000)]
    for f in futs:
        f.result(timeout=120)
    sv.close()
    st = sv.stats()
    assert st["responses"] == 1000
    assert st["recompiles"] == 0
    assert int(telemetry.registry()
               .counter("serving_recompiles_total").value()) == c0


def test_padding_isolation_property_across_the_ladder(served):
    """A request's response is the same whether served alone or packed
    into ANY bucket alongside arbitrary other requests — padding rows
    and co-batched rows can never leak into real rows.  Alone and packed
    run in two bucket shapes, which are two XLA:CPU matmuls with their
    own accumulation order (observed apart by 1.5e-8), so the comparison
    is to rtol 1e-6; a leaked row of N(0, 1) input is off by orders of
    magnitude more."""
    infer, out, scope = served
    shared = fluid.Executor(fluid.CPUPlace())
    sv_alone = _serving(infer, out, scope, max_batch=8, max_wait_ms=0.0,
                        executor=shared)
    sv_pack = _serving(infer, out, scope, max_batch=8, max_wait_ms=200.0,
                       executor=shared)
    sv_alone.warmup()
    sv_pack.warmup()
    rng = np.random.RandomState(3)
    for bucket in sv_pack.buckets:
        for _ in range(3):
            r = int(rng.randint(1, bucket + 1))
            target = rng.randn(r, 16).astype(np.float32)
            alone, = sv_alone.infer({"x": target}, timeout=60)
            # exact-fill co-requests so the batch dispatches the moment
            # the last one lands (deterministic packing, no wait)
            fills, left = [], bucket - r
            while left:
                n = int(rng.randint(1, left + 1))
                fills.append(rng.randn(n, 16).astype(np.float32))
                left -= n
            futs = [sv_pack.submit({"x": f}) for f in fills[:len(fills)//2]]
            tfut = sv_pack.submit({"x": target})
            futs += [sv_pack.submit({"x": f})
                     for f in fills[len(fills)//2:]]
            packed, = tfut.result(timeout=60)
            for f in futs:
                f.result(timeout=60)
            np.testing.assert_allclose(alone, packed, rtol=1e-6, atol=1e-7)
    sv_alone.close()
    sv_pack.close()


def test_positional_requests_follow_feed_order(served):
    infer, out, scope = served
    sv = _serving(infer, out, scope, max_batch=4, max_wait_ms=1.0)
    sv.warmup()
    a = np.random.RandomState(0).randn(2, 16).astype(np.float32)
    by_name, = sv.infer({"x": a}, timeout=60)
    positional, = sv.infer([a], timeout=60)
    np.testing.assert_array_equal(by_name, positional)
    sv.close()


# ---------------------------------------------------------------------------
# Admission control / validation
# ---------------------------------------------------------------------------

def test_backpressure_and_oversize_rejects_are_counted(served):
    infer, out, scope = served
    r0 = int(telemetry.registry().counter("serving_rejects_total").value())
    sv = _serving(infer, out, scope, max_batch=4, max_queue=0)
    with pytest.raises(ServingRejectedError, match="queue full"):
        sv.submit({"x": np.zeros((1, 16), np.float32)})
    with pytest.raises(ServingRejectedError, match="largest bucket"):
        sv.submit({"x": np.zeros((99, 16), np.float32)})
    sv.close()
    with pytest.raises(ServingClosedError):
        sv.submit({"x": np.zeros((1, 16), np.float32)})
    assert sv.stats()["rejects"] == 3
    reg = telemetry.registry().counter("serving_rejects_total")
    assert int(reg.value()) == r0 + 3
    assert int(reg.value(reason="queue_full")) >= 1
    assert int(reg.value(reason="too_large")) >= 1
    assert int(reg.value(reason="closed")) >= 1


def test_request_validation_names_the_problem(served):
    infer, out, scope = served
    sv = _serving(infer, out, scope, max_batch=4)
    with pytest.raises(ServingError, match="missing feed 'x'"):
        sv.submit({"y": np.zeros((1, 16), np.float32)})
    with pytest.raises(ServingError, match=r"must be \[rows, 16\]"):
        sv.submit({"x": np.zeros((1, 7), np.float32)})
    with pytest.raises(ServingError, match="at least one row"):
        sv.submit({"x": np.zeros((0, 16), np.float32)})
    with pytest.raises(ServingError, match="positional request has 2"):
        sv.submit([np.zeros((1, 16), np.float32)] * 2)
    sv.close()


def test_non_batched_fetch_is_refused_at_warmup():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        scalar = layers.mean(layers.fc(x, size=3))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    sv = ServingExecutor(main.clone(for_test=True),
                         feed_specs={"x": ((4,), "float32")},
                         fetch_list=[scalar], scope=scope,
                         place=fluid.CPUPlace(), max_batch=2)
    with pytest.raises(ServingError, match="per-row"):
        sv.warmup()
    sv.close()


def test_dispatch_failure_answers_futures_instead_of_hanging(served):
    """A failing dispatch (device error, allocation failure during
    batch assembly) must surface on every affected request future —
    never an orphaned future a client waits on forever — and must not
    kill the serving loop for later requests."""
    infer, out, scope = served
    sv = _serving(infer, out, scope, max_batch=2, max_wait_ms=1.0)
    sv.warmup()
    real_run = sv._exe.run

    def boom(*args, **kwargs):
        raise RuntimeError("injected dispatch failure")

    sv._exe.run = boom
    f = sv.submit({"x": np.ones((1, 16), np.float32)})
    with pytest.raises(RuntimeError, match="injected dispatch"):
        f.result(timeout=30)
    # the loop survives: restore the executor and serve normally
    sv._exe.run = real_run
    got, = sv.infer({"x": np.ones((1, 16), np.float32)}, timeout=30)
    assert got.shape == (1, 10)
    assert telemetry.registry().gauge("serving_queue_depth").value() == 0
    assert int(telemetry.registry()
               .counter("serving_errors_total").value()) >= 1
    sv.close()


def test_cancelled_future_is_dropped_and_serving_continues(served):
    """submit() returns a real concurrent.futures.Future, so a client
    may cancel() it while queued.  The dispatch fence
    (set_running_or_notify_cancel) must drop the request — not compute
    it, and NOT let set_result raise InvalidStateError and kill the
    completion thread, which would hang every later fut.result()."""
    infer, out, scope = served
    c0 = int(telemetry.registry()
             .counter("serving_cancelled_total").value())
    sv = _serving(infer, out, scope, max_batch=4, max_wait_ms=5.0)
    sv.warmup()
    # hold the scheduler so all three requests are queued together and
    # the cancel deterministically lands before dispatch
    sv._ensure_threads = lambda: None
    fa = sv.submit({"x": np.full((1, 16), 1.0, np.float32)})
    fb = sv.submit({"x": np.full((1, 16), 2.0, np.float32)})
    fc = sv.submit({"x": np.full((1, 16), 3.0, np.float32)})
    assert fb.cancel()
    del sv._ensure_threads          # release the class method
    sv._ensure_threads()
    got_a, = fa.result(timeout=30)
    got_c, = fc.result(timeout=30)
    assert got_a.shape == (1, 10) and got_c.shape == (1, 10)
    assert fb.cancelled()
    # the loop survived the cancelled future: a fresh request round
    # trips through both threads
    got, = sv.infer({"x": np.ones((1, 16), np.float32)}, timeout=30)
    assert got.shape == (1, 10)
    st = sv.stats()
    assert st["cancelled"] == 1
    assert st["responses"] == 3     # the cancelled one is not a response
    assert int(telemetry.registry()
               .counter("serving_cancelled_total").value()) == c0 + 1
    assert telemetry.registry().gauge("serving_queue_depth").value() == 0
    sv.close()


def test_cancelled_future_in_failed_batch_does_not_crash_scheduler(served):
    """A cancelled future co-batched with a failing dispatch must not
    escalate into a scheduler crash: the live request gets the
    exception, the cancelled one stays cancelled, and serving
    continues."""
    infer, out, scope = served
    sv = _serving(infer, out, scope, max_batch=4, max_wait_ms=5.0)
    sv.warmup()
    real_run = sv._exe.run

    def boom(*args, **kwargs):
        raise RuntimeError("injected dispatch failure")

    sv._ensure_threads = lambda: None
    fa = sv.submit({"x": np.ones((1, 16), np.float32)})
    fb = sv.submit({"x": np.ones((1, 16), np.float32)})
    assert fb.cancel()
    sv._exe.run = boom
    del sv._ensure_threads
    sv._ensure_threads()
    with pytest.raises(RuntimeError, match="injected dispatch"):
        fa.result(timeout=30)
    assert fb.cancelled()
    sv._exe.run = real_run
    got, = sv.infer({"x": np.ones((1, 16), np.float32)}, timeout=30)
    assert got.shape == (1, 10)
    assert telemetry.registry().gauge("serving_queue_depth").value() == 0
    sv.close()


def test_warmup_after_traffic_raises(served):
    infer, out, scope = served
    sv = _serving(infer, out, scope, max_batch=2, max_wait_ms=1.0)
    sv.infer({"x": np.zeros((1, 16), np.float32)}, timeout=60)
    with pytest.raises(ServingError, match="before serving traffic"):
        sv.warmup()
    sv.close()


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------

def test_latency_split_and_step_events(served):
    """Queue-wait and compute land in their own histograms (one sample
    per request / per batch) and each batch leaves a kind="serving"
    step-event with the pinned schema."""
    infer, out, scope = served
    reg = telemetry.registry()
    qw0 = reg.histogram("serving_queue_wait_seconds").value()["count"]
    cp0 = reg.histogram("serving_compute_seconds").value()["count"]
    sv = _serving(infer, out, scope, max_batch=4, max_wait_ms=1.0)
    sv.warmup()
    futs = [sv.submit({"x": np.ones((1, 16), np.float32)})
            for _ in range(10)]
    for f in futs:
        f.result(timeout=60)
    sv.close()
    st = sv.stats()
    assert reg.histogram("serving_queue_wait_seconds").value()["count"] \
        == qw0 + 10
    assert reg.histogram("serving_compute_seconds").value()["count"] \
        == cp0 + st["batches"]
    assert reg.gauge("serving_queue_depth").value() == 0
    occ = reg.gauge("serving_batch_occupancy_frac").value()
    assert occ is not None and 0.0 < occ <= 1.0
    evs = [e for e in telemetry.step_events()
           if e.get("kind") == "serving"]
    assert len(evs) >= st["batches"]
    e = evs[-1]
    for key in ("ts_ns", "dur_ns", "bucket", "rows", "occupancy",
                "qwaits_us", "recompiled", "rejects_total"):
        assert key in e, key
    assert len(e["qwaits_us"]) == e["rows"] or e["rows"] >= 1


# ---------------------------------------------------------------------------
# Drain / shutdown (the scheduler never parks)
# ---------------------------------------------------------------------------

def test_close_timeout_raises_instead_of_faking_a_drain(served):
    """If the drain outlives close(timeout=), close() must raise — not
    zero the depth gauge and record a completed drain that never
    happened.  A later close() retries and completes."""
    infer, out, scope = served
    sv = _serving(infer, out, scope, max_batch=2, max_wait_ms=1.0)
    sv.warmup()
    gate = threading.Event()
    real_run = sv._exe.run

    def slow_run(*args, **kwargs):
        gate.wait(30)
        return real_run(*args, **kwargs)

    sv._exe.run = slow_run
    f = sv.submit({"x": np.ones((1, 16), np.float32)})
    with pytest.raises(ServingError, match="did not finish"):
        sv.close(timeout=0.2)
    gate.set()                  # un-wedge; the retry completes
    sv.close(timeout=60)
    got, = f.result(timeout=30)
    assert got.shape == (1, 10)
    assert sv.drained()


def test_request_stop_drains_scheduler_without_close(served):
    """A preemption stop request alone (no close() call) flips the
    scheduler into drain mode: every accepted request is answered, the
    thread exits on its own, and later submits are refused."""
    infer, out, scope = served
    sv = _serving(infer, out, scope, max_batch=8, max_wait_ms=500.0)
    sv.warmup()
    futs = [sv.submit({"x": np.ones((2, 16), np.float32)})
            for _ in range(5)]
    preemption.request_stop("test")
    deadline = time.time() + 30
    while not sv.drained() and time.time() < deadline:
        time.sleep(0.02)
    assert sv.drained()
    assert all(f.done() and f.exception() is None for f in futs)
    with pytest.raises(ServingClosedError):
        sv.submit({"x": np.ones((1, 16), np.float32)})
    sv.close()   # idempotent after a signal-driven drain
    names = [t.name for t in threading.enumerate()]
    assert "serving-scheduler" not in names
    assert "serving-completion" not in names


def test_sigterm_mid_load_exits_zero_all_answered(tmp_path):
    """The end-to-end serving preemption contract: SIGTERM to a live
    serving process → admission stops, accepted requests drain, metrics
    flush, exit 0, no orphaned serving threads."""
    script = tmp_path / "serve_preempt.py"
    jsonl = tmp_path / "events.jsonl"
    script.write_text(textwrap.dedent("""
        import sys, threading, time
        import numpy as np
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import flags, preemption, serving

        flags.set_flag("metrics_jsonl", sys.argv[1])
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \\
                fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            out = fluid.layers.softmax(fluid.layers.fc(x, size=4))
        infer = main.clone(for_test=True)
        preemption.install()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        sv = serving.ServingExecutor(
            infer, feed_specs={"x": ((8,), "float32")}, fetch_list=[out],
            place=fluid.CPUPlace(), max_batch=8, max_wait_ms=2.0,
            max_queue=100000)
        sv.warmup()
        print("STARTED", flush=True)
        accepted = []
        while not preemption.stop_requested():
            try:
                accepted.append(
                    sv.submit({"x": np.ones((1, 8), np.float32)}))
            except serving.ServingClosedError:
                break
            time.sleep(0.001)
        sv.close()
        bad = [f for f in accepted
               if not f.done() or f.exception() is not None]
        assert not bad, "unanswered/failed: %d" % len(bad)
        names = [t.name for t in threading.enumerate()]
        assert "serving-scheduler" not in names, names
        assert "serving-completion" not in names, names
        print("DRAINED answered=%d" % len(accepted), flush=True)
        sys.exit(0)
    """))
    proc = subprocess.Popen(
        [sys.executable, "-u", str(script), str(jsonl)], cwd=REPO,
        env=_child_env(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline()
        assert "STARTED" in line
        time.sleep(0.6)           # let some requests flow
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, (out, err)
    assert "DRAINED answered=" in out
    answered = int(out.split("DRAINED answered=")[1].split()[0])
    assert answered > 0
    # metrics flushed: the JSONL carries serving batch records and the
    # serving-drain lifecycle record
    import json
    events = [json.loads(ln) for ln in
              jsonl.read_text().splitlines() if ln.strip()]
    assert any(e.get("kind") == "serving" for e in events)
    drains = [e for e in events if e.get("kind") == "preemption"
              and e.get("source") == "serving"]
    assert drains and drains[-1]["step"] == answered


# ---------------------------------------------------------------------------
# save_inference_model round trip (the feed-order contract)
# ---------------------------------------------------------------------------

def _two_feed_model():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        z = layers.data(name="zz", shape=[4], dtype="float32")
        a = layers.data(name="aa", shape=[3], dtype="float32")
        out = layers.elementwise_add(layers.fc(z, size=3), a)
    return main, startup, out


@pytest.mark.parametrize("params_filename", [None, "params"])
def test_inference_model_round_trip_serves_in_manifest_order(
        tmp_path, params_filename):
    """save_inference_model → load_inference_model → ServingExecutor:
    the loaded executor's feed order is the SAVED order (not sorted,
    not a col-attr reconstruction), positional requests follow it, and
    responses match the source program bit-for-bit."""
    main, startup, out = _two_feed_model()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    model_dir = str(tmp_path / "model")
    with fluid.scope_guard(scope):
        exe.run(startup)
        # deliberately NOT alphabetical: zz before aa
        fluid.io.save_inference_model(model_dir, ["zz", "aa"], [out],
                                      exe, main,
                                      params_filename=params_filename)
        rng = np.random.RandomState(0)
        zv = rng.randn(2, 4).astype(np.float32)
        av = rng.randn(2, 3).astype(np.float32)
        want, = exe.run(fluid.io.prune_program(main, ["zz", "aa"],
                                               [out.name]),
                        feed={"zz": zv, "aa": av}, fetch_list=[out.name])
        want = np.asarray(want)
    sv = ServingExecutor.from_inference_model(
        model_dir, place=fluid.CPUPlace(), max_batch=4, max_wait_ms=1.0)
    assert sv.feed_names == ["zz", "aa"]
    sv.warmup()
    got, = sv.infer([zv, av], timeout=60)    # positional: saved order
    np.testing.assert_array_equal(got, want)
    by_name, = sv.infer({"aa": av, "zz": zv}, timeout=60)
    np.testing.assert_array_equal(by_name, want)
    sv.close()


def test_doctored_manifest_feed_order_fails_loudly(tmp_path):
    """An order manifest naming a different feed set than the program is
    a mixed-artifact model dir — the loader must refuse, not guess."""
    import json

    main, startup, out = _two_feed_model()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    model_dir = str(tmp_path / "model")
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["zz", "aa"], [out],
                                      exe, main)
    path = os.path.join(model_dir, "__params_order__")
    with open(path) as f:
        manifest = json.load(f)
    manifest["feed_order"] = ["zz", "bogus"]
    with open(path, "w") as f:
        json.dump(manifest, f)
    fresh = fluid.Scope()
    with fluid.scope_guard(fresh):
        with pytest.raises(ValueError, match="mixes artifacts"):
            fluid.io.load_inference_model(model_dir,
                                          fluid.Executor(fluid.CPUPlace()))


# ---------------------------------------------------------------------------
# Multi-QPS soak (the bench acceptance, CI-host measurable)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serving_soak_beats_naive_baseline_2x():
    """bench.py --serving at several QPS levels: continuous batching
    must deliver >= 2x the naive one-request-per-dispatch throughput at
    saturation, with zero steady-state recompiles and the occupancy
    fraction reported in the same artifact."""
    import bench

    out = bench.bench_serving(requests=400,
                              qps_levels=(1000.0, 1e6))
    assert out["zero_steady_state_recompiles"] is True
    assert out["speedup_vs_naive"] >= 2.0, out
    assert 0.0 < out["batch_occupancy_frac"] <= 1.0
    assert out["naive"]["occupancy"] == 1.0   # bucket ladder (1,)
