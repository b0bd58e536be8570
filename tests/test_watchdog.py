"""Training watchdog (ISSUE 15): hang detection, stack-dump-and-abort,
phase-aware grace, launcher heartbeat liveness, and the observability
satellites around them.

Fast (tier-1) coverage: the in-process detection/extension semantics,
the subprocess hang kill-matrix (a worker wedged at the dispatch /
feed-producer / checkpoint-barrier / collective-consensus boundary is
detected within the timeout, dumps all-thread stacks to stderr, and
exits with the dedicated ``EXIT_HANG`` code — distinct from every
crash code), the launcher's heartbeat-stale detection restarting a
plain-pack rank whose watchdog is observe-only (self-abort
suppressed), storage-retry grace preventing false positives,
watchdog-off bit-exact zero overhead, /healthz 503 staleness, and the
metrics-report hang rows.  ISSUE 18 adds the async-save interplay:
the background uploader's storage-retry backoff is invisible to an
armed watchdog (counted, committed, but no deadline extension and no
progress stamps from the suppressed thread), and the shared 2-process
pack's asyncpod segment proves the whole async protocol runs hang-free
under an armed watchdog.

The acceptance run is a REAL 2-process gloo pack (skip-guarded like
tests/test_multihost.py): one rank hangs mid-step after the pod save,
its watchdog aborts with ``EXIT_HANG``, the launcher identifies the
hung rank in its post-mortem, tears the pack down, relaunches the
survivor world of one under ``--max_restarts``/``--elastic_min_nproc``,
which reshard-restores 2→1 and continues on the uninterrupted
control's trajectory."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import distributed as dist
from paddle_tpu.fluid import flags, telemetry, watchdog
from paddle_tpu.fluid.checkpoint import (CheckpointManager,
                                         checkpoint_metadata,
                                         latest_checkpoint)
from paddle_tpu.fluid.storage import MixedProtocolReader, ObjectStoreStorage
from paddle_tpu.distributed.launch import HANG_EXIT_CODE

import faultinject as fi
import dist_multihost_worker as worker_mod
import mh_harness as mh

REPO = mh.REPO
_WORKER = mh.WORKER

@pytest.fixture(autouse=True)
def _disarmed():
    """Every test starts and ends disarmed — a leaked watchdog thread
    (or progress-stamp state) must never bleed into the rest of the
    tier-1 suite."""
    watchdog.disarm()
    yield
    watchdog.disarm()


def _hangs():
    return telemetry.registry().counter("watchdog_hangs_total").value()


def _build_tiny(seed=3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(x, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
    return main, startup, loss


def _feed():
    rng = np.random.RandomState(0)
    xs = rng.randn(8, 8).astype(np.float32)
    return {"x": xs, "y": (xs @ rng.randn(8, 1)).astype(np.float32)}


# ---------------------------------------------------------------------------
# Core semantics
# ---------------------------------------------------------------------------

def test_exit_code_is_mirrored_and_distinct():
    """launch.py supervises without importing jax, so it mirrors the
    abort code — the two constants must stay equal, and clear of the
    codes the runtime already produces (0 drain, 1/2 crashes, 128+n
    signal deaths the shell reports)."""
    assert HANG_EXIT_CODE == watchdog.EXIT_HANG == 117


def test_detection_record_and_recovery_in_observe_mode():
    """Observe-only mode (FLAGS_watchdog_abort=0): a stall past the
    deadline bumps the counter ONCE, appends a ``kind="hang"``
    lifecycle record naming the last phase, and flips health unhealthy;
    resumed progress restores health without double-counting."""
    h0 = _hangs()
    assert watchdog.arm(timeout_s=0.3, abort=False) is True
    telemetry.record_progress("dispatch")
    time.sleep(0.9)
    assert _hangs() - h0 == 1
    h = watchdog.health()
    assert h["healthy"] is False and h["stalled"] is True
    assert h["phase"] == "dispatch"
    rec = [e for e in telemetry.step_events()
           if e.get("kind") == "hang"][-1]
    assert rec["phase"] == "dispatch" and rec["aborting"] is False
    assert rec["age_s"] >= 0.3 and rec["timeout_s"] == 0.3
    # a released hang: progress resumes, health recovers, no re-count
    # (the wait stays under the timeout — only the poll must observe)
    telemetry.record_progress("dispatch")
    time.sleep(0.15)
    assert watchdog.health()["healthy"] is True
    assert _hangs() - h0 == 1


def test_extend_deadline_masks_slow_phase_and_restarts_clock():
    assert watchdog.arm(timeout_s=0.3, abort=False)
    h0 = _hangs()
    with watchdog.extend_deadline("storage_retry", 5.0):
        time.sleep(0.7)   # well past the bare timeout
        assert watchdog.health()["healthy"] is True
        assert watchdog.extension_s() == 5.0
    # exit stamped progress: the age clock restarted
    assert watchdog.extension_s() == 0.0
    assert watchdog.health()["healthy"] is True
    assert _hangs() == h0


def test_storage_retry_backoff_does_not_false_positive():
    """The satellite pin: an injected transient storage failure whose
    retry backoff sleeps LONGER than the watchdog timeout must not be
    called a hang — storage.py wraps each backoff in the phase grace."""
    assert watchdog.arm(timeout_s=0.3, abort=False)
    h0 = _hangs()
    # neutralize the blanket checkpoint grace so THIS test isolates
    # the storage-retry extension (storage.py's backoff wrapper)
    flags.set_flag("watchdog_checkpoint_grace_s", 0.0)
    main, startup, _loss = _build_tiny()
    scope = fluid.Scope()
    try:
        with fluid.scope_guard(scope):
            fluid.Executor(fluid.CPUPlace()).run(startup)
            store = ObjectStoreStorage(retries=2, backoff_s=0.4)
            mgr = CheckpointManager("/tmp/_wd_retry_%d" % os.getpid(),
                                    scope=scope, main_program=main,
                                    async_save=False, storage=store)
            import shutil
            shutil.rmtree(mgr.dirname, ignore_errors=True)
            os.makedirs(mgr.dirname, exist_ok=True)
            with fi.fail_n_times("manifest", 2):
                path = mgr.save()       # sleeps 0.4 + 0.8 while retrying
            assert latest_checkpoint(mgr.dirname, storage=store) == path
            shutil.rmtree(mgr.dirname, ignore_errors=True)
    finally:
        flags.set_flag("watchdog_checkpoint_grace_s",
                       flags._DEFS["watchdog_checkpoint_grace_s"])
    assert _hangs() == h0, "slow retry was miscalled a hang"


def test_async_save_storage_retry_backoff_invisible_to_watchdog(tmp_path):
    """ISSUE 18 satellite: the SAME transient-failure retry, but inside
    the BACKGROUND uploader of an async save while the watchdog is
    armed.  The retries are counted and the save still commits — and
    the progress-suppressed uploader earns NO deadline extension and
    stamps no progress, so background I/O can neither mask a genuine
    training stall nor be miscalled as one (the foreground keeps
    stamping its own liveness)."""
    assert watchdog.arm(timeout_s=0.6, abort=False)
    h0 = _hangs()
    r0 = telemetry.registry().counter("storage_retry_total").value()
    main, startup, _loss = _build_tiny()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        store = ObjectStoreStorage(retries=2, backoff_s=0.3)
        mgr = CheckpointManager(str(tmp_path / "ck"), scope=scope,
                                main_program=main, async_save=True,
                                storage=store)
        telemetry.record_progress("dispatch")
        with fi.fail_n_times("manifest", 2) as seen:
            path = mgr.save()        # returns before the upload runs
            assert mgr._thread is not None
            while mgr._thread is not None and mgr._thread.is_alive():
                # backoff sleeps happen on the suppressed uploader: no
                # watchdog grace may leak to the process while it waits
                assert watchdog.extension_s() == 0.0
                telemetry.record_progress("dispatch")
                time.sleep(0.05)
        mgr.wait()
        assert seen[0] == 2
        assert telemetry.registry().counter(
            "storage_retry_total").value() - r0 == 2
        assert latest_checkpoint(mgr.dirname, storage=store) == path
    assert _hangs() == h0, \
        "background retry backoff was miscalled a hang"


def test_heartbeat_touched_while_healthy_frozen_once_stalled(tmp_path):
    hb = str(tmp_path / "hb" / "heartbeat.0")
    assert watchdog.arm(timeout_s=0.5, abort=False, heartbeat_file=hb)
    telemetry.record_progress("dispatch")
    time.sleep(0.3)
    assert os.path.exists(hb)
    m0 = os.path.getmtime(hb)
    telemetry.record_progress("dispatch")
    time.sleep(0.3)
    assert os.path.getmtime(hb) >= m0       # still being touched
    time.sleep(1.0)                          # now stalled
    m1 = os.path.getmtime(hb)
    time.sleep(0.5)
    # observe-only + stalled: touches STOP so the launcher's staleness
    # clock runs — the "self-abort suppressed" liveness handoff
    assert os.path.getmtime(hb) == m1
    watchdog.disarm()
    assert not os.path.exists(hb)            # disarm cleans up


def test_watchdog_off_is_bit_exact_zero_overhead():
    """FLAGS_watchdog_timeout_s=0 (default): arm() is a no-op, nothing
    stamps, step events carry no watchdog field, no watchdog thread
    runs — and an armed run's losses are bit-identical to off (the
    hot path is observed, never perturbed)."""
    assert float(flags.get_flag("watchdog_timeout_s")) == 0.0
    assert watchdog.arm() is False
    telemetry.record_progress("dispatch")
    assert telemetry.last_progress() == (None, None)
    assert telemetry.last_progress_age_s() is None
    main, startup, loss = _build_tiny()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = _feed()

    def run_n(n):
        out = []
        for _ in range(n):
            v = exe.run(main, feed=feed, fetch_list=[loss])[0]
            out.append(float(np.ravel(np.asarray(v))[0]))
        return out

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        off = run_n(200)
    ev = telemetry.step_events()[-1]
    assert "last_progress_age_s" not in ev
    assert not any(t.name == "fluid-watchdog"
                   for t in threading.enumerate())
    # armed (healthy): same trajectory, bit for bit, zero hang events
    h0 = _hangs()
    hang_recs0 = sum(1 for e in telemetry.step_events()
                     if e.get("kind") == "hang")
    assert watchdog.arm(timeout_s=30.0, abort=False) is True
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup)
        on = run_n(200)
    assert on == off
    assert _hangs() == h0
    assert sum(1 for e in telemetry.step_events()
               if e.get("kind") == "hang") == hang_recs0
    ev = telemetry.step_events()[-1]
    assert ev.get("last_progress_age_s") is not None
    assert telemetry.last_progress()[1] == "dispatch"


def test_progress_stamped_at_runtime_boundaries():
    """The tentpole's stamp points: dispatch, checkpoint phases,
    consensus, barrier — observed via the progress hook."""
    phases = []
    assert watchdog.arm(timeout_s=30.0, abort=False)
    prev = telemetry.set_progress_hook(phases.append)
    try:
        main, startup, loss = _build_tiny()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run(main, feed=_feed(), fetch_list=[loss])
            mgr = CheckpointManager("/tmp/_wd_stamps_%d" % os.getpid(),
                                    scope=scope, main_program=main,
                                    async_save=False)
            mgr.save()
        dist.consensus_flags(False)
        dist.barrier("probe")
    finally:
        telemetry.set_progress_hook(prev)
        import shutil
        shutil.rmtree("/tmp/_wd_stamps_%d" % os.getpid(),
                      ignore_errors=True)
    assert "dispatch" in phases
    assert "compile" in phases          # fresh-executable grace
    assert "checkpoint" in phases and "checkpoint_save" in phases
    assert "consensus" in phases
    assert any(p.startswith("barrier:") for p in phases)


def test_hang_at_is_releasable():
    """The faultinject satellite: hang_at parks the thread reaching a
    named boundary and releases on demand (kill-matrix style, no
    ad-hoc sleeps)."""
    done = []
    with fi.hang_at("checkpoint") as (reached, release):
        def save():
            telemetry.record_progress("checkpoint")
            done.append(True)

        t = threading.Thread(target=save, daemon=True)
        t.start()
        assert reached.wait(5)
        assert not done                 # parked at the boundary
        release.set()
        t.join(5)
        assert done


# ---------------------------------------------------------------------------
# Subprocess hang kill-matrix: wedge at a boundary -> stack dump +
# EXIT_HANG within the timeout
# ---------------------------------------------------------------------------

_MATRIX_SCRIPT = r"""
import os, sys, threading, time
sys.path.insert(0, %(repo)r)
sys.path.insert(0, os.path.join(%(repo)r, "tests"))
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import flags, telemetry, watchdog
from paddle_tpu.fluid import distributed as dist
import faultinject as fi

flags.set_flag("metrics_jsonl", %(jsonl)r)
boundary = %(boundary)r

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    with fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, size=1))
        fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(startup)
feed = {"x": np.ones((4, 8), np.float32)}
exe.run(main, feed=feed, fetch_list=[loss])   # warm compile

watchdog.arm(timeout_s=1.0)
assert watchdog.is_armed()

if boundary == "dispatch":
    with fi.hang_at("dispatch", permanent=True):
        for _ in range(100):
            exe.run(main, feed=feed, fetch_list=[loss])
elif boundary == "feed_ring":
    from paddle_tpu.fluid.reader import FeedRing
    def gen():
        for i in range(100):
            yield {"x": np.ones((4, 8), np.float32)}
    with fi.hang_at("feed_ring", nth=2, permanent=True):
        ring = FeedRing(lambda d: d, gen(), depth=1)
        for d in ring:
            time.sleep(0.01)
elif boundary == "ckpt_barrier":
    # the pod-save barrier whose peer never arrives
    from paddle_tpu.fluid.checkpoint import CheckpointManager
    from paddle_tpu.fluid.storage import ObjectStoreStorage
    flags.set_flag("watchdog_checkpoint_grace_s", 0.5)
    mgr = CheckpointManager(%(ckdir)r, storage=ObjectStoreStorage(),
                            scope=fluid.global_scope(),
                            main_program=main, process_index=0,
                            process_count=2, async_save=False,
                            barrier=lambda name: threading.Event().wait())
    mgr.save()
elif boundary == "consensus":
    with fi.hang_at("consensus", permanent=True):
        dist.consensus_flags(False)
print("UNREACHABLE: boundary %%s did not hang" %% boundary, flush=True)
sys.exit(0)
"""


def test_hang_kill_matrix_subprocess(tmp_path):
    """A worker wedged at each park-prone boundary — dispatch /
    feed-producer / checkpoint-barrier / collective-consensus: detected
    within the timeout (+ phase grace for the checkpoint barrier),
    all-thread stacks dumped to stderr, the ``kind="hang"`` record
    durable in the JSONL naming the phase, and the exit code is
    EXIT_HANG — distinct from every crash exit.  The four wedged
    workers run CONCURRENTLY (each is dominated by interpreter startup
    + its own timeout; serializing them would quadruple the wall)."""
    boundaries = ["dispatch", "feed_ring", "ckpt_barrier", "consensus"]
    procs = {}
    t0 = time.monotonic()
    for boundary in boundaries:
        jsonl = str(tmp_path / ("%s.jsonl" % boundary))
        script = _MATRIX_SCRIPT % {
            "repo": REPO, "jsonl": jsonl, "boundary": boundary,
            "ckdir": str(tmp_path / ("ck_%s" % boundary))}
        procs[boundary] = (subprocess.Popen(
            [sys.executable, "-c", script], cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True), jsonl)
    try:
        for boundary in boundaries:
            proc, jsonl = procs[boundary]
            out, err = proc.communicate(timeout=180)
            assert proc.returncode == watchdog.EXIT_HANG, \
                (boundary, proc.returncode, out, err)
            assert "UNREACHABLE" not in out, (boundary, out)
            assert "[watchdog] HANG" in err, (boundary, err)
            # the all-thread stack dump names parked frames
            assert "Current thread" in err and 'File "' in err
            recs = [json.loads(line) for line in open(jsonl)]
            hang = [r for r in recs if r.get("kind") == "hang"]
            assert len(hang) == 1, (boundary, recs)
            assert hang[0]["phase"].startswith(boundary), (boundary,
                                                          hang)
            assert hang[0]["aborting"] is True
        # detected promptly — nowhere near parked-forever territory
        assert time.monotonic() - t0 < 120
    finally:
        for proc, _jsonl in procs.values():
            if proc.poll() is None:
                proc.kill()


# ---------------------------------------------------------------------------
# Launcher heartbeat liveness / exit-117 classification: moved to
# the scenario table in test_launch_relaunch_matrix.py
# ---------------------------------------------------------------------------


def test_launch_heartbeat_timeout_validation():
    from paddle_tpu.distributed.launch import parse_args
    with pytest.raises(SystemExit):
        parse_args(["--heartbeat_timeout", "-1", "x.py"])


# ---------------------------------------------------------------------------
# Observability satellites
# ---------------------------------------------------------------------------

def test_healthz_503_on_staleness_then_recovers():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from metrics_server import start_metrics_server, healthz_body
    finally:
        sys.path.pop(0)
    srv = start_metrics_server(port=0)
    url = "http://%s:%d/healthz" % (srv.host, srv.port)
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            assert r.status == 200 and r.read().decode() == "ok\n"
        assert watchdog.arm(timeout_s=0.3, abort=False)
        telemetry.record_progress("dispatch")
        time.sleep(0.8)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=10)
        assert ei.value.code == 503
        body = ei.value.read().decode()
        assert "unhealthy" in body and "dispatch" in body
        # progress resumes -> healthy again (wait under the timeout,
        # long enough for a poll tick to clear the stall verdict)
        telemetry.record_progress("dispatch")
        time.sleep(0.15)
        code, body = healthz_body()
        assert code == 200 and body == "ok\n"
    finally:
        srv.close()


def test_metrics_report_hang_rows_and_progress_age_column():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_report
    finally:
        sys.path.pop(0)
    events = [
        {"k": 1, "dur_ns": 50000, "plan_hit": True, "pidx": 0,
         "last_progress_age_s": 0.004},
        {"k": 1, "dur_ns": 50000, "plan_hit": True, "pidx": 1,
         "last_progress_age_s": 0.002},
        {"kind": "hang", "phase": "dispatch", "age_s": 5.2,
         "timeout_s": 5.0, "pidx": 1},
        {"kind": "hang", "phase": "ckpt_barrier:begin", "age_s": 6.0,
         "timeout_s": 5.0, "pidx": 0},
    ]
    rows = metrics_report.summarize(events)
    life = rows["lifecycle"]
    assert life["hangs"] == 2
    assert life["last_hang_phase"] == "ckpt_barrier:begin"
    assert life["hang_detect_p50_s"] == 5.2
    procs = rows["processes"]["by_process"]
    # the hang record's staleness outranks the step events' column
    assert procs["1"]["last_progress_age_s"] == 5.2
    assert procs["0"]["last_progress_age_s"] == 6.0
    text = metrics_report.format_report(rows)
    assert "hangs: 2 detected by the watchdog" in text
    assert "last phase ckpt_barrier:begin" in text
    assert "last_progress_age_s" in text


# ---------------------------------------------------------------------------
# THE acceptance run: 2-process gloo pack, one rank hangs mid-step,
# watchdog abort -> launcher relaunch -> reshard-restore continues
# ---------------------------------------------------------------------------

def _child_env(out_dir, jsonl):
    return mh.child_env(out_dir, "elastic", {
        "MH_ELASTIC_PHASE": "shrink",
        "MH_ELASTIC_CRASH": "hang",
        "FLAGS_metrics_jsonl": jsonl,
    })


def test_pack_async_save_under_armed_watchdog(pack):
    """ISSUE 18 × ISSUE 15: the shared pack's asyncpod segment ran its
    save + commit-wait under a 30s-armed watchdog on both ranks — no
    hang was recorded, no collective was issued by the async protocol,
    and the save call returned well before the (deliberately parked)
    upload completed."""
    ranks, _out = pack
    for out in ranks:
        seg = out["asyncpod"]
        assert seg["hang_delta"] == 0
        assert seg["collective_delta"] == 0
        assert seg["save_returned_s"] < seg["total_s"]


@pytest.mark.slow
def test_two_process_hung_rank_detected_relaunched_continues(tmp_path):
    """ISSUE 15 acceptance: a real 2-process gloo pack trains 3 steps
    of the WUS program and saves a degree-2 pod checkpoint; then the
    last rank WEDGES mid-step (no exit — the PR 14 machinery alone
    would wait forever).  Its in-process watchdog detects the stall
    within FLAGS_watchdog_timeout_s, dumps stacks, and aborts with
    EXIT_HANG; the launcher's post-mortem names the hung rank, tears
    the pack down, and relaunches the survivor world of one
    (``--max_restarts 1 --elastic_min_nproc 1``) which
    reshard-restores 2→1 and probes two degree-1 steps on the
    uninterrupted control's trajectory."""
    out = tmp_path / "hang"
    os.makedirs(out)
    port = 29600 + (os.getpid() % 1200)
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--coordinator", "--nproc_per_node", "2",
         "--started_port", str(port), "--log_dir", str(out),
         "--max_restarts", "1", "--elastic_min_nproc", "1",
         "--grace_period", "10",
         _WORKER],
        env=_child_env(out, str(out / "run.jsonl")),
        cwd=REPO, timeout=300, capture_output=True, text=True)
    logs = ""
    for r in (0, 1):
        lp = os.path.join(str(out), "workerlog.%d" % r)
        if os.path.exists(lp):
            logs += "---- rank %d ----\n%s" % (r, open(lp).read())
    assert proc.returncode == 0, (proc.stdout, proc.stderr, logs)
    # the launcher named the root cause: rank 1 HUNG via watchdog
    # abort, rank 0 was not blamed
    assert "rank 1 HUNG (watchdog self-abort, exit 117)" \
        in proc.stderr, proc.stderr
    assert "relaunching pack" in proc.stderr
    assert "world 2 -> 1" in proc.stderr
    # the hung child really dumped its stacks before aborting
    assert "[watchdog] HANG" in logs, logs
    # the hang lifecycle record is durable in rank 1's JSONL stream
    hang_recs = []
    for suffix in (".p0", ".p1", ""):
        p = str(out / "run.jsonl") + suffix
        if os.path.exists(p):
            hang_recs += [json.loads(line) for line in open(p)
                          if '"hang"' in line]
    assert hang_recs and hang_recs[0]["pidx"] == 1, hang_recs
    # the survivor reshard-restored 2->1 and continued
    with open(os.path.join(str(out), "out_r0.json")) as f:
        shrink = json.load(f)
    assert shrink["phase"] == "shrink1" and shrink["world"] == 1
    rst = shrink["restored"]
    assert rst["resized"] is True and rst["resharded"] is True
    assert (rst["old_world"], rst["new_world"]) == (2, 1)
    # the pod checkpoint the survivor restored was the full 2-process
    # degree-2 artifact
    pod = checkpoint_metadata(
        latest_checkpoint(os.path.join(str(out), "ckpts"),
                          storage=MixedProtocolReader()))
    assert pod["multihost"] is True and pod["process_count"] == 2
    # bit-continuation: the degree-1 probe tracks the uninterrupted
    # single-process control of the SAME nranks=2 program
    feeds = worker_mod.make_feeds()
    main_p, startup_p, loss = worker_mod.build_program(wus=True,
                                                      rank=0, nranks=2)
    control = []
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup_p)
        for f in feeds[:5]:
            v = exe.run(main_p, feed=f, fetch_list=[loss])[0]
            control.append(np.ravel(np.asarray(v)))
    probe = np.asarray(shrink["probe"]).ravel()
    np.testing.assert_allclose(
        probe, [np.mean(control[3]), np.mean(control[4])],
        rtol=1e-4, atol=1e-5)
