"""Pod-scale multi-host SPMD runtime (ISSUE 13): genuine 2-process
jax.distributed CPU runs via ``distributed/launch.py --coordinator``
(gloo collectives, one device per process), plus the single-process
simulated-world coverage of the multi-host checkpoint commit protocol.

Acceptance pins:
- dp loss parity BIT-EXACT vs a single-process run of the same
  transpiled program at K=1 and K=4 windows;
- the explicit-collective path dispatches through the shared
  ``_DispatchPlan`` cache (plan hit-rate ≈ 1.0 steady-state, pinned);
- int8 allreduce byte accounting summed across processes;
- weight-update-sharding state round-trips through a multi-host
  checkpoint (per-process shard files, chief-merged manifest);
- SIGTERM to ONE process drains BOTH cleanly (exit 0, no orphans);
- the marker object is the only visibility point: a checkpoint whose
  merged manifest exists while a sibling process's shards are still
  uploading is never selected.

ISSUE 18 adds the COLLECTIVE-FREE async pod save: ``save()`` returns
after the device→host snapshot, the upload + chief-polls-storage
commit run on a background thread, rank death mid-save costs one
abandoned prefix — pinned here in-process (simulated worlds, fault
injection at every write boundary) and on the shared real pack (the
``asyncpod`` section + the slow chief-kill launcher run).

Each launcher test costs a real 2-process rendezvous (~15-30 s); they
skip cleanly where the jax build has no CPU cross-process collective
transport (gloo).  The launch harness lives in tests/mh_harness.py and
the combined pack is the SESSION-scoped ``pack`` fixture in
conftest.py, shared with test_elastic/test_watchdog.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import distributed as dist
from paddle_tpu.fluid import flags
from paddle_tpu.fluid.checkpoint import (CheckpointManager,
                                         latest_checkpoint,
                                         read_manifest,
                                         validate_checkpoint,
                                         snapshot_addressable)
from paddle_tpu.fluid.storage import MARKER_NAME, ObjectStoreStorage

import faultinject as fi
import mh_harness as mh
import dist_multihost_worker as worker_mod

REPO = mh.REPO

# ---------------------------------------------------------------------------
# Single-process oracles (same builders as the worker — no drift)
# ---------------------------------------------------------------------------

def _single_process_run(precision="fp32", steps=8, windows=2):
    """The SAME transpiled program on ONE process (nranks=2 over two of
    this process's virtual devices), same feeds: per-step fetches carry
    one row per dp shard — row r is what rank r's localized fetch
    returns in the 2-process run, so bit-exactness is row-for-row."""
    feeds = worker_mod.make_feeds()
    main_p, startup_p, loss = worker_mod.build_program(
        precision=precision, rank=0, nranks=2)
    losses, wlosses = [], []
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup_p)
        for f in feeds[:steps]:
            lv = exe.run(main_p, feed=f, fetch_list=[loss])[0]
            losses.append(np.ravel(np.asarray(lv)))
        for w in range(windows):
            window = feeds[steps + 4 * w:steps + 4 * (w + 1)]
            out = exe.run_window(main_p, feed=worker_mod.stack(window),
                                 fetch_list=[loss], steps_per_run=4,
                                 return_numpy=False)
            wlosses.append(np.asarray(out[0]))   # [K, 2] rows per shard
    return losses, wlosses


# ---------------------------------------------------------------------------
# 2-process launcher suites — parity/int8/wus/asyncpod share the
# SESSION-scoped ``pack`` fixture (conftest.py); the SIGTERM consensus
# test needs its own signal-able pack
# ---------------------------------------------------------------------------

def test_two_process_dp_parity_bit_exact_k1_and_k4(pack):
    """THE acceptance pin: a real 2-process jax.distributed CPU run
    trains the dp model to BIT-EXACT loss parity with the
    single-process run of the same program — at K=1 AND inside fused
    K=4 windows — and its dispatches go through the shared
    _DispatchPlan cache (hit-rate ≈ 1.0 steady-state, pinned)."""
    ranks, _dir = pack
    single_losses, single_wlosses = _single_process_run()
    for r, rout in enumerate(ranks):
        out = rout["parity"]
        # K=1: rank r's local loss == dp-shard r's row, every step
        mine = np.asarray(out["losses"]).ravel()
        want = np.asarray([l[r] for l in single_losses])
        np.testing.assert_array_equal(mine, want)
        # K=4 windows: stacked [K] per-step losses, still bit-exact
        for w, wl in enumerate(out["wlosses"]):
            np.testing.assert_array_equal(
                np.asarray(wl), np.asarray(single_wlosses[w][:, r]))
        # dispatch-plan accounting, pinned: startup + step + window
        # executables each miss once, every later dispatch hits —
        # 7 hits from the 8-step K=1 stream + 1 from the second window
        # (steady-state hit rate 1.0; the old per-call executable path
        # is gone)
        assert out["compiles"] == 3, out
        assert out["plan_hits"] == 8, out
        assert out["prometheus_has_process_label"], out


def test_two_process_compiled_cost_and_memory_introspection(pack):
    """Device-cost ledger satellite: ``compiled_cost``/
    ``compiled_memory`` work on the MULTIHOST ``_lowered_executable``
    path (global avals, jax.distributed live) — positive per-step FLOP
    and argument/temp byte figures on every rank, and identical across
    ranks because each rank lowered the same global executable."""
    ranks, _dir = pack
    figures = []
    for rout in ranks:
        out = rout["parity"]
        assert out["hlo_flops"] > 0, out
        assert out["hlo_argument_bytes"] > 0, out
        assert out["hlo_temp_bytes"] >= 0, out
        assert out["hlo_bytes_accessed"] > 0, out
        figures.append((out["hlo_flops"], out["hlo_bytes_accessed"],
                        out["hlo_argument_bytes"],
                        out["hlo_temp_bytes"]))
    assert figures[0] == figures[1], figures


def test_two_process_metrics_jsonl_streams_merge_with_skew(pack):
    """Telemetry satellite: each process writes its own
    ``<path>.p<idx>`` JSONL stream (no interleaving), records carry
    ``pidx``, and tools/metrics_report.py merges the streams into
    per-process p50/p99 rows plus a skew figure."""
    _ranks, out_dir = pack
    base = str(out_dir / "run.jsonl")
    assert not os.path.exists(base)          # only suffixed streams
    assert os.path.exists(base + ".p0") and os.path.exists(base + ".p1")

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_report
    finally:
        sys.path.pop(0)
    events = metrics_report.load_all_events([base])
    assert events and all("pidx" in ev for ev in events)
    rows = metrics_report.summarize(events)
    procs = rows["processes"]
    assert procs["count"] == 2
    assert set(procs["by_process"]) == {"0", "1"}
    for pp in procs["by_process"].values():
        assert pp["dispatches"] > 0
        assert pp["p99_us_per_step"] >= pp["p50_us_per_step"] > 0
    assert procs["p50_skew"] is None or procs["p50_skew"] >= 1.0
    # the merged table renders the per-process section
    text = metrics_report.format_report(rows)
    assert "p50 skew" in text


def _single_process_int8_step_bytes(steps=6):
    """collective_bytes_total delta across exactly ``steps`` K=1
    dispatches of the int8 program on one process (startup's broadcast
    excluded — it moves bytes too)."""
    from paddle_tpu.fluid import telemetry

    feeds = worker_mod.make_feeds()
    main_p, startup_p, loss = worker_mod.build_program(
        precision="int8", rank=0, nranks=2)
    m = telemetry.counter("collective_bytes_total")
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup_p)
        b0 = int(m.value())
        for f in feeds[:steps]:
            exe.run(main_p, feed=f, fetch_list=[loss])
        return int(m.value()) - b0


def test_two_process_int8_allreduce_bytes_sum_across_processes(pack):
    """PR 10's quantized allreduce on real inter-process wire: losses
    identical shard-for-shard to the single-process int8 run, and the
    byte accounting — per-process counters — sums across processes to
    nproc × the single-process figure, with the K=4 window moving
    exactly 4 more steps of bytes."""
    from paddle_tpu.fluid import telemetry

    ranks, _dir = pack
    single_losses, _ = _single_process_run(precision="int8", steps=6,
                                           windows=0)
    for r, rout in enumerate(ranks):
        out = rout["int8"]
        mine = np.asarray(out["losses"]).ravel()
        np.testing.assert_array_equal(
            mine, np.asarray([l[r] for l in single_losses]))
    # single-process control for the byte accounting (delta measured
    # across the same 6 training steps, startup broadcast excluded)
    control = _single_process_int8_step_bytes()
    assert control > 0
    for rout in ranks:
        out = rout["int8"]
        assert out["comm_bytes_k1"] == control, (out, control)
        assert out["int8_bytes"] > 0
        # the K=4 window moved exactly 4 more steps of wire bytes
        per_step = out["comm_bytes_k1"] // 6
        assert out["comm_bytes_k1"] == 6 * per_step, out
        assert out["comm_bytes_window"] == 4 * per_step, out
    total = sum(rout["int8"]["comm_bytes_k1"] for rout in ranks)
    assert total == 2 * control


def test_two_process_weight_update_sharding_ckpt_round_trip(pack):
    """PR 11's ZeRO-sharded optimizer state lives SPLIT ACROSS
    PROCESSES; the multi-host checkpoint writes each process's shard
    files + the chief's merged manifest, and a restore into a fresh
    scope continues BIT-EXACTLY like the uninterrupted run."""
    ranks, out_dir = pack
    for rout in ranks:
        out = rout["wus"]
        assert out["sharded_vars"], out          # moments really sharded
        assert out["manifest_processes"] == 2
        np.testing.assert_array_equal(np.asarray(out["cont"]),
                                      np.asarray(out["base"]))
    # the checkpoint on disk really is multi-host-format and complete
    ckdir = os.path.join(str(out_dir), "ckpts")
    path = latest_checkpoint(ckdir, storage=ObjectStoreStorage())
    assert path is not None
    man = read_manifest(path)
    shard_entries = [e for e in man["tensors"].values() if "shards" in e]
    assert shard_entries
    procs = {s["process"] for e in shard_entries for s in e["shards"]}
    assert procs == {0, 1}                       # both processes wrote


def test_sigterm_to_one_process_drains_both_exit_zero(tmp_path):
    """Preemption consensus: SIGTERM delivered to exactly ONE process
    of the pack — the stop propagates through the per-boundary
    allgather, BOTH processes drain at the same window boundary, take
    the multi-host final save, and exit 0 with no orphans."""
    port = 26500 + (os.getpid() % 1500)
    proc = subprocess.Popen(
        mh.launch_cmd(tmp_path, port),
        env=mh.child_env(tmp_path, "preempt"), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    pids = {}
    try:
        deadline = time.time() + 120
        while len(pids) < 2 and time.time() < deadline:
            assert proc.poll() is None, proc.stdout.read()
            for r in (0, 1):
                pf = os.path.join(str(tmp_path), "pid.r%d" % r)
                if r not in pids and os.path.exists(pf):
                    with open(pf) as f:
                        pids[r] = int(f.read().strip())
            time.sleep(0.05)
        assert len(pids) == 2, "workers never started"
        time.sleep(0.8)                 # let a few windows run
        os.kill(pids[1], signal.SIGTERM)     # ONE process only
        out, _ = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, (out, mh.logs(tmp_path))
    r0, r1 = mh.rank_outputs(tmp_path)
    assert r0["drained"] and r1["drained"]
    # the signal landed on rank 1 ONLY — rank 0 drained by consensus
    assert r1["stop_requested_locally"] is True
    assert r0["stop_requested_locally"] is False
    assert r0["step"] == r1["step"] > 0
    assert r0["ckpt_step"] == r1["ckpt_step"] == r0["step"]
    for pid in pids.values():
        _assert_dead(pid)
    # the final multi-host checkpoint is committed and restorable
    ckdir = os.path.join(str(tmp_path), "ckpts")
    path = latest_checkpoint(ckdir, storage=ObjectStoreStorage())
    assert path is not None
    assert read_manifest(path)["step"] == r0["ckpt_step"]


def _assert_dead(pid, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            os.kill(pid, 0)
        except OSError:
            return
        try:
            with open("/proc/%d/stat" % pid) as f:
                state = f.read().rsplit(")", 1)[-1].split()[0]
            if state == "Z":
                return
        except OSError:
            return
        time.sleep(0.1)
    raise AssertionError("pid %d is still alive (orphaned)" % pid)


# ---------------------------------------------------------------------------
# Single-process: fluid.distributed API + mesh granule validation
# ---------------------------------------------------------------------------

def test_distributed_api_single_process_noops():
    """World-of-one contract: scripts call the API unconditionally."""
    rank, nproc = dist.init()
    assert (rank, nproc) == (0, 1)
    assert dist.process_index() == 0
    assert dist.process_count() == 1
    assert dist.is_chief()
    dist.barrier("single-proc-noop")                   # must not block
    assert dist.any_process(False) is False
    assert dist.any_process(True) is True
    assert dist.all_processes_equal(7) == 7
    # repeated init is idempotent
    assert dist.init() == (0, 1)


def test_init_requires_coordinator_for_multi_process(monkeypatch):
    monkeypatch.delenv("PADDLE_DIST_COORDINATOR", raising=False)
    monkeypatch.delenv("PADDLE_TRAINER_ENDPOINTS", raising=False)
    with pytest.raises(ValueError, match="coordinator"):
        dist.init(num_processes=2, process_id=0)


def test_parallel_env_reads_launcher_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
    monkeypatch.setenv("PADDLE_DIST_COORDINATOR", "10.0.0.1:1234")
    monkeypatch.setenv("PADDLE_LOCAL_DEVICE_IDS", "0,1")
    coord, nproc, rank, local = dist.parallel_env_from_env()
    assert (coord, nproc, rank, local) == ("10.0.0.1:1234", 4, 3, [0, 1])


def test_local_devices_is_this_process_only():
    """The device-selection audit's single source of truth: every
    local_devices() entry belongs to THIS process (a non-chief process
    can therefore never device_put to a remote device through any
    audited call site)."""
    import jax
    from paddle_tpu.fluid.mesh_utils import local_devices

    devs = local_devices()
    assert devs and all(d.process_index == jax.process_index()
                        for d in devs)
    exe = fluid.Executor(fluid.CPUPlace())
    assert exe._device.process_index == jax.process_index()
    assert fluid.ParallelExecutor(use_cuda=False).device_count == \
        len(devs)


def test_dcn_granule_validation():
    """mesh_utils: a leading 'dcn' axis on a non-TPU multi-process
    device set must align with whole process granules."""
    from paddle_tpu.fluid.mesh_utils import _check_dcn_granules

    class Dev:
        def __init__(self, pi, i):
            self.process_index, self.id, self.platform = pi, i, "cpu"

    # 2 processes x 4 devices, dcn=2 → one process per row: fine
    devs = [Dev(p, i) for p in range(2) for i in range(4)]
    _check_dcn_granules(devs, 2, ("dcn", "ici"))
    # dcn=4 → rows cut through processes: refused
    with pytest.raises(ValueError, match="granule"):
        _check_dcn_granules(devs, 4, ("dcn", "ici"))
    # single-process sets pass trivially (virtual dcn)
    _check_dcn_granules([Dev(0, i) for i in range(8)], 4, ("dcn",))


# ---------------------------------------------------------------------------
# Simulated-world multi-host checkpoint protocol (no subprocesses)
# ---------------------------------------------------------------------------

def _tiny_state(scope_seed=0):
    """A program + initialized scope to checkpoint."""
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, size=3))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup_p)
        exe.run(main_p, feed={"x": np.full((2, 4), 0.5, np.float32)},
                fetch_list=[loss], return_numpy=False)
    return main_p, scope


def _threaded_world_save(dirname, scope, program, count=2):
    """Drive a full multi-host save with every role live: one thread
    per process, a real threading.Barrier as the protocol fence —
    in-process, this IS the pod protocol."""
    bar = threading.Barrier(count)
    # async_save=False pins the barriered SYNC protocol — the
    # collective-free async one has its own suite below
    mgrs = [CheckpointManager(dirname, storage=ObjectStoreStorage(),
                              scope=scope, main_program=program,
                              process_index=i, process_count=count,
                              async_save=False,
                              barrier=lambda name: bar.wait(60))
            for i in range(count)]
    errs = []

    def run(m):
        try:
            m.save()
        except BaseException as e:       # noqa: BLE001 — surface below
            errs.append(e)

    threads = [threading.Thread(target=run, args=(m,)) for m in mgrs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errs, errs
    return mgrs


def test_simulated_world_save_restore_round_trip(tmp_path):
    program, scope = _tiny_state()
    mgrs = _threaded_world_save(str(tmp_path), scope, program)
    path = mgrs[0].latest_checkpoint()
    assert path is not None
    body = read_manifest(path)
    assert body["multihost"]["process_count"] == 2
    assert set(body["multihost"]["manifests"]) == {
        "MANIFEST.p0.json", "MANIFEST.p1.json"}
    fresh = fluid.Scope()
    meta = mgrs[1].restore(path, scope=fresh, main_program=program)
    assert meta["step"] == scope.step_counter
    for n in scope.var_names():
        np.testing.assert_array_equal(np.asarray(scope.find_var(n)),
                                      np.asarray(fresh.find_var(n)))


def test_chief_commit_aborts_when_worker_manifest_missing(tmp_path):
    """The chief-commits-before-worker-finishes kill case: even with
    the barrier violated (simulated), the commit ABORTS before writing
    the marker — the marker must never become visible while a sibling's
    shards are still uploading."""
    program, scope = _tiny_state()
    m0, m1 = fi.simulated_world(str(tmp_path), 2,
                                storage=ObjectStoreStorage(),
                                scope=scope, main_program=program)
    store = m0._shared_prefix_storage()
    final = os.path.join(str(tmp_path), "step-%d" % scope.step_counter)
    meta = {"step": int(scope.step_counter),
            "step_counter": int(scope.step_counter),
            "timestamp": time.time()}
    store.begin(final)
    full, shards = snapshot_addressable(
        scope, m0._persistable_names(program))
    m0._mh_write_local(store, final, 0, full, shards, meta)
    # worker (p1) never wrote its manifest — chief must refuse
    with pytest.raises((RuntimeError, ValueError),
                       match="manifest"):
        m0._mh_commit(store, final, 2, meta)
    assert not os.path.exists(os.path.join(final, MARKER_NAME))
    assert latest_checkpoint(str(tmp_path),
                             storage=ObjectStoreStorage()) is None
    # once the worker's part lands, the same commit succeeds
    m1._mh_write_local(store, final, 1, {}, shards, meta)
    m0._mh_commit(store, final, 2, meta)
    assert validate_checkpoint(final, storage=ObjectStoreStorage())


def _phase_save(dirname, scope, program):
    """The pod save's phases in protocol order, driven sequentially by
    one test process for a simulated 2-world (fi.simulated_world): the
    fault hooks see EXACTLY the write boundaries a real pack fires."""
    m0, m1 = fi.simulated_world(dirname, 2, storage=ObjectStoreStorage(),
                                scope=scope, main_program=program)
    store = m0._shared_prefix_storage()
    final = os.path.join(dirname, "step-%d" % scope.step_counter)
    meta = {"step": int(scope.step_counter),
            "step_counter": int(scope.step_counter),
            "timestamp": time.time()}
    store.begin(final)                                   # chief
    full, shards = snapshot_addressable(
        scope, m0._persistable_names(program))
    m1._mh_write_local(store, final, 1, {}, shards, meta)   # worker
    m0._mh_write_local(store, final, 0, full, shards, meta)  # chief
    m0._mh_commit(store, final, 2, meta)                    # chief
    return final


@pytest.mark.parametrize("point", ["tensor:", "pmanifest:p1",
                                   "pmanifest:p0", "manifest_mid",
                                   "marker:"])
def test_simulated_world_kill_matrix_never_selects_torn(tmp_path, point):
    """Crash at every new write boundary of the pod save — per-process
    tensor upload, either side's per-process manifest, the merged
    manifest, the marker — the torn step is never selectable and the
    previous committed step survives as latest."""
    program, scope = _tiny_state()
    good = _threaded_world_save(str(tmp_path), scope,
                                program)[0].latest_checkpoint()
    assert good is not None
    scope.step_counter += 1              # next save targets a new step
    with fi.crash_at(point):
        with pytest.raises(fi.SimulatedCrash):
            _phase_save(str(tmp_path), scope, program)
    assert latest_checkpoint(str(tmp_path),
                             storage=ObjectStoreStorage()) == good


def test_committed_pod_ckpt_with_doctored_files_is_not_selected(tmp_path):
    """Defense in depth past the commit protocol: a marker-committed
    multi-host checkpoint whose sibling manifest vanished, or whose
    marker bytes flipped, is invalid — and restore-side CRCs catch a
    flipped shard file."""
    program, scope = _tiny_state()
    mgrs = _threaded_world_save(str(tmp_path), scope, program)
    path = mgrs[0].latest_checkpoint()
    store = ObjectStoreStorage()
    # flip a marker byte → self-CRC fails → invisible
    marker = os.path.join(path, MARKER_NAME)
    fi.flip_byte(marker)
    assert not validate_checkpoint(path, storage=store)
    assert latest_checkpoint(str(tmp_path), storage=store) is None
    # restore the marker, then delete a sibling manifest → still refused
    _threaded_world_save(str(tmp_path), scope, program)
    path = latest_checkpoint(str(tmp_path), storage=store)
    assert path is not None
    os.unlink(os.path.join(path, "MANIFEST.p1.json"))
    assert not validate_checkpoint(path, storage=store)
    assert latest_checkpoint(str(tmp_path), storage=store) is None


class _ThreadConsensus:
    """Cross-thread stand-in for fluid.distributed.any_process: every
    role deposits its flag, a barrier round computes the global OR."""

    def __init__(self, n):
        self._lock = threading.Lock()
        self._vals = []
        self._deposit = threading.Barrier(n)
        self._read = threading.Barrier(n, action=self._vals.clear)

    def __call__(self, value):
        with self._lock:
            self._vals.append(bool(value))
        self._deposit.wait(60)
        result = any(self._vals)
        self._read.wait(60)
        return result


def test_pod_save_aborts_every_process_when_one_upload_fails(tmp_path):
    """An ORDINARY failure (disk full / retries exhausted) on ONE
    process's shard upload must abort the save on EVERY process — the
    failing role re-raises its own error, the siblings raise a
    sibling-failure error, nobody is stranded in a barrier, no marker
    is written, and the previous checkpoint stays latest."""
    program, scope = _tiny_state()
    good = _threaded_world_save(str(tmp_path), scope,
                                program)[0].latest_checkpoint()
    scope.step_counter += 1
    bar = threading.Barrier(2)
    consensus = _ThreadConsensus(2)
    mgrs = [CheckpointManager(str(tmp_path), storage=ObjectStoreStorage(),
                              scope=scope, main_program=program,
                              process_index=i, process_count=2,
                              async_save=False,
                              barrier=lambda name: bar.wait(60),
                              consensus=consensus)
            for i in range(2)]
    errs = {}

    def run(i, m):
        try:
            m.save()
        except BaseException as e:       # noqa: BLE001
            errs[i] = e

    with fi.raise_at("pmanifest:p1"):    # only the worker's upload fails
        threads = [threading.Thread(target=run, args=(i, m))
                   for i, m in enumerate(mgrs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    assert set(errs) == {0, 1}, errs     # BOTH processes raised
    assert isinstance(errs[1], OSError)
    assert "sibling process failed" in str(errs[0])
    torn = os.path.join(str(tmp_path), "step-%d" % scope.step_counter)
    assert not os.path.exists(os.path.join(torn, MARKER_NAME))
    assert latest_checkpoint(str(tmp_path),
                             storage=ObjectStoreStorage()) == good


def test_pod_upgrade_preserves_rename_committed_checkpoints(tmp_path):
    """A LocalStorage manager that upgrades to the pod marker protocol
    must keep honoring the directory's PRE-POD life: markerless
    rename-committed checkpoints are neither GC'd as crashed-upload
    debris nor hidden from latest_checkpoint — the fallback checkpoint
    survives the world-size change."""
    program, scope = _tiny_state()
    # single-host life: default LocalStorage, rename-committed
    legacy_mgr = CheckpointManager(str(tmp_path), async_save=False,
                                   scope=scope, main_program=program)
    legacy = legacy_mgr.save()
    assert not os.path.exists(os.path.join(legacy, MARKER_NAME))
    # pod life: same dirname, LocalStorage still configured → the save
    # upgrades to the marker protocol (warned once)
    scope.step_counter += 1
    bar = threading.Barrier(2)
    mgrs = [CheckpointManager(str(tmp_path), scope=scope,
                              main_program=program, process_index=i,
                              process_count=2, async_save=False,
                              barrier=lambda name: bar.wait(60))
            for i in range(2)]
    errs = []

    def run(m):
        try:
            with pytest.warns(UserWarning, match="marker protocol"):
                m.save()
        except BaseException as e:       # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=run, args=(m,)) for m in mgrs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errs, errs
    # the chief's gc ran — the legacy rename-committed step SURVIVES
    assert os.path.isdir(legacy)
    store = mgrs[0]._reader_storage()
    newest = latest_checkpoint(str(tmp_path), storage=store)
    assert newest and newest.endswith("step-%d" % scope.step_counter)
    # and with the pod step destroyed, the legacy step is the fallback
    import shutil
    shutil.rmtree(newest)
    assert latest_checkpoint(str(tmp_path), storage=store) == legacy
    meta = mgrs[0].restore(legacy, scope=fluid.Scope(),
                           main_program=program)
    assert meta["step"] == int(os.path.basename(legacy).split("-")[1])


def test_forced_sync_pod_save_uses_barriered_protocol(tmp_path):
    """``save(sync=True)`` on an async-by-default pod manager runs the
    BARRIERED sync protocol to completion before returning — last_step
    set, no background thread left behind, marker committed.  This is
    what the preemption drain and elastic shutdown rely on when the
    process is about to exit and a still-uploading snapshot would be
    lost."""
    program, scope = _tiny_state()
    bar = threading.Barrier(2)
    mgrs = [CheckpointManager(str(tmp_path), storage=ObjectStoreStorage(),
                              scope=scope, main_program=program,
                              async_save=True,
                              process_index=i, process_count=2,
                              barrier=lambda name: bar.wait(60))
            for i in range(2)]
    errs = []

    def run(m):
        try:
            m.save(sync=True)
        except BaseException as e:       # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=run, args=(m,)) for m in mgrs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errs, errs
    for m in mgrs:
        assert m.last_step == scope.step_counter
        assert m._thread is None
    assert latest_checkpoint(str(tmp_path),
                             storage=ObjectStoreStorage()) is not None


# ---------------------------------------------------------------------------
# ISSUE 18: the collective-free async pod save (simulated worlds)
# ---------------------------------------------------------------------------

def _no_collective(*_a, **_k):
    raise AssertionError(
        "collective invoked inside the async pod save path")


def _async_world(dirname, scope, program, count=2):
    """Simulated pod whose EVERY collective hook raises: the async
    protocol must reach agreement through storage alone."""
    return [CheckpointManager(dirname, storage=ObjectStoreStorage(),
                              scope=scope, main_program=program,
                              process_index=i, process_count=count,
                              async_save=True,
                              barrier=_no_collective,
                              consensus=_no_collective)
            for i in range(count)]


@pytest.fixture
def _short_commit_poll():
    """Shrink the bounded commit poll so abandonment tests run in
    milliseconds, restoring the production default afterwards."""
    from paddle_tpu.fluid import flags as flags_mod
    old = flags_mod.get_flag("checkpoint_commit_timeout_s")
    flags_mod.set_flag("checkpoint_commit_timeout_s", 0.4)
    yield
    flags_mod.set_flag("checkpoint_commit_timeout_s", old)


def test_async_pod_save_commits_without_collectives(tmp_path):
    """THE tentpole pin: a full async pod save — chief lease, parallel
    background uploads, chief polls storage for sibling manifests,
    marker written last — commits with ZERO barrier/consensus calls
    (every hook raises if touched), and the committed checkpoint
    restores bit-exactly."""
    program, scope = _tiny_state()
    mgrs = _async_world(str(tmp_path), scope, program)
    ref = {n: np.asarray(scope.find_var(n)).copy()
           for n in scope.var_names()}
    paths = [m.save() for m in mgrs]
    assert paths[0] == paths[1]
    for m in mgrs:
        m.wait()
        assert m._thread is None
        assert m.last_step == scope.step_counter
    path = latest_checkpoint(str(tmp_path), storage=ObjectStoreStorage())
    assert path == paths[0]
    body = read_manifest(path)
    assert body["multihost"]["process_count"] == 2
    assert validate_checkpoint(path, storage=ObjectStoreStorage())
    fresh = fluid.Scope()
    mgrs[1].restore(path, scope=fresh, main_program=program)
    for n, want in ref.items():
        np.testing.assert_array_equal(np.asarray(fresh.find_var(n)),
                                      want)


def test_async_pod_save_inflight_invisible_and_snapshot_isolated(
        tmp_path):
    """While the worker's upload is parked: save() has ALREADY returned
    on every rank, the markerless prefix is invisible to
    latest_checkpoint, the in-flight gauge is up — and scope mutations
    made after save() (training continuing) never leak into the
    committed artifact, which carries the snapshot values."""
    from paddle_tpu.fluid import telemetry

    program, scope = _tiny_state()
    m0, m1 = _async_world(str(tmp_path), scope, program)
    names = scope.var_names()
    ref = {n: np.asarray(scope.find_var(n)).copy() for n in names}
    g = telemetry.registry().gauge("checkpoint_async_in_flight")
    with fi.block_at("pmanifest:p1") as (reached, release):
        m0.save()
        m1.save()                      # returns though upload will park
        assert reached.wait(30)
        assert int(g.value()) == 1
        assert latest_checkpoint(str(tmp_path),
                                 storage=ObjectStoreStorage()) is None
        # "training continues": clobber every var during the upload
        for n in names:
            scope.set_var(n, np.asarray(scope.find_var(n)) + 100.0)
        release.set()
        for m in (m0, m1):
            m.wait()
    assert int(g.value()) == 0
    path = latest_checkpoint(str(tmp_path), storage=ObjectStoreStorage())
    assert path is not None
    fresh = fluid.Scope()
    m0.restore(path, scope=fresh, main_program=program)
    for n, want in ref.items():
        np.testing.assert_array_equal(np.asarray(fresh.find_var(n)),
                                      want)


def test_async_pod_worker_death_chief_abandons(tmp_path,
                                               _short_commit_poll):
    """Kill matrix, worker edge: the worker's uploader dies mid-shard —
    the chief's bounded sibling poll times out and ABANDONS (wait()
    raises nothing on the chief, the abandoned counter moves, training
    would continue); the worker's wait() re-raises its death; the
    previous checkpoint stays latest."""
    from paddle_tpu.fluid import telemetry

    program, scope = _tiny_state()
    good = _threaded_world_save(str(tmp_path), scope,
                                program)[0].latest_checkpoint()
    assert good is not None
    scope.step_counter += 1
    aband = telemetry.counter("checkpoint_commit_abandoned_total")
    a0 = int(aband.value() or 0)
    m0, m1 = _async_world(str(tmp_path), scope, program)
    with fi.crash_at("pmanifest:p1"):
        m0.save()
        m1.save()
        m0.wait()                      # chief: abandoned, NOT an error
        with pytest.raises(fi.SimulatedCrash):
            m1.wait()                  # worker: its own death re-raised
    assert int(aband.value() or 0) - a0 == 1
    assert m0.last_step != scope.step_counter
    assert latest_checkpoint(str(tmp_path),
                             storage=ObjectStoreStorage()) == good


def test_async_pod_chief_death_worker_abandons_then_recovers(
        tmp_path, _short_commit_poll):
    """Kill matrix, chief edge: the chief dies parked before the marker
    write — the worker's marker poll times out and abandons cleanly,
    the torn prefix is invisible, and the NEXT save (both ranks alive)
    commits normally: one rank's death costs one checkpoint."""
    from paddle_tpu.fluid import telemetry

    program, scope = _tiny_state()
    good = _threaded_world_save(str(tmp_path), scope,
                                program)[0].latest_checkpoint()
    scope.step_counter += 1
    aband = telemetry.counter("checkpoint_commit_abandoned_total")
    a0 = int(aband.value() or 0)
    m0, m1 = _async_world(str(tmp_path), scope, program)
    with fi.crash_at("marker:"):
        m0.save()
        m1.save()
        m1.wait()                      # worker: abandoned, NOT an error
        with pytest.raises(fi.SimulatedCrash):
            m0.wait()                  # chief: its own death re-raised
    assert int(aband.value() or 0) - a0 == 1
    assert latest_checkpoint(str(tmp_path),
                             storage=ObjectStoreStorage()) == good
    # survivors keep checkpointing: the next async save commits
    scope.step_counter += 1
    m0b, m1b = _async_world(str(tmp_path), scope, program)
    for m in (m0b, m1b):
        m.save()
    for m in (m0b, m1b):
        m.wait()
        assert m.last_step == scope.step_counter
    newest = latest_checkpoint(str(tmp_path),
                               storage=ObjectStoreStorage())
    assert newest and newest.endswith("step-%d" % scope.step_counter)


def test_async_pod_wedged_worker_chief_abandons_without_hanging(
        tmp_path, _short_commit_poll):
    """Kill matrix, wedge edge: a sibling that neither dies nor
    finishes (upload parked indefinitely) must not wedge the chief —
    the bounded poll abandons within the timeout, and once the wedged
    upload finally completes it finds no marker and abandons too."""
    program, scope = _tiny_state()
    m0, m1 = _async_world(str(tmp_path), scope, program)
    with fi.block_at("pmanifest:p1") as (reached, release):
        t0 = time.monotonic()
        m0.save()
        m1.save()
        assert reached.wait(30)
        m0.wait()                      # bounded: abandons, no hang
        assert time.monotonic() - t0 < 20
        release.set()
        m1.wait()                      # marker never written: abandons
    for m in (m0, m1):
        assert m.last_step != scope.step_counter
    assert latest_checkpoint(str(tmp_path),
                             storage=ObjectStoreStorage()) is None


def test_gc_spares_young_markerless_prefix_reaps_aged(tmp_path):
    """Satellite (a), the reaper/GC race: a markerless prefix younger
    than FLAGS_checkpoint_reap_min_age_s is a LIVE async upload — gc
    must spare it (and readers never select it); once aged past the
    guard it is debris and is reaped."""
    from paddle_tpu.fluid import flags as flags_mod

    program, scope = _tiny_state()
    mgr = CheckpointManager(str(tmp_path), storage=ObjectStoreStorage(),
                            scope=scope, main_program=program,
                            async_save=False, process_index=0,
                            process_count=2,
                            barrier=lambda name: None)
    # a committed step so gc has something legitimate to retain
    committed = _threaded_world_save(str(tmp_path), scope,
                                     program)[0].latest_checkpoint()
    # an in-flight prefix: chief's begin() claim (lease), no marker
    debris = os.path.join(str(tmp_path), "step-9999")
    store = mgr._shared_prefix_storage()
    store.begin(debris)
    store.put(debris, "t.npy", b"x" * 8, "tensor:t")
    mgr.gc()
    assert os.path.isdir(debris), \
        "gc reaped a younger-than-guard (live) async upload"
    assert latest_checkpoint(str(tmp_path),
                             storage=ObjectStoreStorage()) == committed
    # aged past the guard (flag to 0): now it is debris — reaped
    old = flags_mod.get_flag("checkpoint_reap_min_age_s")
    flags_mod.set_flag("checkpoint_reap_min_age_s", 0.0)
    try:
        mgr.gc()
    finally:
        flags_mod.set_flag("checkpoint_reap_min_age_s", old)
    assert not os.path.exists(debris)
    assert latest_checkpoint(str(tmp_path),
                             storage=ObjectStoreStorage()) == committed


# ---------------------------------------------------------------------------
# ISSUE 18 on the REAL pack (asyncpod section of the shared run)
# ---------------------------------------------------------------------------

def test_two_process_async_pod_save_commits_and_overlaps(pack):
    """The acceptance pin on real collectives: the async pod save's
    upload provably OVERLAPS training dispatches (rank 1's upload span
    encloses dispatch records in its own JSONL stream; both ranks stamp
    ckpt_overlap dispatches), zero collective calls and zero watchdog
    hangs across the save, the in-flight prefix was invisible, and the
    committed checkpoint restored bit-exactly."""
    ranks, out_dir = pack
    for rout in ranks:
        out = rout["asyncpod"]
        assert out["collective_delta"] == 0, out
        assert out["hang_delta"] == 0, out
        assert out["latest_while_inflight"] is None, out
        assert out["overlap_steps"] >= 4, out
        assert out["committed_step"] is not None
        assert out["manifest_processes"] == 2
        assert out["restore_exact"] is True
        assert len(out["losses_during"]) == 4
    assert ranks[1]["asyncpod"]["upload_parked_after_save"] is True
    # rank 1's JSONL: its parked upload span must ENCLOSE dispatch
    # records — the structural proof the upload ran DURING training
    events = []
    with open(str(out_dir / "run.jsonl") + ".p1") as f:
        for line in f:
            if line.strip():
                events.append(json.loads(line))
    uploads = [ev for ev in events if ev.get("kind") == "span"
               and ev.get("span") == "ckpt"
               and ev.get("name") == "upload"]
    assert uploads, "no ckpt upload span in rank 1's stream"
    dispatches = [ev for ev in events
                  if "kind" not in ev and "dur_ns" in ev]
    enclosed = [
        d for d in dispatches for u in uploads
        if u["ts_ns"] < d["ts_ns"]
        and d["ts_ns"] + d["dur_ns"] < u["ts_ns"] + u["dur_ns"]]
    assert len(enclosed) >= 4, (len(enclosed), len(uploads),
                                len(dispatches))
    assert any(d.get("ckpt_overlap") for d in enclosed)
    # the committed artifact on shared storage is a 2-process pod ckpt
    ckdir = os.path.join(str(out_dir), "ckpts_async")
    path = latest_checkpoint(ckdir, storage=ObjectStoreStorage())
    assert path is not None
    assert read_manifest(path)["multihost"]["process_count"] == 2


@pytest.mark.slow
def test_two_process_chief_killed_mid_async_save_survivor_resumes(
        tmp_path):
    """ISSUE 18 acceptance, the pod-scale kill: the CHIEF dies hard
    parked before the marker write of an async save.  The worker's
    bounded commit poll abandons (exit 0, counter moved, last_step
    pinned at the committed step); the launcher relaunches the survivor
    world of one, which resumes the LAST COMMITTED step bit-exact —
    blind to the markerless debris the dead save left behind."""
    port = 24800 + (os.getpid() % 1500)
    proc = subprocess.run(
        mh.launch_cmd(tmp_path, port,
                      extra_args=["--max_restarts", "1",
                                  "--elastic_min_nproc", "1",
                                  "--grace_period", "10"]),
        env=mh.child_env(
            tmp_path, "asynckill",
            {"FLAGS_checkpoint_commit_timeout_s": "2.0",
             "FLAGS_metrics_jsonl": str(tmp_path / "kill.jsonl")}),
        cwd=REPO, timeout=420, capture_output=True, text=True)
    assert proc.returncode == 0, (proc.stdout, proc.stderr,
                                  mh.logs(tmp_path))
    assert "relaunching pack" in proc.stderr, proc.stderr
    assert "world 2 -> 1" in proc.stderr, proc.stderr
    with open(os.path.join(str(tmp_path), "abandon_r1.json")) as f:
        aband = json.load(f)
    with open(os.path.join(str(tmp_path), "resume_r0.json")) as f:
        resume = json.load(f)
    # the worker abandoned exactly once and kept the committed step
    assert aband["abandoned_delta"] == 1, aband
    assert aband["last_step"] == resume["committed_step_expected"]
    assert aband["latest"] == "step-%d" % aband["last_step"]
    # the survivor restored the committed step bit-exact, debris intact
    assert resume["world"] == 1 and resume["prev_nproc"] == 2
    assert resume["step"] == resume["committed_step_expected"]
    assert resume["exact"] is True, resume
    assert resume["latest"] == "step-%d" % resume["step"]
    assert len(resume["prefixes"]) == 2, resume   # committed + debris
    # the operator view agrees: 1 committed, 1 in-flight/abandoned,
    # 0 torn → exit 0 (satellite b's CLI on real pod debris)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "checkpoint_inspect.py"),
         os.path.join(str(tmp_path), "ckpts"), "--json"],
        capture_output=True, text=True)
    assert out.returncode == 0, (out.stdout, out.stderr)
    doc = json.loads(out.stdout)
    assert doc["counts"].get("committed") == 1
    assert doc["counts"].get("in-flight", 0) + \
        doc["counts"].get("abandoned", 0) == 1
    assert "torn" not in doc["counts"]
