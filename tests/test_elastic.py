"""Elastic training (ISSUE 14): checkpoint resharding across
weight-update-sharding degrees / world sizes, the elastic driver loop,
the launcher's restart-with-new-world support, and the operator
tooling around them.

Fast (tier-1) coverage runs in-process on the 8-virtual-device CPU
mesh: a degree-N checkpoint restores onto a degree-M program
(``restore(reshard=True)``), the N→M→N round trip continues BIT-EXACT
vs an uninterrupted control, mixed-degree directories select/GC
correctly, the pivot-save kill matrix never loses the fallback
checkpoint, the in-process ``elastic.run_elastic`` resize emits the
``kind="resize"`` lifecycle record with recovery seconds, and the
launcher relaunches crashed children under ``--max_restarts``.

The acceptance run is a REAL 2-process gloo pack (skip-guarded like
tests/test_multihost.py): it saves a degree-2 pod checkpoint, the pack
is killed, ``launch.py --max_restarts --elastic_min_nproc`` relaunches
the survivor world of one which reshard-restores 2→1, and a fresh
2-process pack re-expands 1→2 with BIT-EXACT loss continuation vs the
uninterrupted single-process control.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import distributed as dist
from paddle_tpu.fluid import elastic, flags, preemption, telemetry
from paddle_tpu.fluid.checkpoint import (CheckpointManager,
                                         checkpoint_metadata,
                                         latest_checkpoint,
                                         read_manifest)
from paddle_tpu.fluid.storage import (MARKER_NAME, MixedProtocolReader,
                                      ObjectStoreStorage)
from paddle_tpu.fluid.transpiler import GradAllReduce

import faultinject as fi
import mh_harness as mh
import dist_multihost_worker as worker_mod

REPO = mh.REPO
_WORKER = mh.WORKER

# ---------------------------------------------------------------------------
# Shared world: one tiny WUS job, several sharding degrees.  Programs
# and executors are built once per module (compiles dominate cost);
# every test trains in its own fresh Scope.
# ---------------------------------------------------------------------------

def _build_wus(nranks, fuse_grad_size_mb=32, hidden=8):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, size=hidden, act="relu")
            pred = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.MomentumOptimizer(0.05, 0.9).minimize(loss)
    GradAllReduce(weight_update_sharding=True,
                  fuse_grad_size_mb=fuse_grad_size_mb).transpile(
        startup_program=startup, main_program=main, rank=0,
        endpoints=[], nranks=nranks)
    return {"main": main, "startup": startup, "loss": loss}


_FEEDS = None


def _feeds():
    global _FEEDS
    if _FEEDS is None:
        rng = np.random.RandomState(0)
        xs = rng.randn(16, 16).astype(np.float32)
        _FEEDS = {"x": xs, "y": (xs @ rng.randn(16, 1)).astype(np.float32)}
    return _FEEDS


@pytest.fixture(scope="module")
def W():
    """Degree-keyed program/executor cache: ``W(deg)`` returns the
    build dict with a shared Executor whose plan cache stays warm
    across tests."""
    cache = {}

    def get(deg):
        if deg not in cache:
            built = _build_wus(deg)
            built["exe"] = fluid.Executor(fluid.CPUPlace())
            cache[deg] = built
        return cache[deg]

    return get


def _fresh_scope(w):
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        w["exe"].run(w["startup"])
    return scope


def _steps(w, scope, n):
    """n training steps; returns the per-step raveled per-shard loss
    rows (bit-comparable across runs of the same degree)."""
    out = []
    with fluid.scope_guard(scope):
        for _ in range(n):
            v = w["exe"].run(w["main"], feed=dict(_feeds()),
                             fetch_list=[w["loss"]])[0]
            out.append([float(x) for x in np.ravel(np.asarray(v))])
    return out


# ---------------------------------------------------------------------------
# Tentpole: cross-degree reshard restore
# ---------------------------------------------------------------------------

def test_reshard_gate_metadata_and_bit_exact_roundtrip(W, tmp_path):
    """The acceptance core, in-process: a degree-4 checkpoint (a) still
    refuses a degree-2 restore WITHOUT reshard — with an error citing
    checkpoint_metadata and reshard=True; (b) restores WITH
    reshard=True and keeps training; and (c) the 4→2→4 round trip
    (pivot-saved at the SAME step into a fresh dir, no degree-2 steps
    in between) continues BIT-EXACTLY like the uninterrupted degree-4
    control — resharding loses no information."""
    w4, w2 = W(4), W(2)
    dir_a, dir_b = str(tmp_path / "a"), str(tmp_path / "b")

    s4 = _fresh_scope(w4)
    _steps(w4, s4, 3)
    CheckpointManager(dir_a, scope=s4, main_program=w4["main"],
                      async_save=False).save()
    control = _steps(w4, s4, 3)          # the uninterrupted trajectory

    # (a) the gate fires without reshard, citing the way out
    s2 = _fresh_scope(w2)
    mgr_a2 = CheckpointManager(dir_a, scope=s2, main_program=w2["main"])
    with pytest.raises(RuntimeError, match="world size"):
        mgr_a2.resume()
    with pytest.raises(RuntimeError, match="reshard=True"):
        mgr_a2.resume()
    with pytest.raises(RuntimeError, match="checkpoint_metadata"):
        mgr_a2.resume()

    # (b) metadata without loading tensors
    path = latest_checkpoint(dir_a)
    info = checkpoint_metadata(path)
    assert info["shard_degree"] == 4
    assert info["process_count"] == 1 and not info["multihost"]
    assert "wus_velocity_0" in info["sharded_vars"]
    assert info["tensor_count"] > 0 and info["total_bytes"] > 0
    body = read_manifest(path)
    assert body["sharded_numel"]["wus_velocity_0"] > 0

    # (c) reshard 4→2, pivot-save at the SAME step into dir_b, then
    # 2→4 — and the re-expanded run continues bit-exactly
    meta = mgr_a2.resume(reshard=True)
    assert meta["resharded"] is True and meta["shard_degree"] == 4
    mgr_b = CheckpointManager(dir_b, scope=s2, main_program=w2["main"],
                              async_save=False)
    mgr_b.save()
    # the degree-2 world really trains (its loss tracks the control's
    # global mean — different summation order, so allclose not equal)
    got2 = _steps(w2, s2, 3)
    np.testing.assert_allclose(
        [np.mean(r) for r in got2], [np.mean(r) for r in control],
        rtol=1e-4, atol=1e-5)

    s4b = _fresh_scope(w4)
    meta_b = CheckpointManager(dir_b, scope=s4b,
                               main_program=w4["main"]).resume(
        reshard=True)
    assert meta_b["resharded"] is True and meta_b["shard_degree"] == 2
    got4 = _steps(w4, s4b, 3)
    assert got4 == control, (got4, control)


def test_reshard_refuses_different_bucket_layout(W, tmp_path):
    """A degree change must not paper over a LAYOUT change: the same
    var name with a different logical bucket size (here per-grad
    buckets via fuse_grad_size_mb=0 vs the fused default) is refused
    loudly instead of silently truncated into scrambled state."""
    w4 = W(4)
    s4 = _fresh_scope(w4)
    _steps(w4, s4, 1)
    CheckpointManager(str(tmp_path), scope=s4, main_program=w4["main"],
                      async_save=False).save()
    other = _build_wus(2, fuse_grad_size_mb=0)
    with pytest.raises(RuntimeError, match="bucket layouts differ"):
        CheckpointManager(str(tmp_path), scope=fluid.Scope(),
                          main_program=other["main"]).resume(
            reshard=True)


def test_mixed_degree_selection_and_gc(W, tmp_path):
    """After a resize, one directory legitimately holds degree-4 AND
    degree-2 checkpoints: ``latest_checkpoint`` picks the newest
    complete one whatever its degree, never a torn one; retention GC
    counts both degrees, keeps the newest, and never deletes the only
    restorable checkpoint."""
    import shutil
    d = str(tmp_path)
    w4, w2 = W(4), W(2)
    s4 = _fresh_scope(w4)
    _steps(w4, s4, 1)
    mgr4 = CheckpointManager(d, scope=s4, main_program=w4["main"],
                             async_save=False, max_to_keep=2)
    p_old = mgr4.save()

    s2 = _fresh_scope(w2)
    mgr2 = CheckpointManager(d, scope=s2, main_program=w2["main"],
                             async_save=False, max_to_keep=2)
    mgr2.resume(reshard=True)
    s2.step_counter += 5
    p_new = mgr2.save()
    assert p_new != p_old
    # a TORN newer step (crashed copy of the degree-4 dir) is invisible
    p_torn = os.path.join(d, "step-%d" % (s2.step_counter + 5))
    shutil.copytree(p_old, p_torn)
    fi.truncate_file(os.path.join(p_torn, "MANIFEST.json"))
    assert latest_checkpoint(d) == p_new
    # both degrees restorable side by side, each by its own manifest
    assert checkpoint_metadata(p_old)["shard_degree"] == 4
    assert checkpoint_metadata(p_new)["shard_degree"] == 2
    # retention: keep-2 counts both degrees (old + new survive); with
    # keep-1 the degree-4 step goes, the newest (degree-2) NEVER does
    mgr2.gc()
    assert os.path.isdir(p_old) and os.path.isdir(p_new)
    mgr1 = CheckpointManager(d, scope=s2, main_program=w2["main"],
                             async_save=False, max_to_keep=1)
    mgr1.gc()
    assert not os.path.isdir(p_old)
    assert os.path.isdir(p_new)
    assert latest_checkpoint(d) == p_new
    meta = CheckpointManager(d, scope=_fresh_scope(w4),
                             main_program=w4["main"]).resume(
        reshard=True)
    assert meta["shard_degree"] == 2


@pytest.mark.parametrize("point", ["tensor:", "manifest_mid", "marker:"])
def test_pivot_save_kill_matrix_keeps_fallback(W, tmp_path, point):
    """The reshard-restore write boundaries: the elastic pivot (re-save
    at the new degree, into a fresh object-store prefix) killed at any
    write boundary leaves the ORIGINAL degree-4 checkpoint as latest —
    the job reshard-restores from it again; a crash-free retry then
    commits the degree-2 pivot."""
    w4, w2 = W(4), W(2)
    dir_a, dir_b = str(tmp_path / "a"), str(tmp_path / "b")
    store = ObjectStoreStorage()

    s4 = _fresh_scope(w4)
    _steps(w4, s4, 2)
    CheckpointManager(dir_a, scope=s4, main_program=w4["main"],
                      async_save=False, storage=store).save()

    s2 = _fresh_scope(w2)
    CheckpointManager(dir_a, scope=s2, main_program=w2["main"],
                      storage=store).resume(reshard=True)
    mgr_b = CheckpointManager(dir_b, scope=s2, main_program=w2["main"],
                              async_save=False, storage=store)
    with fi.crash_at(point):
        with pytest.raises(fi.SimulatedCrash):
            mgr_b.save()
    # the torn pivot is invisible; the degree-4 original still restores
    assert latest_checkpoint(dir_b, storage=store) is None
    s2b = _fresh_scope(w2)
    meta = CheckpointManager(dir_a, scope=s2b, main_program=w2["main"],
                             storage=store).resume(reshard=True)
    assert meta["resharded"] is True
    # retry without the fault: the pivot commits and wins
    mgr_b.save()
    p = latest_checkpoint(dir_b, storage=store)
    assert p is not None
    assert checkpoint_metadata(p)["shard_degree"] == 2


# ---------------------------------------------------------------------------
# The in-process elastic driver
# ---------------------------------------------------------------------------

def test_run_elastic_in_process_resize_records_and_status(W, tmp_path):
    """``elastic.run_elastic`` absorbs a preemption + degree change in
    one process: cycle 0 trains at degree 4 through train_from_dataset
    (whose feeds now land on the collective mesh in a world of one —
    the prefetch-placement fix) and is stop-requested mid-stream; the
    driver shuts the world down, rebuilds at degree 2,
    reshard-restores, and cycle 1 finishes — leaving a ``resize``
    lifecycle record with old/new degree and recovery seconds in the
    step-event ring AND the metrics JSONL."""
    jsonl = str(tmp_path / "run.jsonl")
    degrees = {0: 4, 1: 2}
    seen = []

    def build(ctx):
        w = W(degrees[ctx.cycle])
        scope = _fresh_scope(w)
        seen.append((ctx.cycle, ctx.process_count))
        mgr = CheckpointManager(str(tmp_path / "ck"), scope=scope,
                                main_program=w["main"],
                                async_save=False)
        build.w = w
        return mgr, scope, w["main"]

    class DS:
        def __init__(self, cycle):
            self.cycle = cycle

        def set_thread(self, n):
            pass

        def _prepare_to_run(self):
            pass

        def _finish_to_run(self):
            pass

        def __iter__(self):
            for i in range(4 if self.cycle else 100):
                if self.cycle == 0 and i == 2:
                    preemption.request_stop("capacity-lost")
                yield dict(_feeds())

    def train(ctx):
        w = build.w
        with fluid.scope_guard(ctx.scope):
            return w["exe"].train_from_dataset(
                ctx.program, DS(ctx.cycle), fetch_list=[w["loss"]],
                print_period=10 ** 9, checkpoint_manager=ctx.manager)

    r0 = telemetry.registry().counter("elastic_resizes_total").value()
    flags.set_flag("metrics_jsonl", jsonl)
    try:
        status = elastic.run_elastic(
            build, train,
            next_world=lambda ctx: {} if ctx.cycle == 0 else None)
    finally:
        flags.set_flag("metrics_jsonl", "")
        telemetry.close_jsonl()
    # train_from_dataset returned its status dict; the driver read the
    # consensus verdict from it
    assert status["last"] == {"steps": 4, "preempted": False,
                              "rollbacks": 0}
    assert status["cycles"] == 2 and status["resizes"] == 1
    assert status["preempted"] is False
    assert seen == [(0, 1), (1, 1)]
    assert telemetry.registry().counter(
        "elastic_resizes_total").value() - r0 == 1
    recs = [json.loads(line) for line in open(jsonl)
            if '"resize"' in line]
    assert len(recs) == 1, recs
    rec = recs[0]
    assert rec["old_degree"] == 4 and rec["new_degree"] == 2
    assert rec["old_world"] == rec["new_world"] == 1
    assert rec["recovery_s"] > 0
    assert rec["step"] == status["restored_step"]
    # the ring carries it too (chrome trace / metrics_report source)
    ring = [ev for ev in telemetry.step_events()
            if ev.get("kind") == "resize"]
    assert ring and ring[-1]["old_degree"] == 4


def test_distributed_shutdown_world_of_one_and_reinit():
    """shutdown() is a safe no-op teardown for a never-connected world:
    identity resets, a later init() works, telemetry label cleared."""
    assert dist.init() == (0, 1)
    dist.shutdown()
    assert dist.process_count() == 1 and dist.process_index() == 0
    assert telemetry.process_label() is None
    assert dist.init() == (0, 1)


def test_run_elastic_carries_next_world_spec_to_reinit(tmp_path,
                                                       monkeypatch):
    """The next_world spec must reach the LOOP-TOP ``distributed.init``
    of the following cycle: an explicit identity handed back by
    next_world may not fight the (possibly stale) launcher env that an
    argless re-init would autodetect from — e.g. a shrink-to-one spec
    under leftover PADDLE_TRAINERS_NUM=2 would try to re-rendezvous
    into the torn-down world."""
    calls = []
    real_init = dist.init

    def recording_init(**kw):
        calls.append(dict(kw))
        return real_init(**kw)

    monkeypatch.setattr(dist, "init", recording_init)

    def build(ctx):
        prog = fluid.Program()
        mgr = CheckpointManager(str(tmp_path / "ck"),
                                scope=fluid.global_scope(),
                                main_program=prog)
        return mgr, fluid.global_scope(), prog

    def train(ctx):
        return {"steps": 0, "preempted": ctx.cycle == 0, "rollbacks": 0}

    spec = {"num_processes": 1, "process_id": 0}
    status = elastic.run_elastic(
        build, train,
        next_world=lambda ctx: dict(spec) if ctx.cycle == 0 else None)
    assert status["cycles"] == 2
    assert calls == [{}, spec]


# ---------------------------------------------------------------------------
# checkpoint_metadata on pod checkpoints + the inspect CLI
# ---------------------------------------------------------------------------

def _threaded_world_save(dirname, scope, program, count=2):
    bar = threading.Barrier(count)
    # async_save=False: this helper pins the barriered SYNC pod
    # protocol (the async one is test_multihost.py's _async_world)
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
        except BaseException as e:       # noqa: BLE001 — surfaced below
            errs.append(e)

    threads = [threading.Thread(target=run, args=(m,)) for m in mgrs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errs, errs
    return mgrs


def test_checkpoint_metadata_multihost_and_inspect_cli(W, tmp_path,
                                                      capsys):
    """checkpoint_metadata walks the pod manifest chain (process_count
    from the chief's merge, marker required) without loading tensors;
    tools/checkpoint_inspect.py prints the summary and exits nonzero
    exactly when something is torn — including a doctored sibling
    manifest a shallow look would miss."""
    w4 = W(4)
    s4 = _fresh_scope(w4)
    _steps(w4, s4, 1)
    d = str(tmp_path / "pod")
    mgrs = _threaded_world_save(d, s4, w4["main"])
    path = mgrs[0].latest_checkpoint()
    info = checkpoint_metadata(path)
    assert info["multihost"] is True and info["process_count"] == 2
    assert info["shard_degree"] == 4
    assert info["step"] == s4.step_counter

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import checkpoint_inspect
    finally:
        sys.path.pop(0)
    assert checkpoint_inspect.main([d, "--deep"]) == 0
    out = capsys.readouterr().out
    assert "OK" in out and "world 2 process(es) (multihost)" in out
    # doctor a sibling manifest: metadata AND the CLI both refuse —
    # the marker granted visibility but the content fails, so this is
    # the TORN state (genuine corruption, the one exit-1 condition)
    fi.flip_byte(os.path.join(path, "MANIFEST.p1.json"))
    with pytest.raises(ValueError, match="manifest"):
        checkpoint_metadata(path)
    assert checkpoint_inspect.main([d]) == 1
    out = capsys.readouterr().out
    assert "TORN" in out
    # --json dialect
    assert checkpoint_inspect.main([d, "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["valid"] is False and doc["checkpoints"]


def test_inspect_classifies_markerless_object_store_save(W, tmp_path,
                                                         capsys):
    """A markerless ObjectStoreStorage dir stays INVISIBLE to the
    restore readers (checkpoint_metadata refuses, latest_checkpoint
    skips) — but with async pod checkpoints it is frequently a LIVE
    upload, so the operator CLI CLASSIFIES instead of alarming: younger
    than the reap guard → in-flight, exit 0; aged past it → abandoned
    debris, exit 0; only a marker-granted-but-invalid dir is TORN and
    exits 1."""
    w = W(2)
    s = _fresh_scope(w)
    _steps(w, s, 1)
    d = str(tmp_path / "obj")
    mgr = CheckpointManager(d, storage=ObjectStoreStorage(), scope=s,
                            main_program=w["main"], async_save=False)
    path = mgr.save()
    assert checkpoint_metadata(path)["step"] == s.step_counter
    os.unlink(os.path.join(path, MARKER_NAME))   # the marker-crash dir
    with pytest.raises(ValueError, match="commit marker"):
        checkpoint_metadata(path)
    assert latest_checkpoint(d) is None          # readers: invisible
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import checkpoint_inspect
    finally:
        sys.path.pop(0)
    # young (save seconds ago, lease clock): presumed a live async
    # upload — IN-FLIGHT, and the pre-flight does NOT fail
    assert checkpoint_inspect.main([d]) == 0
    assert "INFLIGHT" in capsys.readouterr().out
    # aged past the reap guard: crashed-save debris — ABANDONED, still
    # exit 0 (debris is the reaper's problem, not corruption)
    old = flags.get_flag("checkpoint_reap_min_age_s")
    flags.set_flag("checkpoint_reap_min_age_s", 0.0)
    try:
        assert checkpoint_inspect.main([d, "--json"]) == 0
    finally:
        flags.set_flag("checkpoint_reap_min_age_s", old)
    doc = json.loads(capsys.readouterr().out)
    assert doc["counts"].get("abandoned") == 1
    assert doc["checkpoints"][0]["state"] == "abandoned"
    assert doc["valid"] is True


# ---------------------------------------------------------------------------
# (the --max_restarts relaunch/cap scenarios live in
# test_launch_relaunch_matrix.py)
# ---------------------------------------------------------------------------


def test_launch_elastic_min_nproc_needs_coordinator():
    with pytest.raises(SystemExit):
        from paddle_tpu.distributed.launch import parse_args
        parse_args(["--elastic_min_nproc", "1", "x.py"])


# ---------------------------------------------------------------------------
# metrics_report: resize lifecycle rows
# ---------------------------------------------------------------------------

def test_metrics_report_resize_rows():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_report
    finally:
        sys.path.pop(0)
    events = [
        {"k": 1, "dur_ns": 50000, "plan_hit": True},
        {"kind": "resize", "step": 12, "old_world": 2, "new_world": 1,
         "old_degree": 2, "new_degree": 1, "recovery_s": 1.5},
        {"kind": "resize", "step": 20, "old_world": 1, "new_world": 2,
         "old_degree": 1, "new_degree": 2, "recovery_s": 0.5},
    ]
    rows = metrics_report.summarize(events)
    life = rows["lifecycle"]
    assert life["resizes"] == 2
    assert life["last_resize"] == {"step": 20, "old_world": 1,
                                   "new_world": 2, "old_degree": 1,
                                   "new_degree": 2}
    assert life["resize_recovery_p50_s"] == 0.5   # nearest-rank of 2
    text = metrics_report.format_report(rows)
    assert "elastic: 2 resize(s)" in text
    assert "world 1 -> 2" in text and "recovery p50 0.500 s" in text
    # dur_ns fallback for records predating the recovery_s field
    rows2 = metrics_report.summarize(
        [{"kind": "resize", "step": 1, "dur_ns": 2_000_000_000}])
    assert rows2["lifecycle"]["resize_recovery_p50_s"] == 2.0


# ---------------------------------------------------------------------------
# THE acceptance run: 2-process gloo pack, kill, 2→1, then 1→2
# ---------------------------------------------------------------------------

def _child_env(out_dir, phase, jsonl):
    return mh.child_env(out_dir, "elastic",
                        {"MH_ELASTIC_PHASE": phase,
                         "FLAGS_metrics_jsonl": jsonl})


_logs = mh.logs


def _resize_records(jsonl_base):
    recs = []
    for suffix in ("", ".p0", ".p1"):
        p = jsonl_base + suffix
        if os.path.exists(p):
            recs.extend(json.loads(line) for line in open(p)
                        if '"resize"' in line)
    return recs


def test_elastic_smoke_shrink_expand_bit_exact_in_process(W, tmp_path):
    """Fast smoke for the acceptance run's exact pivot sequence (the
    full 2-process launcher version is ``@slow``): a degree-2 save,
    reshard-restore 2→1, pivot-save at degree 1 into a FRESH dir at the
    SAME step, reshard-restore 1→2 — and the re-expanded degree-2 run
    continues BIT-EXACTLY like the uninterrupted control."""
    w2, w1 = W(2), W(1)
    pod_dir, pivot_dir = str(tmp_path / "pod"), str(tmp_path / "pivot")

    s2 = _fresh_scope(w2)
    _steps(w2, s2, 3)
    CheckpointManager(pod_dir, scope=s2, main_program=w2["main"],
                      async_save=False,
                      storage=ObjectStoreStorage()).save()
    control = _steps(w2, s2, 5)        # the uninterrupted trajectory

    # shrink 2→1 + pivot at the SAME step (no degree-1 training first)
    s1 = _fresh_scope(w1)
    meta = CheckpointManager(pod_dir, scope=s1,
                             main_program=w1["main"],
                             storage=ObjectStoreStorage()).resume(
        reshard=True)
    assert meta["resharded"] is True and meta["shard_degree"] == 2
    CheckpointManager(pivot_dir, scope=s1, main_program=w1["main"],
                      async_save=False,
                      storage=ObjectStoreStorage()).save()
    # the degree-1 world really trains before the expand
    assert _steps(w1, s1, 2)

    # expand 1→2 from the pivot: bit-exact continuation
    s2b = _fresh_scope(w2)
    meta_b = CheckpointManager(pivot_dir, scope=s2b,
                               main_program=w2["main"],
                               storage=ObjectStoreStorage()).resume(
        reshard=True)
    assert meta_b["resharded"] is True and meta_b["shard_degree"] == 1
    got = _steps(w2, s2b, 5)
    assert got == control, (got, control)


@pytest.mark.slow
def test_two_process_elastic_shrink_then_expand_bit_exact(tmp_path):
    """ISSUE 14 acceptance: a real 2-process gloo pack saves a degree-2
    pod checkpoint at step 3 and the pack dies (one rank exits hard,
    the launcher tears the group down); ``--max_restarts 1
    --elastic_min_nproc 1`` relaunches the SURVIVOR world of one, which
    reshard-restores 2→1 (a resize record with recovery seconds lands
    in the JSONL), pivot-saves at degree 1, probes two degree-1 steps,
    and exits 0.  A fresh 2-process pack then re-expands 1→2 and
    trains steps 3..7 BIT-EXACTLY like the uninterrupted
    single-process control — the 2→1→2 reshard round trip loses
    nothing."""
    out_a = tmp_path / "shrink"
    out_b = tmp_path / "expand"
    os.makedirs(out_a), os.makedirs(out_b)
    port = 28200 + (os.getpid() % 1200)

    # phase A: shrink.  One launcher invocation covers attempt 0 (the
    # 2-proc life + crash) AND attempt 1 (the survivor world of one).
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--coordinator", "--nproc_per_node", "2",
         "--started_port", str(port), "--log_dir", str(out_a),
         "--max_restarts", "1", "--elastic_min_nproc", "1",
         "--grace_period", "10",
         _WORKER],
        env=_child_env(out_a, "shrink", str(out_a / "run.jsonl")),
        cwd=REPO, timeout=300, capture_output=True, text=True)
    assert proc.returncode == 0, (proc.stdout, proc.stderr,
                                  _logs(out_a))
    assert "relaunching pack" in proc.stderr
    assert "world 2 -> 1" in proc.stderr
    with open(os.path.join(str(out_a), "out_r0.json")) as f:
        shrink = json.load(f)
    assert shrink["phase"] == "shrink1" and shrink["world"] == 1
    assert shrink["attempt"] == 1 and shrink["prev_nproc"] == 2
    rst = shrink["restored"]
    assert rst["resized"] is True and rst["resharded"] is True
    assert rst["shard_degree"] == 2
    assert (rst["old_world"], rst["new_world"]) == (2, 1)
    # the pod checkpoint really was a 2-process degree-2 artifact with
    # genuinely split shard files
    pod = checkpoint_metadata(
        latest_checkpoint(os.path.join(str(out_a), "ckpts"),
                          storage=MixedProtocolReader()))
    assert pod["multihost"] is True and pod["process_count"] == 2
    assert pod["shard_degree"] == 2
    man = read_manifest(pod["path"])
    procs_writing = {s["process"]
                     for e in man["tensors"].values() if "shards" in e
                     for s in e["shards"]}
    assert procs_writing == {0, 1}
    # the resize record: 2→1 with a real recovery time
    rec_a = [r for r in _resize_records(str(out_a / "run.jsonl"))
             if r["new_world"] == 1]
    assert rec_a and rec_a[0]["old_world"] == 2
    assert rec_a[0]["old_degree"] == 2 and rec_a[0]["new_degree"] == 1
    assert rec_a[0]["recovery_s"] > 0

    # the uninterrupted single-process control of the SAME nranks=2
    # program (bit-exact oracle, as test_multihost pins)
    feeds = worker_mod.make_feeds()
    built = worker_mod.build_program(wus=True, rank=0, nranks=2)
    main_p, startup_p, loss = built
    control = []
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup_p)
        for f in feeds[:8]:
            v = exe.run(main_p, feed=f, fetch_list=[loss])[0]
            control.append(np.ravel(np.asarray(v)))
    # the degree-1 probe tracks the control's global mean
    probe = np.asarray(shrink["probe"]).ravel()
    np.testing.assert_allclose(
        probe, [np.mean(control[3]), np.mean(control[4])],
        rtol=1e-4, atol=1e-5)

    # phase B: expand 1→2 from the degree-1 pivot
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--coordinator", "--nproc_per_node", "2",
         "--started_port", str(port + 40), "--log_dir", str(out_b),
         "--grace_period", "10",
         _WORKER],
        env=dict(_child_env(out_b, "expand",
                            str(out_b / "run.jsonl")),
                 MH_CKPTS=os.path.join(str(out_a), "ckpts_pivot")),
        cwd=REPO, timeout=300, capture_output=True, text=True)
    assert proc.returncode == 0, (proc.stdout, proc.stderr,
                                  _logs(out_b))
    for r in (0, 1):
        with open(os.path.join(str(out_b), "out_r%d.json" % r)) as f:
            expand = json.load(f)
        rst = expand["restored"]
        assert rst["resized"] is True and rst["resharded"] is True
        assert rst["shard_degree"] == 1
        assert (rst["old_world"], rst["new_world"]) == (1, 2)
        # the pivot carried the pod checkpoint's step verbatim
        assert rst["step"] == shrink["restored"]["step"] == pod["step"]
        # THE bit-exact pin: steps 3..7 of the re-expanded 2-process
        # run == the uninterrupted control, row r per rank
        mine = np.asarray(expand["cont"]).ravel()
        want = np.asarray([control[i][r] for i in range(3, 8)])
        np.testing.assert_array_equal(mine, want)
    rec_b = [r for r in _resize_records(str(out_b / "run.jsonl"))
             if r["new_world"] == 2]
    assert rec_b and rec_b[0]["old_world"] == 1
    assert rec_b[0]["recovery_s"] > 0


def test_inspect_cli_on_pack_checkpoint_dirs(pack):
    """The operator pre-flight on REAL pod artifacts: both the sync
    (wus) and the async (asyncpod) checkpoint dirs of the shared pack
    pass checkpoint_inspect — everything committed, nothing torn, no
    stale staging debris, exit 0."""
    _ranks, out_dir = pack
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "checkpoint_inspect.py"),
         os.path.join(str(out_dir), "ckpts"),
         os.path.join(str(out_dir), "ckpts_async"), "--json"],
        capture_output=True, text=True)
    assert out.returncode == 0, (out.stdout, out.stderr)
    doc = json.loads(out.stdout)
    assert doc["valid"] is True
    assert set(doc["counts"]) == {"committed"}, doc["counts"]
    assert doc["counts"]["committed"] >= 2
    assert doc["stale_tmp"] == []
