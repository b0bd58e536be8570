"""Pod-level tracing (ISSUE 16): telemetry spans, the cross-process
Chrome-trace merge (tools/pod_trace.py), straggler attribution, the
per-link-class ``collective_bytes_total{axis}`` split, and the
tier-1 test-time budget tool.

Pins:
- span-OFF path: bit-exact losses, ZERO added host syncs, zero span
  records — observability must cost nothing when off;
- two doctored per-process streams (one torn line) merge into ONE trace
  with ranks on distinct tracks, a HAND-COMPUTED barrier-entry skew,
  hang/resize lifecycle markers on the same timeline, and the torn line
  skipped-and-counted;
- the live 2-process × 2-device gloo pack (hierarchical nnodes=2): one
  merged trace, rank 1 (its consensus entry parked ~0.35 s by a
  released ``faultinject.hang_at``) named straggler with ≥0.25 s skew,
  and bytes split across BOTH the 'ici' and 'dcn' axis labels;
- ``telemetry.set_process_index`` re-suffixes an already-open JSONL
  stream on identity change;
- tools/test_budget.py flags duration regressions against the
  checked-in baseline.
"""

import json
import os
import sys

import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import flags, profiler, telemetry

import dist_multihost_worker as worker_mod
import mh_harness
import test_multihost as mh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import pod_trace  # noqa: E402
import metrics_report as mr  # noqa: E402
import test_budget as budget_tool  # noqa: E402


# ---------------------------------------------------------------------------
# Span layer: off = free, on = wall-anchored records
# ---------------------------------------------------------------------------

def _train4(jsonl_path):
    """4 dp steps of the shared worker program on this process's
    devices; returns (losses, host-sync delta)."""
    flags.set_flag("metrics_jsonl", jsonl_path)
    try:
        main_p, startup_p, loss = worker_mod.build_program(rank=0,
                                                           nranks=2)
        feeds = worker_mod.make_feeds(steps=4)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup_p)
            s0 = profiler.host_sync_count()
            out = [worker_mod.fetch_rows(
                exe.run(main_p, feed=f, fetch_list=[loss],
                        return_numpy=False)[0]) for f in feeds]
            syncs = profiler.host_sync_count() - s0
    finally:
        flags.set_flag("metrics_jsonl", "")
    return out, syncs


def _load_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


def test_spans_off_bit_exact_no_syncs_no_records(tmp_path):
    """The acceptance guarantee: FLAGS_trace_spans off (the default)
    adds NO host syncs and NO records, and turning spans on does not
    perturb the math — losses bit-exact either way."""
    off_path = str(tmp_path / "off.jsonl")
    on_path = str(tmp_path / "on.jsonl")
    off, syncs_off = _train4(off_path)
    telemetry.enable_spans()
    try:
        on, syncs_on = _train4(on_path)
    finally:
        telemetry.enable_spans(False)
    assert on == off                       # bit-exact, spans on or off
    assert syncs_on == syncs_off           # zero ADDED host syncs
    assert not any(e.get("kind") == "span"
                   for e in _load_jsonl(off_path))
    spans = [e for e in _load_jsonl(on_path) if e.get("kind") == "span"]
    assert spans, "span records missing with spans enabled"
    # every span carries the cross-process clock bridge + duration
    assert all("wall_ns" in e and "dur_ns" in e and "ts_ns" in e
               for e in spans)
    assert any(e["span"] == "dispatch" for e in spans)


def test_set_process_index_resuffixes_open_jsonl_stream(tmp_path):
    """Identity change while the JSONL handle is open (elastic resize
    re-init) must close + re-suffix the stream: records never keep
    landing in the old rank's file."""
    base = str(tmp_path / "ev.jsonl")
    flags.set_flag("metrics_jsonl", base)
    try:
        telemetry.set_process_index(0, 2)
        telemetry.record_step_event(step=1, ts_ns=1, dur_ns=1)
        telemetry.set_process_index(1, 2)   # resize: rank 0 -> rank 1
        telemetry.record_step_event(step=2, ts_ns=2, dur_ns=1)
        telemetry.set_process_index(None)   # back to single-process
        telemetry.record_step_event(step=3, ts_ns=3, dur_ns=1)
    finally:
        flags.set_flag("metrics_jsonl", "")
        telemetry.set_process_index(None)
    assert [e["step"] for e in _load_jsonl(base + ".p0")] == [1]
    assert [e["step"] for e in _load_jsonl(base + ".p1")] == [2]
    assert [e["step"] for e in _load_jsonl(base)] == [3]


# ---------------------------------------------------------------------------
# Doctored-stream merge: hand-computable skew, torn lines, lifecycle
# ---------------------------------------------------------------------------

def _write_doctored(tmp_path):
    """Two per-process streams with a hand-computable geometry: rank 0
    anchors wall=1.0 s at its barrier entry, rank 1 wall=1.3 s at the
    SAME barrier -> skew exactly 300 ms, straggler rank 1.  Rank 1's
    stream ends in a torn line (killed mid-write)."""
    base = str(tmp_path / "run.jsonl")
    r0 = [
        {"kind": "span", "span": "barrier", "name": "sync", "k": 0,
         "ts_ns": 500, "dur_ns": 100_000, "wall_ns": 1_000_000_000,
         "pidx": 0},
        {"step": 1, "k": 1, "ts_ns": 600, "dur_ns": 1000, "pidx": 0},
        {"kind": "hang", "phase": "dispatch", "ts_ns": 700, "dur_ns": 0,
         "k": 0, "pidx": 0},
    ]
    r1 = [
        {"kind": "span", "span": "barrier", "name": "sync", "k": 0,
         "ts_ns": 9999, "dur_ns": 50_000, "wall_ns": 1_300_000_000,
         "pidx": 1},
        {"kind": "resize", "old_world": 2, "new_world": 1, "ts_ns": 12000,
         "dur_ns": 0, "k": 0, "pidx": 1},
    ]
    with open(base + ".p0", "w") as f:
        for e in r0:
            f.write(json.dumps(e) + "\n")
    with open(base + ".p1", "w") as f:
        for e in r1:
            f.write(json.dumps(e) + "\n")
        f.write('{"kind": "span", "span": "barr')   # torn final line
    return base


def test_doctored_streams_merge_skew_and_lifecycle(tmp_path):
    base = _write_doctored(tmp_path)
    by_rank, skipped = pod_trace.merge_streams([base])
    assert sorted(by_rank) == [0, 1]
    assert skipped == 1                    # the torn line: counted
    trace = pod_trace.build_trace(by_rank, skipped=skipped)
    od = trace["otherData"]
    assert od["ranks"] == [0, 1] and od["skipped_lines"] == 1
    # ranks land on DISTINCT Chrome-trace processes, both named
    metas = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M"}
    assert metas == {"rank 0", "rank 1"}
    assert {e["pid"] for e in trace["traceEvents"]
            if e.get("ph") == "X"} == {0, 1}
    # hand-computed skew: 1.3 s - 1.0 s at the one shared barrier
    [b] = od["boundary_skews"]
    assert (b["span"], b["boundary"], b["seq"]) == ("barrier", "sync", 0)
    assert b["skew_ns"] == 300_000_000
    assert b["straggler"] == 1 and od["straggler"] == 1
    assert b["entries"] == {0: 1_000_000_000, 1: 1_300_000_000}
    # lifecycle markers ride the SAME merged timeline as the spans:
    # rank 0's hang at local ts 700 with offset (1e9 - 500) rebases to
    # exactly 200 ns after t0 = 0.2 us
    hang = [e for e in trace["traceEvents"] if e["name"] == "hang"]
    assert len(hang) == 1 and hang[0]["ph"] == "i"
    assert hang[0]["pid"] == 0 and hang[0]["ts"] == pytest.approx(0.2)
    resize = [e for e in trace["traceEvents"] if e["name"] == "resize"]
    assert len(resize) == 1 and resize[0]["pid"] == 1
    # the human-readable report names the straggler
    report = pod_trace.format_skew_report(trace)
    assert "p1" in report and "1 torn line(s) skipped" in report


def test_pod_trace_cli_writes_trace(tmp_path):
    base = _write_doctored(tmp_path)
    out = str(tmp_path / "merged.json")
    assert pod_trace.main([base, "-o", out]) == 0
    trace = json.load(open(out))
    assert trace["otherData"]["straggler"] == 1
    assert pod_trace.main([str(tmp_path / "nope.jsonl")]) == 1


def test_unanchored_rank_rides_sibling_offset(tmp_path):
    """A stream with NO span records can't bridge its clock — it must
    ride the other ranks' median offset (and be called out), never
    crash the merge."""
    base = _write_doctored(tmp_path)
    with open(base + ".p2", "w") as f:
        f.write(json.dumps({"step": 9, "k": 1, "ts_ns": 100,
                            "dur_ns": 10, "pidx": 2}) + "\n")
    by_rank, skipped = pod_trace.merge_streams([base])
    trace = pod_trace.build_trace(by_rank, skipped=skipped)
    assert trace["otherData"]["clock_unanchored_ranks"] == [2]
    assert "no span records" in pod_trace.format_skew_report(trace)


def test_metrics_report_stragglers_section(tmp_path):
    """metrics_report.py over the same streams: the stragglers section
    carries per-boundary skew percentiles + the worst-rank histogram."""
    base = _write_doctored(tmp_path)
    events, skipped = [], 0
    for p in (base + ".p0", base + ".p1"):
        evs, sk = mr.load_events_counted(p)
        events += evs
        skipped += sk
    assert skipped == 1
    rows = mr.summarize(events)
    st = rows["stragglers"]
    assert st["boundaries"]["sync"]["count"] == 1
    assert st["boundaries"]["sync"]["p50_skew_us"] == \
        pytest.approx(300_000.0)
    assert st["worst_rank_counts"] == {"1": 1}
    assert st["worst_rank"] == "1"
    text = mr.format_report(rows)
    assert "sync" in text and "worst rank" in text


# ---------------------------------------------------------------------------
# tools/test_budget.py: the tier-1 duration budget
# ---------------------------------------------------------------------------

_LOG = """\
========== slowest 20 durations ==========
12.00s call     tests/test_a.py::test_slow
2.50s setup    tests/test_a.py::test_slow
0.50s call     tests/test_b.py::test_fast
5.00s call     tests/test_c.py::test_new
"""


def test_budget_parse_and_diff():
    cur = budget_tool.parse_durations(_LOG)
    # setup/teardown phases are fixture costs, not test budgets
    assert cur == {"tests/test_a.py::test_slow": 12.0,
                   "tests/test_b.py::test_fast": 0.5,
                   "tests/test_c.py::test_new": 5.0}
    baseline = {"tests/test_a.py::test_slow": 2.0,
                "tests/test_b.py::test_fast": 0.4}
    regs, new = budget_tool.diff(cur, baseline, ratio=1.5, slack_s=1.0)
    # 12.0 > 1.5*2.0 + 1.0 = 4.0 -> regression; 0.5 < 1.6 -> fine
    assert [r[0] for r in regs] == ["tests/test_a.py::test_slow"]
    assert regs[0][3] == pytest.approx(4.0)
    # baseline-absent test over ratio*slack -> flagged as new-slow
    assert [n[0] for n in new] == ["tests/test_c.py::test_new"]


def test_budget_cli_update_then_strict_pass(tmp_path):
    log = tmp_path / "tier1.log"
    log.write_text(_LOG)
    baseline = str(tmp_path / "baseline.txt")
    assert budget_tool.main([str(log), "--baseline", baseline,
                             "--update"]) == 0
    loaded = budget_tool.load_baseline(baseline)
    assert loaded["tests/test_a.py::test_slow"] == 12.0
    # same log vs its own baseline: within budget, strict passes
    assert budget_tool.main([str(log), "--baseline", baseline,
                             "--strict"]) == 0
    # a 10x regression fails --strict but stays warn-only by default
    slow = tmp_path / "slow.log"
    slow.write_text("120.00s call    tests/test_a.py::test_slow\n")
    assert budget_tool.main([str(slow), "--baseline", baseline,
                             "--strict"]) == 1
    assert budget_tool.main([str(slow), "--baseline", baseline]) == 0


def test_checked_in_tier1_baseline_loads():
    """The baseline the verify recipe diffs against exists and parses."""
    path = os.path.join(REPO, "tests", "tier1_durations_baseline.txt")
    baseline = budget_tool.load_baseline(path)
    assert baseline, "tests/tier1_durations_baseline.txt missing/empty"
    assert all(v >= 0 for v in baseline.values())


# ---------------------------------------------------------------------------
# The live 2-process pack: merged trace + straggler + axis split
# ---------------------------------------------------------------------------

def test_trace_pack_straggler_and_axis_split(tmp_path):
    """ISSUE 16 acceptance: a genuine 2-process (× 2 virtual devices)
    hierarchical run produces ONE merged Chrome trace with per-rank
    tracks, names the injected slow rank (released hang_at park at its
    consensus entry) as the straggler, and splits
    collective_bytes_total across BOTH hierarchy axis labels."""
    out_dir = tmp_path / "mh_trace"
    out_dir.mkdir()
    jsonl = str(out_dir / "run.jsonl")
    ranks = mh_harness.run_pack("trace", out_dir, 26000, extra_env={
        "FLAGS_metrics_jsonl": jsonl,
        "FLAGS_trace_spans": "1",
        # 2 virtual CPU devices per proc -> a (dcn=2, ici=2) mesh, so
        # BOTH link classes of the hierarchical ring are exercised
        "PADDLE_COORDINATOR_DEVICES_PER_PROC": "2",
    })
    for r in ranks:
        assert r["devices"] == 4
        ba = r["bytes_by_axis"]
        # the per-link-class split: both axis labels carry traffic, and
        # the innermost (ici) ring moves more bytes than the
        # cross-process (dcn) hop — the whole point of going hierarchical
        assert ba["ici"] > 0 and ba["dcn"] > 0
        assert ba["ici"] > ba["dcn"]
        assert sum(ba.values()) == r["bytes_total"]
    trace_path = str(out_dir / "pod.trace.json")
    assert pod_trace.main([jsonl, "-o", trace_path]) == 0
    trace = json.load(open(trace_path))
    od = trace["otherData"]
    assert od["ranks"] == [0, 1]
    assert od["skipped_lines"] == 0
    assert od["clock_unanchored_ranks"] == []
    # per-rank tracks with real span content on each
    for rank in (0, 1):
        names = {e["name"] for e in trace["traceEvents"]
                 if e.get("pid") == rank and e.get("ph") == "X"}
        assert "span:barrier" in names and "span:consensus" in names
        assert "span:dispatch" in names
    # straggler attribution: rank 1 parked ~0.35 s at consensus entry;
    # the skew survives the cross-process clock bridge
    cons = [b for b in od["boundary_skews"] if b["span"] == "consensus"]
    assert cons, od["boundary_skews"]
    worst = max(cons, key=lambda b: b["skew_ns"])
    assert worst["straggler"] == 1
    assert worst["skew_ns"] >= 250_000_000, worst
    assert od["straggler"] == 1
    report = pod_trace.format_skew_report(trace)
    assert "straggler" in report and "p1" in report
