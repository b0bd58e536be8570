"""Chrome-trace export coverage (profiler.py stop_profiler): emitted
traceEvents schema (phase, ts/dur in microseconds, tid propagation), the
file landing at profile_path, the aggregation-table ordering, the
step-event interleave track, and the session lifecycle of RecordEvent
spans (they live in the telemetry ring)."""

import json
import os
import threading
import time

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import profiler, telemetry


def _host_events(trace):
    return [e for e in trace["traceEvents"] if e.get("cat") == "host"]


def test_chrome_trace_schema_and_file(tmp_path):
    telemetry.reset_step_events()    # keep the step track empty here
    profiler.start_profiler()
    with profiler.RecordEvent("outer_span"):
        time.sleep(0.002)
    with profiler.RecordEvent("inner_span"):
        time.sleep(0.001)
    path = str(tmp_path / "prof")
    trace = profiler.stop_profiler(profile_path=path)

    # file actually written at profile_path
    fpath = path + ".chrome_trace.json"
    assert os.path.isfile(fpath)
    on_disk = json.load(open(fpath))
    assert on_disk == trace

    evs = _host_events(trace)
    assert {e["name"] for e in evs} == {"outer_span", "inner_span"}
    for e in evs:
        assert e["ph"] == "X"                        # complete events
        assert isinstance(e["ts"], float)            # µs since origin
        assert isinstance(e["dur"], float) and e["dur"] > 0
        assert e["pid"] == os.getpid()
        assert e["tid"] == threading.get_ident()     # tid propagation
    outer = next(e for e in evs if e["name"] == "outer_span")
    # ts/dur are in MICROseconds: a 2ms sleep must read >= ~2000µs
    assert outer["dur"] >= 1500
    # spans recorded in order on the same timeline
    inner = next(e for e in evs if e["name"] == "inner_span")
    assert inner["ts"] >= outer["ts"] + outer["dur"] - 1e3


def test_chrome_trace_tid_propagation_across_threads(tmp_path):
    """Spans recorded from worker threads (the DataLoader producer case)
    carry their own tid so tracks separate in the viewer."""
    profiler.start_profiler()

    def worker():
        with profiler.RecordEvent("from_worker"):
            time.sleep(0.001)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    with profiler.RecordEvent("from_main"):
        time.sleep(0.001)
    trace = profiler.stop_profiler(profile_path=str(tmp_path / "p"))
    evs = {e["name"]: e for e in _host_events(trace)}
    assert evs["from_main"]["tid"] == threading.get_ident()
    assert evs["from_worker"]["tid"] != evs["from_main"]["tid"]


def test_aggregation_table_ordering(tmp_path, capsys):
    """stop_profiler prints the per-event table sorted by total_ms
    descending (the reference PrintProfiler contract)."""
    profiler.start_profiler()
    for _ in range(2):
        with profiler.RecordEvent("slow_event"):
            time.sleep(0.005)
    with profiler.RecordEvent("fast_event"):
        time.sleep(0.001)
    profiler.stop_profiler(profile_path=str(tmp_path / "p"))
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if "_event" in ln]
    assert len(lines) == 2
    assert lines[0].startswith("slow_event")         # biggest total first
    assert lines[1].startswith("fast_event")
    # calls column aggregates repeats
    assert lines[0].split()[-1] == "2"


def test_step_events_interleave_on_own_track(tmp_path):
    """Executor dispatches recorded while profiling land in the chrome
    trace as cat='step' events on the 'step-events' tid, same µs
    timeline as the host spans."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[3], dtype="float32")
            y = fluid.layers.scale(x, scale=2.0)
    telemetry.reset_step_events()
    exe = fluid.Executor(fluid.CPUPlace())
    profiler.start_profiler()
    with fluid.scope_guard(fluid.Scope()):
        with profiler.RecordEvent("host_work"):
            exe.run(main, feed={"x": np.ones((2, 3), np.float32)},
                    fetch_list=[y])
    trace = profiler.stop_profiler(profile_path=str(tmp_path / "p"))
    steps = [e for e in trace["traceEvents"] if e.get("cat") == "step"]
    assert steps, "no step-event track in the chrome trace"
    ev = steps[-1]
    assert ev["tid"] == "step-events"
    assert ev["ph"] == "X" and ev["dur"] > 0
    assert ev["name"] == "step"
    assert ev["args"]["k"] == 1 and "plan_hit" in ev["args"]
    # same clock as host spans: the dispatch sits inside the host span
    host = next(e for e in _host_events(trace)
                if e["name"] == "host_work")
    assert host["ts"] <= ev["ts"] <= host["ts"] + host["dur"]
    # a window dispatch is named by its K
    telemetry.record_step_event(ts_ns=time.perf_counter_ns(), dur_ns=10,
                                k=4, window=True)
    trace2 = profiler.stop_profiler(profile_path=str(tmp_path / "p2"))
    names = [e["name"] for e in trace2["traceEvents"]
             if e.get("cat") == "step"]
    assert "window[k=4]" in names


def test_trace_export_survives_numpy_fields(tmp_path):
    """Step-event args may carry numpy scalars; the chrome-trace dump
    must degrade like the JSONL exporter, not TypeError away the whole
    trace at session end."""
    telemetry.reset_step_events()
    telemetry.record_step_event(ts_ns=time.perf_counter_ns(), dur_ns=5,
                                step=np.int32(7), k=1)
    profiler.start_profiler()
    path = str(tmp_path / "np_trace")
    profiler.stop_profiler(profile_path=path)
    doc = json.load(open(path + ".chrome_trace.json"))
    ev = next(e for e in doc["traceEvents"] if e.get("cat") == "step")
    assert ev["args"]["step"] == 7
    telemetry.reset_step_events()


def test_start_profiler_clears_previous_events():
    """A fresh session never inherits old spans, and a RecordEvent
    outside any session leaves no ring record at all."""
    telemetry.reset_step_events()
    with profiler.RecordEvent("outside"):
        pass
    assert telemetry.step_events() == []
    profiler.start_profiler()
    with profiler.RecordEvent("stale"):
        pass
    profiler.stop_profiler(profile_path=None)
    profiler.start_profiler()
    with profiler.RecordEvent("fresh"):
        pass
    trace = profiler.stop_profiler(profile_path=None)
    names = [e["name"] for e in _host_events(trace)]
    assert names == ["fresh"]
    profiler.start_profiler()
    with profiler.RecordEvent("dropped"):
        pass
    profiler.reset_profiler()
    assert _host_events(profiler.stop_profiler(profile_path=None)) == []
