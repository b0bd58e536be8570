"""The readers of what the program says of itself (``harness/program_spans.py``
and the twelve ``layer_metrics/`` files over it) on a recorded trace, and the
two idle shares on synthetic intervals.

``fixture_program.xplane.pb``: two steps of the flash cell (my chip run,
PR 26), cut by ``cut_program_xplane.py``: the device lines as in
``fixture.xplane.pb`` plus the program's ``fluid.*`` spans with their labels.
``fixture_program_scopes.json``: ``profiler.step_scopes()`` of the same run,
cut to the instructions the fixture holds.
"""

import os
import shutil

import pytest

from harness import loop, program_spans, spec, trace
from paddle_tpu.fluid import profiler, telemetry

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_program.xplane.pb")
SCOPES = spec.load_json(os.path.join(HERE, "fixture_program_scopes.json"))
BENCH = spec.load_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
NEW = ("feed_stage_ms", "idle_feed_wait_share", "idle_dispatch_share",
       "dispatch_self_ms", "enqueue_ms", "trace_lower_s",
       "step_recompiles_in_setup", "backward_ms_per_step",
       "optimizer_ms_per_step", "flash_fwd_ms_per_step",
       "flash_dq_ms_per_step", "flash_dkv_ms_per_step")
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def reader(name):
    return spec.load_module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                         name + ".py"))


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    """A traced run's context: the stretch's xplane is the newest one under
    ``loop.TRACE_DIR``, the compiled step's names are the program's."""
    where = tmp_path / "cell" / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    shutil.copy(FIXTURE, where / "host.xplane.pb")
    monkeypatch.setattr(loop, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(profiler, "step_scopes", lambda: SCOPES)
    monkeypatch.setattr(program_spans, "_memo", {})
    fluid_scopes = {k: trace.FLUID_SCOPE.search(v).group(0)
                    for k, v in SCOPES.items() if trace.FLUID_SCOPE.search(v)}
    return {"trace": trace.reduce_trace(FIXTURE, [0], steps=0,
                                        scopes=fluid_scopes)}


def test_the_twelve_entries_are_the_ones_this_file_tests():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(NEW) <= set(entries)
    assert [m["name"] for m in BENCH["per_layer"]][-12:] == list(NEW)
    for kernel in KERNELS:
        assert entries[kernel + "_ms_per_step"]["workloads"] == \
            ["bert_base_s512_flash"]
    assert {entries[n]["source"] for n in NEW} == \
        {"program_span", "program_counter", "device_trace"}


def test_spans_of_the_recorded_stretch(ctx):
    found = program_spans.spans(ctx)
    assert found.path.startswith(loop.TRACE_DIR)
    steps = found.named("step")
    assert len(steps) >= 2
    numbers = [labels["step_num"] for _, _, _, labels in steps]
    assert numbers == list(range(numbers[0], numbers[0] + len(steps)))
    for kind in ("feed_wait", "dispatch", "enqueue"):
        spans = found.named(kind)
        assert len(spans) == len(steps), kind
        for (line, start, end, labels), (s_line, s0, s1, s_labels) in \
                zip(spans, steps):
            assert line == s_line and s0 <= start and end <= s1, kind
            assert labels["step"] == s_labels["step_num"]
    for (_, d0, d1, _), (_, e0, e1, _) in zip(found.named("dispatch"),
                                              found.named("enqueue")):
        assert d0 <= e0 and e1 <= d1
    stages = found.named("feed_stage")
    assert stages and {s[0] for s in stages}.isdisjoint(
        {s[0] for s in steps})                       # the worker's thread
    assert all(labels["bytes"] > 0 and "batch" in labels
               for _, _, _, labels in stages)
    # the benchmark's own span around exe.run holds the step: the inside
    # can be held against the outside
    assert len(found.caller_s) == len(steps)
    assert sum(found.caller_s) >= found.seconds("step")
    assert sum(found.caller_s) == pytest.approx(found.seconds("step"),
                                                rel=0.05)


def test_span_readers_on_the_recorded_stretch(ctx, capsys):
    found = program_spans.spans(ctx)
    n = len(found.named("step"))
    enqueue = reader("enqueue_ms").read(ctx)
    own = reader("dispatch_self_ms").read(ctx)
    assert enqueue == pytest.approx(1e3 * found.seconds("enqueue") / n)
    assert 0 < own < enqueue      # on the chip: 3.1 ms against 5.9
    assert own + enqueue == pytest.approx(
        1e3 * (found.seconds("step") - found.seconds("feed_wait")) / n)
    stage = reader("feed_stage_ms").read(ctx)
    assert stage == pytest.approx(
        1e3 * found.seconds("feed_stage") / len(found.named("feed_stage")))
    in_wait = reader("idle_feed_wait_share").read(ctx)
    in_step = reader("idle_dispatch_share").read(ctx)
    r = ctx["trace"]
    idle = 100.0 * (1.0 - r.devices[0]["busy_s"] / r.devices[0]["window_s"])
    assert in_wait >= 0 and in_step >= 0 and in_wait + in_step <= idle
    out = capsys.readouterr().out
    assert out.count("idle by program span:") == 1      # two readers, one pass
    assert "inside against outside" in out and "traced stretch:" in out


def test_role_readers_on_the_recorded_stretch(ctx, capsys):
    r = ctx["trace"]
    roles = program_spans.role_seconds(ctx)
    assert sum(roles.values()) == pytest.approx(
        sum(end - start for _, _, start, end, _ in r.ops()))
    assert roles["bwd"] > roles["fwd"] > roles["opt"] > 0
    assert reader("backward_ms_per_step").read(ctx) == pytest.approx(
        1e3 * roles["bwd"] / r.steps)
    assert reader("optimizer_ms_per_step").read(ctx) == pytest.approx(
        1e3 * roles["opt"] / r.steps)
    # a backward op's instructions sit under role_bwd and nowhere else
    for label, name, start, end, _ in r.ops():
        if label.startswith("fluid_") and label.endswith("_grad"):
            assert "role_bwd" in SCOPES[name], name
    out = capsys.readouterr().out
    assert out.count("step by op role") == 1
    assert "forward remainder" in out and "under no role scope" in out


def test_kernel_readers_on_the_recorded_stretch(ctx):
    r = ctx["trace"]
    by_kernel = {k: reader(k + "_ms_per_step").read(ctx) for k in KERNELS}
    assert all(v > 0 for v in by_kernel.values())
    # the three are the whole of what the older reader lumps together
    assert sum(by_kernel.values()) == pytest.approx(
        1e3 * r.custom_call_seconds("tpu_custom_call") / r.steps)
    assert program_spans.kernel_ms_per_step(ctx, "flash_dbias") == 0.0


def test_a_step_without_role_scopes_or_kernel_names_raises(ctx, monkeypatch):
    stale = {k: v.replace("role_", "r0le_") for k, v in SCOPES.items()}
    monkeypatch.setattr(profiler, "step_scopes", lambda: stale)
    with pytest.raises(RuntimeError, match="stale compilation cache"):
        reader("backward_ms_per_step").read(ctx)
    dev = ctx["trace"].devices[0]
    dev["ops"] = [(label, "branch_1_fun.%d" % i if target else name,
                   start, end, target)
                  for i, (label, name, start, end, target)
                  in enumerate(dev["ops"])]
    with pytest.raises(RuntimeError, match="none is named flash_"):
        reader("flash_dq_ms_per_step").read(ctx)
    # a step with no Mosaic call at all reads 0.0: a reading, not a fault
    dev["ops"] = [o for o in dev["ops"] if not o[4]]
    assert reader("flash_dq_ms_per_step").read(ctx) == 0.0


def test_nothing_from_a_program_without_them(ctx, monkeypatch):
    """The driver lays these files over the parent's checkout: there the
    readers return nothing and do not raise."""
    monkeypatch.delattr(profiler, "step_scopes")
    for name in ("backward_ms_per_step", "optimizer_ms_per_step",
                 "flash_fwd_ms_per_step"):
        assert reader(name).read(ctx) is None
    assert program_spans.compile_counter("no_such_counter_total") is None
    # a trace with no fluid.step (the parent records none)
    plain = os.path.join(loop.TRACE_DIR, "cell", "plugins", "profile", "run",
                         "host.xplane.pb")
    shutil.copy(os.path.join(HERE, "fixture.xplane.pb"), plain)
    program_spans._memo.clear()
    for name in ("enqueue_ms", "dispatch_self_ms", "feed_stage_ms",
                 "idle_feed_wait_share", "idle_dispatch_share"):
        assert reader(name).read(ctx) is None
    # an untraced run
    for name in NEW[:5] + NEW[7:]:
        assert reader(name).read({"trace": None}) is None


def test_compile_counter_readers_read_the_programs_registry():
    seconds = telemetry.registry().counter("xla_compile_seconds_total")
    compiles = telemetry.registry().counter("xla_backend_compiles_total")
    ctx = {"trace": None}           # counters need no trace
    before = reader("trace_lower_s").read(ctx)
    recompiles = reader("step_recompiles_in_setup").read(ctx)
    seconds.inc(1.5, phase="trace", why="dispatch")
    seconds.inc(0.25, phase="lower", why="dispatch")
    seconds.inc(7.0, phase="backend", why="dispatch")       # compile_s's
    seconds.inc(3.0, phase="trace", why="introspection")    # the harness's
    compiles.inc(why="recompile")
    compiles.inc(why="dispatch")
    assert reader("trace_lower_s").read(ctx) == pytest.approx(before + 1.75)
    assert reader("step_recompiles_in_setup").read(ctx) == recompiles + 1


def test_overlap_of_interval_lists():
    a = [(0.0, 1.0), (2.0, 3.0), (2.5, 4.0)]        # merged: (2.0, 4.0)
    b = [(0.5, 2.25), (3.5, 5.0)]
    assert program_spans.overlap_seconds(a, b) == pytest.approx(
        0.5 + 0.25 + 0.5)
    assert program_spans.overlap_seconds(a, []) == 0.0
    assert program_spans.overlap_seconds(a, a) == pytest.approx(3.0)


def test_idle_shares_on_synthetic_intervals():
    """A 10 s window, three steps of 2 s each with a loader wait at the
    start of each; the chip idles 0.5 s inside the first wait, 0.25 s in the
    second step after its wait, 1 s between steps and 0.3 s across the third
    step's wait and what follows it."""
    steps = [(0.0, 2.0), (3.0, 5.0), (6.0, 8.0)]
    waits = [(0.0, 0.6), (3.0, 3.1), (6.0, 6.2)]
    gaps = [(0.1, 0.6), (4.0, 4.25), (5.0, 6.0), (6.1, 6.4)]
    in_wait, in_step = program_spans.idle_shares(gaps, 10.0, steps, waits)
    assert in_wait == pytest.approx(100 * (0.5 + 0.1) / 10.0)
    assert in_step == pytest.approx(100 * (0.25 + 0.2) / 10.0)
    # no gap inside any span: both are 0.0, a reading
    assert program_spans.idle_shares([(2.1, 2.9)], 10.0, steps, waits) == \
        (0.0, 0.0)
    assert program_spans.idle_shares([], 10.0, steps, waits) == (0.0, 0.0)
