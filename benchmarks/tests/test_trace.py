"""The reduction from a profiler trace to device metrics: the interval
arithmetic on synthetic lists, the HLO-text parsing on literal lines, and
the reading on one small recorded trace (``fixture.xplane.pb``: cut by
``cut_xplane.py`` from a chip run of this benchmark, see
``test_recorded_trace``)."""

import os

import pytest

from harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def test_merge_and_union():
    iv = [(5.0, 6.0), (1.0, 3.0), (2.0, 4.0), (4.0, 4.5), (7.0, 7.0)]
    assert trace.merge(iv) == [(1.0, 4.5), (5.0, 6.0)]
    assert trace.union_seconds(iv) == pytest.approx(4.5)
    assert trace.union_seconds([]) == 0.0
    # one inside another counts once
    assert trace.union_seconds([(0.0, 10.0), (2.0, 3.0)]) == 10.0


def test_gaps_are_what_busy_leaves_open():
    busy = [(1.0, 2.0), (3.0, 5.0)]
    assert trace.gaps(busy, 0.0, 6.0) == [(0.0, 1.0), (2.0, 3.0), (5.0, 6.0)]
    assert trace.gaps(busy, 1.0, 5.0) == [(2.0, 3.0)]
    assert trace.gaps([], 0.0, 2.0) == [(0.0, 2.0)]


def test_gap_goes_to_the_span_covering_most_of_it():
    spans = [("bench.exe_run", 0.0, 1.2), ("bench.fence", 1.2, 4.0)]
    assert trace.attribute((1.0, 2.0), spans) == "bench.fence"
    assert trace.attribute((0.5, 1.3), spans) == "bench.exe_run"
    assert trace.attribute((5.0, 6.0), spans) == trace.NO_SPAN


def test_top_sums_by_name_and_ranks():
    pairs = [("a", 1.0), ("b", 5.0), ("a", 2.5), ("c", 0.5)]
    assert trace.top(pairs, n=2) == [["b", 5.0], ["a", 3.5]]


FUSION = ("%convert_reduce_fusion.19 = (f32[512]{0:T(512)S(1)}, bf16[256,512,"
          "28,28]{1,0,3,2:T(8,128)(2,1)}) fusion(bf16[256,512,28,28]{1,0,3,2}"
          " %get-tuple-element.2289), kind=kOutput, calls=%fused_computation")
MOSAIC = ('%custom-call.7 = bf16[384,512,64]{2,1,0} custom-call(bf16[384,512,'
          '64]{2,1,0} %bitcast.1), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={bf16[384,512,64]{2,1,0}}')


def test_parse_op_labels_by_scope_else_by_name_and_kind():
    assert trace.parse_op(FUSION, {}) == \
        ("convert_reduce_fusion/kOutput", "convert_reduce_fusion.19", "")
    assert trace.parse_op(FUSION, {"convert_reduce_fusion.19":
                                   "fluid_conv2d"})[0] == "fluid_conv2d"
    assert trace.parse_op(MOSAIC, {}) == \
        ("custom-call", "custom-call.7", "tpu_custom_call")
    assert trace.parse_op("%copy.1347 = f32[64]{0} copy(f32[64]{0} %p)",
                          {})[:2] == ("copy", "copy.1347")


def test_scope_map_reads_the_fluid_scope_of_each_instruction():
    hlo = "\n".join([
        "ENTRY %main {",
        '  %mul.1 = f32[512,10]{1,0} multiply(%a, %b), metadata={op_name='
        '"jit(fn)/fluid_momentum/mul" stack_frame_id=99}',
        '  ROOT %fusion.3 = bf16[8]{0} fusion(%c), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(fn)/jvp(fluid_conv2d)/conv" source_line=3}',
        '  %copy.2 = f32[8]{0} copy(%d), metadata={op_name="jit(fn)/other"}',
        "  %bare.1 = f32[8]{0} add(%d, %d)",
        "}"])
    assert trace.scope_map(hlo) == {"mul.1": "fluid_momentum",
                                    "fusion.3": "fluid_conv2d"}


def test_recorded_trace():
    """Two steps of ``bert_base_s512_flash`` on one TPU v5 lite (my chip
    run, PR 24), each cut to its first 60 and last 40 operations plus 12
    Mosaic calls: the numbers below were read off that file once and pin
    the reading, not the chip."""
    r = trace.reduce_trace(os.path.join(HERE, "fixture.xplane.pb"), [0],
                           steps=0)
    assert r.steps == 2                      # counted from XLA Modules
    assert sorted(r.devices) == [0]
    d = r.devices[0]
    ops = r.ops()
    assert d["busy_s"] == pytest.approx(
        trace.union_seconds((o[2], o[3]) for o in ops))
    assert 0 < d["busy_s"] < d["window_s"]
    assert d["window_s"] == pytest.approx(max(o[3] for o in ops) -
                                          min(o[2] for o in ops))
    assert 0.0 < r.idle_share() < 1.0
    # the cut left holes in the middle of both steps; the host was in the
    # benchmark's fence while the device ran what was cut out
    gaps = dict(trace.top(r.idle_gaps()))
    assert max(gaps, key=gaps.get) == "bench.fence"
    assert sum(gaps.values()) == pytest.approx(d["window_s"] - d["busy_s"])
    assert {n for n, _, _ in r.host_spans} == {"bench.exe_run",
                                               "bench.fence"}
    mosaic = r.op_seconds(lambda label, name, t: t == "tpu_custom_call")
    assert mosaic == r.custom_call_seconds("tpu_custom_call") > 0
    out = r.breakdown()
    assert set(out) == {"device_ops", "idle_gaps"}
    assert 0 < len(out["device_ops"]) <= 10
    assert all(isinstance(n, str) and len(n) < 64 and s > 0
               for n, s in out["device_ops"])


def test_a_trace_without_device_operations_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path))
    with pytest.raises(RuntimeError, match="no device operation"):
        trace.reduce_trace(os.path.join(HERE, "fixture.xplane.pb"), [7], 2)
