"""Every per-layer metric of ``BENCHMARK.json`` has a reader of its own, and
each reader gives the number its definition says — or nothing where its
source does not exist in the run."""

import os

import pytest

from harness import peaks, spec, trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = spec.load_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
FLASH = "bert-base-uncased-attndrop0"


def reader(name):
    return spec.load_module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                         name + ".py"))


def context(config=FLASH, traced=True, chips=1, **traffic):
    params = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                         config + ".json"))
    params.update(traffic)
    builder = spec.load_module(os.path.join(spec.BENCH_DIR, "configs",
                                            params["builder"]))
    reduced = trace.reduce_trace(os.path.join(HERE, "fixture.xplane.pb"),
                                 [0], steps=0) if traced else None
    return {"params": params, "builder": builder, "chips": chips,
            "peaks": peaks.peaks_for("TPU v5 lite"), "steps": 100,
            "window_s": 20.0, "wait_s": 0.5, "dispatch_s": [0.010] * 100,
            "setup_compile_s": 12.5, "setup_compiles": 3, "trace": reduced}


def test_every_per_layer_metric_has_a_reader_and_a_known_target():
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert callable(reader(m["name"]).read), m["name"]
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", [])) <= cells


def test_host_readers():
    ctx = context(traced=False, seq_len=512, batch=32)
    assert reader("feed_wait_share").read(ctx) == pytest.approx(2.5)
    # 10 ms in exe.run a step, of which 0.5 s / 100 steps waiting for data
    assert reader("dispatch_host_ms").read(ctx) == pytest.approx(5.0)
    assert reader("compile_s").read(ctx) == 12.5
    assert reader("xla_compiles_in_setup").read(ctx) == 3
    for name in ("step_device_ms", "device_idle_share",
                 "flash_attn_ms_per_step", "flash_attn_roofline",
                 "allreduce_ms_per_step"):
        assert reader(name).read(ctx) is None       # untraced: nothing to read


def test_trace_readers_on_the_recorded_trace(capsys):
    ctx = context(seq_len=512, batch=32)
    r = ctx["trace"]
    busy = r.devices[0]["busy_s"]
    assert reader("step_device_ms").read(ctx) == pytest.approx(
        1e3 * busy / 2)
    assert reader("device_idle_share").read(ctx) == pytest.approx(
        100 * (1 - busy / r.devices[0]["window_s"]))
    mosaic_s = r.custom_call_seconds("tpu_custom_call")
    assert reader("flash_attn_ms_per_step").read(ctx) == pytest.approx(
        1e3 * mosaic_s / 2)
    costs = ctx["builder"].kernel_costs(ctx["params"])
    least = max(costs["flops"] / 197e12, costs["bytes"] / 819e9)
    assert reader("flash_attn_roofline").read(ctx) == pytest.approx(
        100 * least / (mosaic_s / 2))
    assert "binds" in capsys.readouterr().out       # says which bound
    assert reader("allreduce_ms_per_step").read(ctx) is None   # one chip


def test_kernel_readers_return_nothing_off_the_flash_path():
    ctx = context("bert-base-uncased", seq_len=128, batch=128)
    assert reader("flash_attn_roofline").read(ctx) is None
    ctx["trace"].devices[0]["ops"] = [
        o for o in ctx["trace"].ops() if o[4] != "tpu_custom_call"]
    assert reader("flash_attn_ms_per_step").read(ctx) is None


def test_all_reduce_reader_on_the_recorded_four_chip_trace():
    """``fixture_dp4.xplane.pb``: two steps of the four-chip data-parallel
    ResNet-50 cell (my chip run, PR 24), cut like the other fixture plus 12
    all-reduces a step."""
    ctx = context("resnet50-v1.5", traced=False, chips=4, batch=1024)
    r = ctx["trace"] = trace.reduce_trace(
        os.path.join(HERE, "fixture_dp4.xplane.pb"), [0, 1, 2, 3], steps=0)
    assert sorted(r.devices) == [0, 1, 2, 3] and r.steps == 2
    assert r.busy_s == pytest.approx(
        sum(d["busy_s"] for d in r.devices.values()) / 4)
    assert r.idle_share() == pytest.approx(
        max(1 - d["busy_s"] / d["window_s"] for d in r.devices.values()))
    reduces = [o for o in r.ops(0) if o[1].startswith("all-reduce")]
    assert len(reduces) == 24
    assert reader("allreduce_ms_per_step").read(ctx) == pytest.approx(
        1e3 * sum(o[3] - o[2] for o in reduces) / 2)
    ctx["chips"] = 1
    assert reader("allreduce_ms_per_step").read(ctx) is None
