"""The bench entry points must stay runnable — the driver executes
bench.py blind at round end, so its protocol pieces get CI coverage."""

import numpy as np
import pytest


def test_timed_steps_protocol():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fluid.timing import timed_steps

    f = jax.jit(lambda x: x * 2.0)
    xs = [jnp.float32(i) for i in range(40)]

    def step(i):
        return [f(xs[i % len(xs)])]

    dt, last = timed_steps(step, steps=30, warmup=2)
    assert dt > 0 and np.isfinite(last)


def test_bench_module_imports_and_constants():
    import bench
    from paddle_tpu.fluid import costmodel

    # ONE peaks table keyed by device_kind; an unknown device is an
    # error, never a default
    assert costmodel.device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="cpu"):
        costmodel.device_peaks("cpu")
    # the --infer reference table mirrors BASELINE.md's published numbers
    assert bench.REF_V100_FP16_MS["vgg16"][1] == 3.32
    assert bench.REF_V100_FP16_MS["resnet50"][128] == 64.52
    assert callable(bench.bench_resnet)
    assert callable(bench.bench_control_resnet)
    assert callable(bench.bench_infer)
    assert callable(bench.bench_bert)


def test_graft_entry_importable():
    import __graft_entry__ as g

    assert callable(g.entry) and callable(g.dryrun_multichip)


def test_bench_window_sweep_surface():
    import bench

    assert callable(bench.bench_hot_path_window)
    assert callable(bench.bench_feed_bound)
    assert callable(bench._emit_error_json)


def test_hot_path_result_carries_metrics_object():
    """bench.py --hot-path emits a final ``metrics`` object in its JSON
    line (telemetry PR): pinned keys so the harness/driver can rely on
    them, with the measured loop provably on the cached-plan path."""
    import json

    import bench

    out = bench.bench_hot_path(steps=5)
    json.dumps(out)                      # the emitted line must serialize
    m = out["metrics"]
    for key in ("plan_hits", "plan_misses", "compiles", "host_syncs",
                "step_events", "dispatch_host_seconds_sum",
                "dispatch_count", "preemptions", "rollbacks",
                "storage_retries", "feed_ring_occupancy",
                "h2d_overlap_frac", "optimizer_state_bytes",
                "comm_bucket_overlap_frac"):
        assert key in m, key
    # optimizer-memory / overlap gauges: absolute, sane regardless of
    # what ran earlier in the process
    assert m["optimizer_state_bytes"] is None or \
        m["optimizer_state_bytes"] > 0
    assert m["comm_bucket_overlap_frac"] is None or \
        0.0 <= m["comm_bucket_overlap_frac"] < 1.0
    # input-pipeline gauges ride every metrics object: absolute values,
    # sane whether or not a feed ring ran earlier in the process
    assert m["feed_ring_occupancy"] is None or m["feed_ring_occupancy"] >= 0
    assert m["h2d_overlap_frac"] is None or \
        0.0 <= m["h2d_overlap_frac"] <= 1.0
    # the metrics are DELTAS over the section baseline, so they speak
    # for this invocation regardless of what ran earlier in the process:
    # exactly two plans built (startup + train step), hits dominate, the
    # measured loop stayed sync-free, every dispatch left a step-event
    assert m["plan_misses"] == 2
    assert m["plan_hits"] > m["plan_misses"]
    assert m["host_syncs"] == 0
    assert m["compiles"] == 2            # startup + the train step
    assert m["step_events"] > 0 and m["dispatch_count"] > 0
    # a healthy bench loop never preempts, rolls back, or retries I/O
    assert m["preemptions"] == 0
    assert m["rollbacks"] == 0
    assert m["storage_retries"] == 0
    # device-cost ledger object (costmodel PR): pinned keys so the
    # harness can diff HLO cost across runs; captured via the AOT path
    # AFTER the metrics delta snapshot, so the pins above are untouched
    cost = out["cost"]
    assert cost is not None
    for key in ("sig", "flops_per_step", "transcendentals",
                "bytes_per_step", "peak_bytes", "argument_bytes",
                "output_bytes", "temp_bytes", "instructions",
                "fusions", "collectives", "estimated_step_s",
                "roofline_peak_flops", "roofline_peak_bytes_per_s"):
        assert key in cost, key
    assert cost["flops_per_step"] > 0
    assert cost["estimated_step_s"] > 0
    assert cost["sig"].endswith(":k1")


def test_telemetry_metrics_helper_keys():
    import bench

    m = bench._telemetry_metrics()
    assert set(m) == {"plan_hits", "plan_misses", "compiles",
                      "host_syncs", "step_events",
                      "dispatch_host_seconds_sum", "dispatch_count",
                      "preemptions", "rollbacks", "storage_retries",
                      "feed_ring_occupancy", "h2d_overlap_frac",
                      "optimizer_state_bytes",
                      "comm_bucket_overlap_frac"}


def test_feed_bound_protocol():
    """bench.py --hot-path --feed-bound: a deliberately input-bound run
    measures starvation/overlap — pinned keys and sane values (the
    consumer must spend most of the wall waiting; the overlap gauge is
    a fraction; the step-events carry data_wait_s)."""
    import json

    import bench

    out = bench.bench_feed_bound(windows=6, K=2, delay_s=0.002)
    json.dumps(out)
    for key in ("metric", "unit", "value", "windows", "k", "depth",
                "generator_delay_s", "wall_s", "wait_s", "wait_frac",
                "data_wait_p50_us", "data_wait_p99_us",
                "h2d_overlap_frac", "feed_ring_occupancy",
                "ring_windows", "metrics"):
        assert key in out, key
    assert out["metric"] == "executor_feed_bound"
    assert out["ring_windows"] == 6
    # feed-bound by construction: waiting dominates the wall, the
    # overlap fraction is a valid fraction well below 1, and the ring
    # never gets ahead of the consumer
    assert out["wait_frac"] > 0.5, out
    assert 0.0 <= out["h2d_overlap_frac"] <= 0.9, out
    # occupancy counts staged windows only (not the end sentinel), so a
    # drained feed-bound run ends at exactly 0
    assert out["feed_ring_occupancy"] == 0, out
    assert out["data_wait_p99_us"] >= out["data_wait_p50_us"] > 0.0
    # the healthy-run contract still holds for the shared metrics block
    assert out["metrics"]["host_syncs"] == 0
    assert out["metrics"]["preemptions"] == 0


def test_self_healing_metric_keys_pinned():
    """The self-healing runtime's metric names are a public monitoring
    surface (dashboards/alerts key on them): pin that importing fluid
    registers every one."""
    import paddle_tpu.fluid  # noqa: F401 — registers the producers

    from paddle_tpu.fluid import telemetry

    reg = telemetry.registry()
    for name in ("preemption_signals_total", "preemption_stops_total",
                 "preemption_requested", "rollback_total",
                 "rollback_last_step", "storage_retry_total",
                 "storage_retry_exhausted_total"):
        assert reg.get(name) is not None, name


def test_bench_chip_mode_on_cpu_backend_is_a_json_error():
    """The harness parses bench stdout's LAST line as JSON — a chip mode
    that finds no TPU must end stdout with {"error": ..., "metric":
    null} and exit non-zero, never time a CPU."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "bench.py", "8", "2"], cwd=repo,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 3
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr
    doc = json.loads(lines[-1])
    assert doc["metric"] is None
    assert "needs a TPU" in doc["error"] and "'cpu'" in doc["error"]


def test_bench_comm_section_keys_and_ratios():
    """bench.py --hot-path grew a ``comm`` section: gradient-allreduce
    wire bytes by precision from the collective_bytes_total counter.
    Pin the keys and the acceptance ratios — int8 (block scales
    included) must sit at <= 0.30x the fp32 payload, bf16 at 0.5x, and
    the a2a int8 mode compresses too."""
    import json

    import bench

    out = bench.bench_comm(steps=2)
    json.dumps(out)
    for key in ("steps", "devices", "grad_numel", "quant_block_size",
                "allreduce_bytes_per_step", "a2a_bytes_per_step",
                "int8_vs_fp32", "bf16_vs_fp32", "a2a_int8_vs_fp32",
                "wus_bytes_per_step", "wus_fp32_vs_allreduce",
                "wus_optimizer_state_bytes", "wus_overlap_frac"):
        assert key in out, key
    ar = out["allreduce_bytes_per_step"]
    assert set(ar) == {"fp32", "bf16", "int8"}
    assert all(v > 0 for v in ar.values()), ar
    # the acceptance criterion: quartered wire bytes, scales included
    assert out["int8_vs_fp32"] <= 0.30, out["int8_vs_fp32"]
    assert abs(out["bf16_vs_fp32"] - 0.5) < 1e-6, out["bf16_vs_fp32"]
    a2a = out["a2a_bytes_per_step"]
    assert a2a["int8"] < 0.5 * a2a["fp32"], a2a
    # weight-update sharding: RS+AG at the allreduce's own wire bytes
    # (the bucket divides the 8-dev ring evenly here — ratio exactly 1),
    # optimizer state sharded (~1/devices of the 2 fp32 Adam moments)
    assert out["wus_fp32_vs_allreduce"] == 1.0, out
    # int8 composition bytes are pinned analytically: each quantized
    # phase moves the same payload the allreduce's matching phase would
    from paddle_tpu.fluid.quantized_collectives import (
        allreduce_wire_bytes, phase_wire_bytes)
    numel = out["grad_numel"]
    assert 2 * phase_wire_bytes(numel, "int8",
                                world_size=out["devices"]) == \
        allreduce_wire_bytes(numel, "int8", world_size=out["devices"])
    moments_full = 2 * 4 * out["grad_numel"]
    assert out["wus_optimizer_state_bytes"] <= \
        moments_full / (out["devices"] / 2.0)
    assert out["wus_overlap_frac"] == 0.0      # one bucket: no headroom
    # byte accounting matches the ONE shared convention exactly —
    # including the ring-padding of the int8 block count
    from paddle_tpu.fluid.quantized_collectives import (
        allreduce_wire_bytes)
    assert ar["fp32"] == allreduce_wire_bytes(out["grad_numel"], "fp32")
    assert ar["int8"] == allreduce_wire_bytes(
        out["grad_numel"], "int8", world_size=out["devices"])


def test_serving_bench_protocol():
    """bench.py --serving: continuous-batching serving vs the naive
    one-request-per-dispatch baseline on open-loop Poisson traffic —
    pinned JSON keys (the driver parses the last stdout line; the
    parseable-error-line-on-failure contract rides bench.main() as for
    every other mode), sane values, and the shape-discipline pin."""
    import json

    import bench

    out = bench.bench_serving(requests=40, qps_levels=(5000.0,))
    json.dumps(out)                      # the emitted line must serialize
    for key in ("metric", "unit", "value", "vs_baseline",
                "vs_baseline_kind", "requests", "max_batch", "buckets",
                "max_wait_ms", "levels", "naive", "speedup_vs_naive",
                "zero_steady_state_recompiles", "batch_occupancy_frac",
                "metrics"):
        assert key in out, key
    assert out["metric"] == "serving_throughput"
    assert out["unit"] == "requests/sec"
    assert out["buckets"] == [1, 2, 4, 8, 16]
    for row in out["levels"] + [out["naive"]]:
        for key in ("offered_qps", "achieved_rps", "wall_s", "p50_ms",
                    "p99_ms", "occupancy", "batches", "recompiles",
                    "rejects", "warmup_s"):
            assert key in row, key
        assert row["achieved_rps"] > 0
        assert row["p99_ms"] >= row["p50_ms"] > 0
        assert 0.0 < row["occupancy"] <= 1.0
        assert row["rejects"] == 0
    # every request answered exactly once per mode, all shapes warm
    assert out["zero_steady_state_recompiles"] is True
    # the naive baseline really is one request per dispatch
    assert out["naive"]["batches"] == out["requests"]
    # the shared metrics block keeps the healthy-run contract
    assert out["metrics"]["preemptions"] == 0


def test_serving_metric_names_pinned():
    """The serving runtime's metric names are a public monitoring
    surface (the scrape endpoint exposes them to dashboards): pin that
    importing fluid registers every one."""
    import paddle_tpu.fluid  # noqa: F401 — registers the producers

    from paddle_tpu.fluid import telemetry

    reg = telemetry.registry()
    for name in ("serving_requests_total", "serving_responses_total",
                 "serving_rejects_total", "serving_recompiles_total",
                 "serving_batches_total", "serving_padded_rows_total",
                 "serving_errors_total", "serving_cancelled_total",
                 "serving_queue_depth",
                 "serving_batch_occupancy_frac",
                 "serving_queue_wait_seconds", "serving_compute_seconds"):
        assert reg.get(name) is not None, name


def test_step_event_comm_fields_in_schema():
    """Step events carry per-dispatch comm_bytes / comm_by for programs
    with explicit collectives, and 0/None for plain programs — pinned
    here because tools/metrics_report.py keys on them."""
    import bench
    from paddle_tpu.fluid import telemetry

    bench.bench_comm(steps=1)
    evs = [e for e in telemetry.step_events() if not e.get("kind")]
    assert evs
    assert all("comm_bytes" in e for e in evs), evs[-1]
    with_comm = [e for e in evs if e["comm_bytes"]]
    assert with_comm, "no dispatch recorded collective traffic"
    e = with_comm[-1]
    assert isinstance(e["comm_by"], dict) and e["comm_by"]
    assert sum(e["comm_by"].values()) == e["comm_bytes"]


def test_multihost_bench_keys_pinned():
    """bench.py --hot-path --multihost N artifact keys, pinned for the
    harness/driver.  The key set is checked WITHOUT a pack spawn; the
    real 2-process run is the slow pin below."""
    import bench

    assert callable(bench.bench_multihost)
    assert callable(bench._multihost_worker)
    want = {"metric", "unit", "value", "processes", "steps",
            "steps_per_run", "per_process_us_per_step",
            "per_process_allreduce_bytes", "allreduce_bytes_total",
            "plan_hit_rate"}
    assert set(bench.MULTIHOST_RESULT_KEYS) == want


@pytest.mark.slow
def test_multihost_bench_real_two_process_run():
    """A REAL 2-process --multihost artifact: every pinned key present,
    per-process vectors sized to the pack, allreduce bytes symmetric
    across processes and summed, plan hit-rate 1.0 (every measured
    dispatch rides the shared dispatch-plan cache)."""
    import bench

    out = bench.bench_multihost(nproc=2, steps=30)
    for key in bench.MULTIHOST_RESULT_KEYS:
        assert key in out, key
    assert len(out["per_process_us_per_step"]) == 2
    assert len(out["per_process_allreduce_bytes"]) == 2
    b0, b1 = out["per_process_allreduce_bytes"]
    assert b0 == b1 > 0
    assert out["allreduce_bytes_total"] == b0 + b1
    assert out["plan_hit_rate"] == 1.0
    assert out["value"] == max(out["per_process_us_per_step"]) > 0
