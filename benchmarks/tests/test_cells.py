"""Every cell of ``BENCHMARK.json`` at its configuration's tiny sizes through
the harness's own functions on the CPU (four virtual devices for the
four-chip cell), the shape of the result object, and that the harness takes
a new cell as data.  A CPU run shows control flow and counts — never a time,
a rate or a utilization."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from harness import loop, spec

BENCH = spec.load_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
END_TO_END = {m["name"] for m in BENCH["end_to_end"]}


def run_tiny(cell, capsys):
    result = loop.run_cell(cell, seed=2 ** 31 + 11, seconds=2.0, trace=False,
                           t_start=time.perf_counter(), tiny=True)
    faults = [ln for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("FAULT: ")]
    return result, faults


@pytest.mark.parametrize("name", CELLS)
def test_cell_at_tiny_sizes(name, capsys):
    cell = spec.load_cell(name)
    result, faults = run_tiny(cell, capsys)
    json.dumps(result)
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert set(result["metrics"]) == END_TO_END
    assert all(set(m) == {"value", "unit"} for m in result["metrics"].values())
    assert result["attempted"] > 2 * cell.params(True)["pool"]
    assert result["failed"] == 0
    # on the CPU the Pallas kernels are interpreted, so a configuration that
    # expects the Mosaic call in its HLO reports that one fault and no other
    expected = ["FAULT: no %r in the compiled step" % w
                for w in cell.builder.expects_in_hlo(cell.params(True))]
    assert faults == expected
    assert result["correct"] == (not expected)


def test_a_new_cell_is_data(tmp_path, capsys):
    """A later PR adds ``traffic/<name>.json`` and a ``workloads`` entry and
    edits no file that is there."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("tests", ".trace",
                                                  "__pycache__"))
    traffic = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                          "s128_b128_loader.json"))
    traffic.update(batch=64, pool=3)
    with open(tmp_path / "benchmarks" / "traffic" / "x_new.json", "w") as f:
        json.dump(traffic, f)
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "x_new_cell", "chips": 1,
                               "config": "bert-base-uncased",
                               "traffic": "x_new", "why": "test"})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell("x_new_cell", str(tmp_path / "BENCHMARK.json"))
    assert cell.params()["batch"] == 64
    assert cell.builder.__file__.startswith(str(tmp_path))
    result, faults = run_tiny(cell, capsys)
    assert result["correct"] and not faults


def test_no_cell_or_configuration_is_named_in_the_harness():
    names = set(CELLS) | {c["name"] for c in BENCH["configs"]} | \
        {w["traffic"] for w in BENCH["workloads"]}
    sources = [os.path.join(spec.BENCH_DIR, "run.py")]
    for sub in ("harness", "wraps", "layer_metrics"):
        folder = os.path.join(spec.BENCH_DIR, sub)
        sources += [os.path.join(folder, f) for f in os.listdir(folder)
                    if f.endswith(".py")]
    for path in sources:
        with open(path) as f:
            text = f.read()
        assert not [n for n in names if n in text], path


def test_unknown_workload_ends_the_run():
    with pytest.raises(SystemExit, match="no workload"):
        spec.load_cell("no_such_cell")


def test_run_py_on_a_cpu_prints_no_result_line():
    """Anything but a TPU ends the run with another code than 0 and no
    result line: there is no CPU fallback."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.lstrip().startswith("{")]
