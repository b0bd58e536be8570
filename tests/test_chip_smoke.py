"""chip_smoke.py and the rules it stands on, as far as a CPU can check them:
the smoke refuses a CPU, its dry run passes, an explicit TPUPlace never
lands on a CPU device, importing the entry modules initialises no backend
(a launcher parent must not hold the chip), and the compile cache is placed
from outside."""

import json
import os
import subprocess
import sys

import pytest

import paddle_tpu.fluid as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    return subprocess.run([sys.executable] + args, cwd=REPO,
                          env=dict(os.environ, **env), capture_output=True,
                          text=True, timeout=300)


def test_smoke_refuses_a_cpu_and_prints_no_result():
    proc = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "platform='cpu'" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines()), \
        proc.stdout


def test_smoke_dry_run_passes_at_tiny_sizes(capsys):
    import chip_smoke

    chip_smoke.main(["--dry-run-cpu"])
    out = capsys.readouterr().out
    assert "DRY RUN" in out
    # a dry run ends with its summary and prints no result line: the
    # driver reads `{"ok": ..., "device": ...}` only from a chip run
    last = out.strip().splitlines()[-1]
    assert last.startswith("dry-run summary: ")
    assert not any(ln.startswith("{") for ln in out.splitlines()), out
    doc = json.loads(last[len("dry-run summary: "):])
    assert doc["device"]["platform"] == "cpu" and doc["native"] is True
    assert doc["phases"] == {"device": "pass", "resnet50": "pass",
                             "bert": "pass", "kernels": "pass",
                             "flash_dropout": "pass"}
    # a program-bound loader moving from host batches to staged ones
    # compiles nothing again, in the executor or under it in jit
    assert doc["informational"]["resnet50"][
        "xla_compiles_after_first_step"] == 0


def test_smoke_result_line_is_exactly_ok_and_device(monkeypatch, capsys):
    """The driver reads the last stdout line of a chip run and takes
    nothing but `ok` and the device as JAX reports it; the per-phase
    detail is on the `summary:` line before it."""
    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "phase_device",
                        lambda dry_run, chips: (device, [None]))
    for name in ("phase_resnet", "phase_bert", "phase_kernels",
                 "phase_flash_dropout"):
        monkeypatch.setattr(chip_smoke, name, lambda *a: {})
    chip_smoke.main([])
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].startswith("summary: ")
    assert json.loads(lines[-2][len("summary: "):])["phases"] == {
        "device": "pass", "resnet50": "pass", "bert": "pass",
        "kernels": "pass", "flash_dropout": "pass"}


def test_explicit_tpu_place_never_resolves_to_a_cpu():
    with pytest.raises(RuntimeError, match="needs a TPU.*'cpu'"):
        fluid.Executor(fluid.TPUPlace())
    # no place = the default backend's first device, what JAX would pick
    assert fluid.Executor()._device.platform == "cpu"


def test_importing_entry_modules_initialises_no_backend():
    code = (
        "import paddle_tpu, paddle_tpu.fluid, paddle_tpu.distributed.launch\n"
        "import bench, chip_smoke\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, list(xb._backends)\n")
    proc = _run(["-c", code])
    assert proc.returncode == 0, proc.stderr


def test_compile_cache_dir_comes_from_the_environment(monkeypatch):
    import jax
    from paddle_tpu.fluid import executor

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    fluid.Executor(fluid.CPUPlace())
    assert jax.config.jax_compilation_cache_dir == before

    # unset: only a TPU executor gets the fixed <checkout>/.jax_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))

    class Dev:
        platform = "cpu"
    # whatever cache serves, its key covers the scope names (metadata)
    # (scopes and the lowering rule's line, not the caller's stack)
    keyed = [("jax_compilation_cache_include_metadata_in_key", True),
             ("jax_traceback_in_locations_limit", 1)]
    executor.maybe_enable_compile_cache(Dev)
    assert updates == keyed
    Dev.platform = "tpu"
    executor.maybe_enable_compile_cache(Dev)
    assert updates[2:] == keyed + [("jax_compilation_cache_dir",
                                    os.path.join(REPO, ".jax_cache"))]


def test_launcher_refuses_several_plain_processes_on_a_tpu_host(
        monkeypatch, capsys):
    from paddle_tpu.distributed import launch

    args = launch.parse_args(["--nproc_per_node", "4", "train.py"])
    for platforms in ("", "tpu", "tpu,cpu"):
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        assert launch.launch(args) == 2
        assert "ONE process drives all local chips" in \
            capsys.readouterr().err
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not launch._children_may_claim_tpu()
