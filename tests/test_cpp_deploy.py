"""C++-only train/deploy path: compile + run the embedded-interpreter demo.

Reference: paddle/fluid/train/demo (C++ training driver) and
inference/api/demo_ci (C++ predictor client).  The demo trains fit_a_line,
saves an inference model, then serves it through the C predictor ABI —
all driven from a C++ main().
"""

import os
import subprocess
import sys
import sysconfig
import tempfile

import pytest

_DEPLOY = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "paddle_tpu", "native", "deploy")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cpp_train_deploy_demo():
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    pyver = "python%d.%d" % sys.version_info[:2]
    with tempfile.TemporaryDirectory() as td:
        exe = os.path.join(td, "demo")
        compile_cmd = [
            "g++", "-std=c++17", "-O1",
            os.path.join(_DEPLOY, "predictor_capi.cc"),
            os.path.join(_DEPLOY, "demo.cc"),
            "-I" + inc, "-L" + libdir, "-l" + pyver,
            "-Wl,-rpath," + libdir, "-o", exe]
        cp = subprocess.run(compile_cmd, capture_output=True, text=True,
                            timeout=180)
        assert cp.returncode == 0, cp.stderr
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        rp = subprocess.run([exe, _REPO, td], capture_output=True,
                            text=True, timeout=300, env=env)
        assert rp.returncode == 0, (rp.stdout, rp.stderr)
        assert "train done" in rp.stdout
        assert "C++ train+deploy demo OK" in rp.stdout
        assert os.path.exists(os.path.join(td, "model", "__model__"))
