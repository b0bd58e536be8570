"""Test env: virtual 8-device CPU mesh (SURVEY.md §4 — the reference tests
multi-device entirely on localhost; we mirror that with
xla_force_host_platform_device_count, per the driver's dryrun contract)."""

import os
import sys

# Force the CPU backend with a virtual 8-device mesh, through the
# environment (inherited by every subprocess a test spawns) and through
# jax.config (holds even if something imported jax earlier).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test, excluded from the tier-1 gate "
        "(-m 'not slow')")


# ---------------------------------------------------------------------------
# THE shared 2-process gloo pack.  A real rendezvous costs ~15-30 s
# (jax import + coordinator handshake dominate, not the training
# steps), so the combined parity+int8+wus+asyncpod run executes ONCE
# per session and every consumer across test_multihost / test_elastic /
# test_watchdog reads its per-rank outputs, checkpoint dirs, and
# metrics/span JSONL streams.
# ---------------------------------------------------------------------------

_pack_cache = {}


@pytest.fixture(scope="session")
def pack(tmp_path_factory):
    """The combined 2-process run (mode "all"), executed once per
    session; yields (per-rank outputs, out_dir).  Spans are on so the
    async-pod save's upload/dispatch overlap is provable from the
    JSONL."""
    import mh_harness as mh
    if "ranks" not in _pack_cache:
        out_dir = tmp_path_factory.mktemp("mh_pack")
        ranks = mh.run_pack(
            "all", out_dir, 23000,
            extra_env={"FLAGS_metrics_jsonl": str(out_dir / "run.jsonl"),
                       "FLAGS_trace_spans": "1"})
        _pack_cache["ranks"] = ranks
        _pack_cache["dir"] = out_dir
    return _pack_cache["ranks"], _pack_cache["dir"]


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs + scope (the reference's
    program_guard/scope_guard hygiene)."""
    import paddle_tpu.fluid as fluid
    main = fluid.Program()
    startup = fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup):
        with fluid.scope_guard(scope):
            with fluid.unique_name.guard():
                yield
