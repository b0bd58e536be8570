"""``tools/step_memory.py``: a benchmark cell's training step compiled for a
described v5e, and what it holds.  Slow (a minute and 7 GB a step): outside
the tier-1 gate, for the PR that touches what the long-sequence cells
trace (tests/test_fused_attention_grad.py's pinned programs)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_moonlight_step_holds_the_temporaries_it_held():
    """4,880,808,448 bytes of temporaries at PR 34's tree and at PR 36's
    (18.28544 GB of ``hbm_peak_gb`` on the chip at both); PR 35's tree,
    the row statistics in the multi-pass kernels too, compiled to
    5,376,560,128 here and read 18.5395 GB there, past the cell's bound."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "step_memory.py"),
         "moonlight_ep8share_s4096_train"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 ALLOW_MULTIPLE_LIBTPU_LOAD="1"))
    assert out.returncode == 0, out.stderr[-2000:]
    record = json.loads(out.stdout.strip().splitlines()[-1])
    assert record["temp_size_in_bytes"] == 4880808448, record
