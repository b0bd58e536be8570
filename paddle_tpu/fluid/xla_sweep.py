"""Whole-model conv-lowering sweep for the ResNet ceiling (ROADMAP S2):
each configuration runs ``bench.py <batch> <steps> --resnet-only
--no-control`` in a fresh subprocess and the JSON line is collected.
The levers are the FRAMEWORK lowering flags (FLAGS_conv_im2col /
conv_layout / conv_pallas — they provably change the emitted HLO) plus
one XLA_FLAGS row.  This parent never touches JAX — each child owns the
chip in turn.  Errors are captured per row, never fatal.

Run: python -m paddle_tpu.fluid.xla_sweep [batch] [steps]
One JSON row per config, streamed.
"""

import json
import os
import subprocess
import sys

# repo root derived from this file (…/paddle_tpu/fluid/xla_sweep.py)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Sweep rows: the framework lowering flags, plus one row that sets a
# libtpu flag (a `--xla_tpu_*` flag parses only where libtpu registered
# its flag set in-process; elsewhere that row records the parse error).
SWEEP = [
    ("baseline", ""),
    ("im2col_3x3", "", {"FLAGS_conv_im2col": "3x3"}),
    ("im2col_all", "", {"FLAGS_conv_im2col": "all"}),
    ("nhwc_layout", "", {"FLAGS_conv_layout": "NHWC"}),
    ("nhwc_plus_im2col", "", {"FLAGS_conv_layout": "NHWC",
                              "FLAGS_conv_im2col": "3x3"}),
    ("pallas_conv3x3", "", {"FLAGS_conv_pallas": "1"}),
    ("tpu_flag_vmem_64m", "--xla_tpu_scoped_vmem_limit_kib=65536"),
]


def run_one(name, xla_flags, env_extra=None, batch=256, steps=8):
    env = dict(os.environ)
    if xla_flags:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " +
                            xla_flags).strip()
    env.update(env_extra or {})
    cmd = [sys.executable, "bench.py", str(batch), str(steps),
           "--resnet-only", "--no-control"]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=1500, env=env, cwd=_REPO_ROOT)
    except subprocess.TimeoutExpired:
        return {"config": name, "error": "timeout"}
    line = (out.stdout.strip().splitlines() or [""])[-1]
    try:
        data = json.loads(line)
        return {"config": name, "img_s": data.get("value"),
                "mfu_est": data.get("resnet50_mfu_est")}
    except Exception:
        return {"config": name, "rc": out.returncode,
                "error": (out.stderr or out.stdout)[-300:]}


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    best = None
    for entry in SWEEP:
        name, flags_ = entry[0], entry[1]
        env_extra = entry[2] if len(entry) > 2 else None
        row = run_one(name, flags_, env_extra, batch, steps)
        print(json.dumps(row), flush=True)
        if isinstance(row.get("img_s"), (int, float)):
            if best is None or row["img_s"] > best["img_s"]:
                best = row
    if best:
        print(json.dumps({**best, "config": "BEST",
                  "best_config": best["config"]}),
              flush=True)


if __name__ == "__main__":
    main()
