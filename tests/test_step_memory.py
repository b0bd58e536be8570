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


# cell -> ``temp_size_in_bytes`` of its step compiled for a described v5e
STEP_TEMPORARIES = {
    # PR 34's tree and PR 36's (18.28544 GB of ``hbm_peak_gb`` on the chip
    # at both); PR 35's tree, the row statistics in the multi-pass kernels
    # too, compiled to 5,376,560,128 here and read 18.5395 GB there, past
    # the cell's bound
    "moonlight_ep8share_s4096_train": 4880808448,
    # PR 38: the kernels read Q, K, V and dO as [B, S, H * D] in place;
    # with the head split (36 padded copies kept for the backward) the
    # step held 9,866,375,680
    "bert_base_s512_flash": 8172316672,
}


@pytest.mark.slow
@pytest.mark.parametrize("cell", sorted(STEP_TEMPORARIES))
def test_step_holds_the_temporaries_it_held(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "step_memory.py"), cell],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 ALLOW_MULTIPLE_LIBTPU_LOAD="1"))
    assert out.returncode == 0, out.stderr[-2000:]
    record = json.loads(out.stdout.strip().splitlines()[-1])
    assert record["temp_size_in_bytes"] == STEP_TEMPORARIES[cell], record


HLO = '''HloModule jit_fn

%fused_computation.1 (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  ROOT %t = bf16[8,8]{1,0} tanh(%p)
}

ENTRY %main (a.1: bf16[8,8], b.1: bf16[8,8]) -> (bf16[8,8], bf16[8,8]) {
  %a.1 = bf16[8,8]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="a"}
  %b.1 = bf16[8,8]{1,0:T(8,128)(2,1)} parameter(1), metadata={op_name="b"}
  %copy.3 = bf16[8,8]{1,0:T(8,128)(2,1)S(1)} copy(%a.1), backend_config={}
  %copy-start.2 = (bf16[8,8]{1,0}, bf16[8,8]{1,0:S(1)}, u32[]{:S(2)}) copy-start(%mul_fusion), cross_program_prefetch_index=0
  %mul_fusion = bf16[8,8]{1,0} fusion(%copy.3, %b.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(fn)/role_fwd/fluid_mul/dot_general" stack_frame_id=4}
  %loop_body = bf16[8,8]{1,0} fusion(%mul_fusion), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fn)/role_bwd/ut_loop/fluid_mul_grad/transpose(jvp(fluid_mul))/dot_general"}
  ROOT %out = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) tuple(%loop_body, %copy.3)
}
'''

BUFFERS = '''BufferAssignment:
allocation 0: size 128, parameter 0, shape |bf16[8,8]| at ShapeIndex {}:
 value: <1 a.1 @0> (size=128,offset=0): bf16[8,8]{1,0}
allocation 1: size 512, preallocated-temp:
 value: <2 mul_fusion @0> (size=128,offset=0): bf16[8,8]{1,0}
 value: <3 copy-start.2{0} @0> (size=128,offset=0): bf16[8,8]{1,0}
 value: <4 loop_body @0> (size=256,offset=128): bf16[8,8]{1,0}
 value: <5 copy.3 @0> (size=128,offset=384): bf16[8,8]{1,0}
allocation 2: size 4096, color 1, preallocated-temp:
 value: <6 copy.3 @1> (size=4096,offset=0): bf16[8,8]{1,0}

Total bytes used: 4736 (4.6KiB)

Used values:
<2 mul_fusion @0>
 positions:
  mul_fusion
'''


def test_by_op_groups_the_temp_allocations_buffers_by_scope(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "step_memory", os.path.join(ROOT, "tools", "step_memory.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    hlo = tmp_path / "module_0001.jit_fn.after_optimizations.txt"
    buffers = tmp_path / \
        "module_0001.jit_fn.after_optimizations-buffer-assignment.txt"
    hlo.write_text(HLO)
    buffers.write_text(BUFFERS)
    scopes = tool.instruction_scopes(str(hlo))
    assert scopes["mul_fusion"] == "role_fwd/fluid_mul"
    # the first role_* and the first fluid_*: the instruction's own op
    assert scopes["loop_body"] == "role_bwd/fluid_mul_grad"
    # what XLA made itself takes the scope of what it copies
    assert scopes["copy-start.2"] == "role_fwd/fluid_mul (via copy-start)"
    assert scopes["copy.3"] == "(argument) (via copy)"
    # the HBM allocation alone: the colored one is on-chip memory
    allocations = tool.temp_buffers(str(buffers))
    assert list(allocations) == [1] and allocations[1][0] == 512
    table = tool.by_scope(allocations, scopes)
    # two buffers share bytes 0-128: half each; the shares sum to the bytes
    assert table["role_fwd/fluid_mul"] == [64.0, 128, 1]
    assert table["role_fwd/fluid_mul (via copy-start)"] == [64.0, 128, 1]
    assert table["role_bwd/fluid_mul_grad"] == [256.0, 256, 1]
    assert table["(argument) (via copy)"] == [128.0, 128, 1]
    assert sum(row[0] for row in table.values()) == 512
