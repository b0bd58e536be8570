#!/usr/bin/env python
"""What a benchmark cell's training step holds, without the chip: the
step compiled for a described v5e (XLA:TPU and Mosaic run here; nothing
runs on a device) and its ``memory_analysis()``.

Usage:
    python tools/step_memory.py CELL [--dump DIR] [--hlo FILE]

    python tools/step_memory.py moonlight_ep8share_s4096_train

Prints one JSON line: ``temp_size_in_bytes`` beside the argument, output,
alias and code sizes.  ``temp_size_in_bytes`` is the step's temporaries;
``--dump DIR`` keeps XLA's dump, whose ``*memory-usage-report.txt`` names
the ``preallocated-temp`` allocation, which is the chip's
``peak_bytes_reserved`` (Moonlight: 4.27 GiB = 4,590,141,440 in the cell's
log), the half of ``hbm_peak_gb`` that a change to the program can move
through XLA's schedule alone (PERF.md section 6, PRs 35 and 36).  ``--hlo
FILE`` writes the optimized module's text, to set two trees' steps side by
side.  To read another tree, run that tree's copy of this file.

A step takes about a minute and 7 GB of host memory (Moonlight 45-75 s,
the flash cell 55 s): one at a time on a shared sandbox.  The cell is
built as ``benchmarks/harness/loop.run_cell`` builds it.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = ("argument_size_in_bytes", "output_size_in_bytes",
         "temp_size_in_bytes", "alias_size_in_bytes",
         "generated_code_size_in_bytes")


def compile_step(cell_name, dump=None):
    """The compiled training step of ``cell_name`` for one v5e chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import numpy as np
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import spec
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import executor

    cell = spec.load_cell(cell_name)
    params = cell.params()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, loss = cell.builder.build(params)
    batch = cell.builder.make_batch(np.random.default_rng(7), params)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        compiled, feed_vals = exe._resolve_compiled(main, batch, [loss],
                                                    scope, None)
        shapes = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip),
            (executor._scope_state(scope, compiled.state_mut),
             executor._scope_state(scope, compiled.state_ro),
             tuple(feed_vals), np.int32(0)))
        options = {} if dump is None else {
            "xla_dump_to": dump, "xla_dump_hlo_as_text": True}
        return compiled._jitted.lower(*shapes).compile(
            compiler_options=options)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--dump")
    ap.add_argument("--hlo")
    args = ap.parse_args()
    t0 = time.time()
    step = compile_step(args.cell, args.dump)
    analysis = step.memory_analysis()
    record = {name: getattr(analysis, name) for name in SIZES}
    record.update(cell=args.cell, seconds=round(time.time() - t0, 1))
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(step.as_text())
    print(json.dumps(record))


if __name__ == "__main__":
    main()
