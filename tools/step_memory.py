#!/usr/bin/env python
"""What a benchmark cell's training step holds, without the chip: the
step compiled for a described v5e (XLA:TPU and Mosaic run here; nothing
runs on a device) and its ``memory_analysis()``.

Usage:
    python tools/step_memory.py CELL [--dump DIR] [--hlo FILE] [--by-op]

    python tools/step_memory.py moonlight_ep8share_s4096_train

Prints one JSON line: ``temp_size_in_bytes`` beside the argument, output,
alias and code sizes and XLA's ``peak_memory_in_bytes``.  ``temp_size_in_bytes`` is the step's temporaries;
``--dump DIR`` keeps XLA's dump, whose ``*memory-usage-report.txt`` names
the ``preallocated-temp`` allocation, which is the chip's
``peak_bytes_reserved`` (Moonlight: 4.27 GiB = 4,590,141,440 in the cell's
log), the half of ``hbm_peak_gb`` that a change to the program can move
through XLA's schedule alone (PERF.md section 6, PRs 35 and 36).  ``--hlo
FILE`` writes the optimized module's text, to set two trees' steps side by
side.  To read another tree, run that tree's copy of this file.

``--by-op`` says WHOSE the temporaries are: the buffers of the step's
``preallocated-temp`` allocations grouped by the ``role_*`` / ``fluid_<op>``
scope of the instruction that defines each, the ten largest scopes first.
It reads XLA's own buffer assignment from the dump
(``*after_optimizations-buffer-assignment.txt``; ``memory_analysis()``'s
``serialized_buffer_assignment_proto`` comes back empty from XLA:TPU through
the PJRT C API).  Buffers share their bytes over time, so a scope gets two
columns: ``share``, each byte of an allocation split evenly among the
buffers that ever lie on it (the column sums to the bytes any buffer uses), and
``buffers``, the plain sum of the sizes of the buffers it defines.

A step takes about a minute and 7 GB of host memory (Moonlight 45-75 s,
the flash cell 55 s): one at a time on a shared sandbox.  The cell is
built as ``benchmarks/harness/loop.run_cell`` builds it.
"""

import argparse
import collections
import glob
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = ("argument_size_in_bytes", "output_size_in_bytes",
         "temp_size_in_bytes", "alias_size_in_bytes",
         "generated_code_size_in_bytes", "peak_memory_in_bytes")


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


ALLOCATION = re.compile(r"^allocation (\d+): size (\d+),(.*)$")
VALUE = re.compile(
    r"^ value: <\d+ ([^\s{]+)\S* @\d+> \(size=(\d+),offset=(\d+)\)")
DEFINED = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
CALL = re.compile(r"\s*([a-z][a-z0-9\-]*)\(\s*(?:%([\w.\-]+))?")
OP_NAME = re.compile(r'op_name="([^"]*)"')
ROLE = re.compile(r"role_[a-z]+")
FLUID_OP = re.compile(r"fluid_[A-Za-z0-9_.]+")
ARGUMENT = "(argument)"


def _after_shape(text):
    """``text`` past the result shape: a tuple shape is skipped to its
    closing parenthesis, any other has no space in it."""
    if not text.startswith("("):
        return text[text.index(" "):]
    depth = 0
    for i, c in enumerate(text):
        depth += (c == "(") - (c == ")")
        if depth == 0:
            return text[i + 1:]
    return ""


def instruction_scopes(hlo_path):
    """``{instruction: scope}`` of one optimized module's text.  The scope
    is ``role_x/fluid_<op>`` from the instruction's ``op_name`` (the first
    of each: inside a loop the first ``fluid_*`` is the instruction's own
    op).  An instruction XLA made itself (a copy, a prefetch, a relayout
    fusion) has none: it takes the scope of what it copies, followed
    through its first operand, and says so: ``role_fwd/fluid_mul (via
    copy)``; what it copies of the step's arguments is ``(argument) (via
    copy)``."""
    own, opcode_of, operand_of = {}, {}, {}
    with open(hlo_path) as f:
        for line in f:
            m = DEFINED.match(line)
            if not m:
                continue
            call = CALL.match(_after_shape(m.group(2)))
            if not call:
                continue
            name = m.group(1)
            opcode_of[name], operand_of[name] = call.groups()
            op_name = OP_NAME.search(line)
            parts = [r.search(op_name.group(1)) for r in (ROLE, FLUID_OP)] \
                if op_name else []
            if any(parts):
                own[name] = "/".join(p.group(0) for p in parts if p)
            elif call.group(1) == "parameter":
                own[name] = ARGUMENT

    def scope(name):
        seen = name
        for _ in range(64):
            if seen in own:
                return own[seen] if seen == name else \
                    "%s (via %s)" % (own[seen], opcode_of[name])
            seen = operand_of.get(seen)
            if seen is None:
                break
        return "(xla) %s" % opcode_of.get(name, "?")

    return {name: scope(name) for name in opcode_of}


def temp_buffers(path):
    """``{allocation: (size, [(instruction, size, offset)])}`` of the
    ``preallocated-temp`` allocations in HBM in one
    ``*buffer-assignment.txt``: those of no ``color`` (memory space 0; a
    colored one lies in on-chip memory)."""
    allocations, current = {}, None
    with open(path) as f:
        for line in f:
            if line.startswith("Used values:"):
                break
            m = ALLOCATION.match(line)
            if m:
                current = None
                if "preallocated-temp" in m.group(3) and \
                        "color" not in m.group(3):
                    current = allocations[int(m.group(1))] = \
                        (int(m.group(2)), [])
                continue
            m = VALUE.match(line)
            if m and current is not None:
                current[1].append((m.group(1), int(m.group(2)),
                                   int(m.group(3))))
    return allocations


def by_scope(allocations, scopes):
    """``{scope: [share bytes, buffer bytes, buffers]}`` (see ``--by-op``
    in the module's docstring)."""
    table = collections.defaultdict(lambda: [0.0, 0, 0])
    for _, values in allocations.values():
        edges = collections.defaultdict(lambda: ([], []))
        for instruction, size, offset in values:
            scope = scopes.get(instruction, "(unknown)")
            table[scope][1] += size
            table[scope][2] += 1
            if size:
                edges[offset][0].append(scope)
                edges[offset + size][1].append(scope)
        live, last = collections.Counter(), 0
        for edge in sorted(edges):
            n = sum(live.values())
            for scope, count in live.items():
                table[scope][0] += (edge - last) * count / n
            starts, ends = edges[edge]
            live.update(starts)
            live.subtract(ends)
            live += collections.Counter()      # drop the zeros
            last = edge
    return table


def print_by_op(dump, top=10):
    paths = glob.glob(os.path.join(
        dump, "*after_optimizations-buffer-assignment.txt"))
    if not paths:
        print("step_memory: XLA dumped no buffer assignment under %s" % dump,
              file=sys.stderr)
        sys.exit(2)
    # the step is the largest module of the dump
    path = max(paths, key=os.path.getsize)
    allocations = temp_buffers(path)
    table = by_scope(allocations, instruction_scopes(path.replace(
        "-buffer-assignment.txt", ".txt")))
    total = sum(size for size, _ in allocations.values())
    print("preallocated-temp in HBM: %d allocation(s), %d bytes; %d buffers "
          "in %d scopes" % (len(allocations), total,
                            sum(row[2] for row in table.values()),
                            len(table)))
    print("%-56s %14s %7s %15s %8s" % ("scope", "share, bytes", "%",
                                       "buffers, bytes", "buffers"))
    rows = sorted(table.items(), key=lambda kv: -kv[1][0])
    rest = rows[top:]
    rows = rows[:top] + [("(%d more scopes)" % len(rest), [
        sum(row[i] for _, row in rest) for i in range(3)])]
    for scope, (share, nbytes, n) in rows:
        print("%-56s %14d %6.2f%% %15d %8d" % (
            scope, share, 100.0 * share / total, nbytes, n))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--dump")
    ap.add_argument("--hlo")
    ap.add_argument("--by-op", action="store_true")
    args = ap.parse_args()
    t0 = time.time()
    with tempfile.TemporaryDirectory() as scratch:
        dump = args.dump or (scratch if args.by_op else None)
        step = compile_step(args.cell, dump)
        if args.by_op:
            print_by_op(dump)
    analysis = step.memory_analysis()
    record = {name: getattr(analysis, name) for name in SIZES}
    record.update(cell=args.cell, seconds=round(time.time() - t0, 1))
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(step.as_text())
    print(json.dumps(record))


if __name__ == "__main__":
    main()
