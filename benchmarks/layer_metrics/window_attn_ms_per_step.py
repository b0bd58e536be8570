"""kernels layer: device milliseconds a step spends in the Pallas flash
attention kernels of the layers that attend through a sliding window, on the
first chip: the Mosaic calls XLA:TPU named ``flash_fwd`` / ``flash_dq`` /
``flash_dkv`` after the kernels' ``name=`` whose ``op_name`` holds the scope
``attn_window``, which ``ops/pallas_ops.py`` puts around a windowed call's
kernels alone (the full layers' calls carry the same kernel names and not
the scope).  Under ``recompute`` the forward kernel runs a second time in
the backward; it is counted: time, not needed work.  Nothing from a program
without the scope (a step with no window, or a program from before the
attribute existed)."""

from harness import program_spans

SCOPE, KERNELS = "attn_window", ("flash_fwd", "flash_dq", "flash_dkv")


def window_seconds(ops, names):
    """Summed durations of the operations ``(label, instruction, start,
    end, target)`` that are a named flash kernel under the scope (``names``:
    ``op_name`` by instruction)."""
    return sum(end - start for _, name, start, end, _ in ops
               if name.split(".")[0] in KERNELS
               and SCOPE in names.get(name, ""))


def read(ctx):
    reduced = ctx["trace"]
    if reduced is None or not reduced.steps:
        return None
    names = program_spans.step_scopes()
    if names is None:
        return None
    seconds = window_seconds(reduced.ops(), names)
    return 1e3 * seconds / reduced.steps if seconds else None
