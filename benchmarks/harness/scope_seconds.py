"""Device time of the operations a lowering put under a ``jax.named_scope``
of its own, inside its ``fluid_<op>`` scope: the program's
``step_scopes()`` maps each instruction of the compiled step to its
``op_name``, which holds every scope the instruction was traced under —
forward and, through ``transpose(jvp(<scope>))``, backward alike.  A fusion
carries its root's name, so an elementwise tail fused into a neighbour is
read with the neighbour.  Nothing (None) from a program without scopes or
an untraced run."""

from . import program_spans


def scope_ms_per_step(ctx, *scopes, instructions=()):
    """Milliseconds a step spends on the first chip in operations whose
    ``op_name`` holds one of ``scopes``, or whose instruction's name starts
    with one of ``instructions`` (a custom call the compiler itself puts in
    carries no ``op_name``); None where the step has none."""
    reduced = ctx["trace"]
    if reduced is None or not reduced.steps:
        return None
    names = program_spans.step_scopes()
    if names is None:
        return None
    instructions = tuple(instructions)
    seconds = sum(end - start for _, name, start, end, _ in reduced.ops()
                  if any(s in names.get(name, "") for s in scopes)
                  or (instructions and name.startswith(instructions)))
    return 1e3 * seconds / reduced.steps if seconds else None


FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def flash_kernels_ms_per_step(ctx):
    """Milliseconds a step spends in the Mosaic calls XLA:TPU named after
    the flash attention kernels' ``name=`` (each alone is a metric of its
    own, ``flash_fwd_ms_per_step`` ...); None where a reader of
    ``program_spans`` finds nothing or the step runs none of them."""
    parts = [program_spans.kernel_ms_per_step(ctx, k) for k in FLASH_KERNELS]
    if None in parts or not sum(parts):
        return None
    return sum(parts)
