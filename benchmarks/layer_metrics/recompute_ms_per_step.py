"""lowering layer: device milliseconds a step spends replaying the forward
of its ``recompute`` spans inside the backward, on the first chip: the
price of fitting.  ``recompute_grad`` differentiates a second run of the
span under ``jax.checkpoint``, which builds the span's forward again under
the scope ``rematted_computation`` (``ops/control_flow_ops.REPLAY_SCOPE``:
``.../checkpoint/rematted_computation/...`` in an operation's ``op_name``;
the span's transposes carry ``checkpoint/`` alone and are the backward
proper).  The replayed ``flash_fwd`` calls and the replayed expert layers'
conditionals are among it.  A fusion carries its root's name.  Nothing from
a step without spans."""

from harness import program_spans

SCOPE = "rematted_computation"


def replay_seconds(ops, names):
    """Summed durations of the operations ``(label, instruction, start,
    end, target)`` whose ``op_name`` (``names``: by instruction) holds the
    scope."""
    return sum(end - start for _, name, start, end, _ in ops
               if SCOPE in names.get(name, ""))


def read(ctx):
    reduced = ctx["trace"]
    if reduced is None or not reduced.steps:
        return None
    names = program_spans.step_scopes()
    if names is None:
        return None
    seconds = replay_seconds(reduced.ops(), names)
    return 1e3 * seconds / reduced.steps if seconds else None
