"""lowering layer: device milliseconds a step spends rematerialising the
loop's passes, on the first chip.  ``recurrent_grad`` runs one reverse scan
whose step builds the pass again from its saved carry under the scope
``ut_remat`` and then pulls the cotangents back through it.  The
rematerialised FORWARD operations carry ``ut_remat/jvp(ut_loop)/...``; their
transposes carry ``transpose(jvp(ut_loop))`` or, out of a ``custom_vjp``
(the flash backward kernels), ``transpose(ut_remat)``, and are the backward
proper: they are told apart by that ``transpose(``.  A fusion carries its
root's name."""

from harness import program_spans

SCOPE, TRANSPOSED = "ut_remat", "transpose("


def remat_seconds(ops, names):
    """Summed durations of the operations ``(label, instruction, start,
    end, target)`` whose ``op_name`` (``names``: by instruction) holds the
    scope and is no transpose."""
    return sum(end - start for _, name, start, end, _ in ops
               if SCOPE in names.get(name, "")
               and TRANSPOSED not in names.get(name, ""))


def read(ctx):
    reduced = ctx["trace"]
    if reduced is None or not reduced.steps:
        return None
    names = program_spans.step_scopes()
    if names is None:
        return None
    seconds = remat_seconds(reduced.ops(), names)
    return 1e3 * seconds / reduced.steps if seconds else None
