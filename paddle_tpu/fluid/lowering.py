"""Block → JAX/XLA lowering.

This replaces the reference's per-op interpreter hot loop
(``framework/executor.cc:416-421``: ``op->Run(scope, place)`` per OpDesc) with
whole-block tracing: every op's registered lowering rule consumes/produces
values in a name→value environment (the functional image of the reference's
``Scope``), and the resulting function is compiled once by XLA and cached
(``executor.py``).  Buffer lifetime inside a compiled block is XLA's problem —
the reference's eager-deletion GC (``framework/garbage_collector.h``) is
subsumed.
"""

import contextlib
import types

import jax
import jax.numpy as jnp

from .data_types import is_floating
from .framework import OpRole
from .registry import get_op_def
from . import telemetry

# Op types consumed by the executor itself rather than lowered.
_STRUCTURAL_OPS = frozenset(["feed", "fetch"])

# trace-time telemetry (docs/observability.md): counted while jax traces
# the step function, so a growing blocks_traced count between steady-
# state steps is a retrace leak — the classic silent step-time killer
_m_blocks = telemetry.counter(
    "lowering_blocks_traced_total", "program blocks traced to XLA")


def step_prng_key(seed, step):
    """Base PRNG key of ONE training step: the program seed folded with
    the step index.  ``step`` is IN-TRACE (a traced int32 scalar), which
    is what makes the multi-step fused window (``Executor.run_window``,
    a ``lax.scan`` over K inner steps) correct: each inner step derives
    its own key from ``step0 + i`` inside the trace, so dropout masks,
    random fills, and every step-keyed schedule advance per INNER step —
    never per host dispatch.  Shared by the executor's single-step and
    window compile paths and the pipeline schedule so the derivation
    cannot drift between them (K=1 vs K>1 must be bit-identical)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), step)


class ExecState:
    """Per-trace execution state threaded through lowerings."""

    def __init__(self, blocks, step, base_key, is_test=False, axis_env=(),
                 amp_dtype=None, amp_keep=False, mesh=None):
        self.blocks = blocks          # program blocks, for control-flow ops
        self.step = step              # traced int32 scalar, increments per run
        self.base_key = base_key      # PRNG key folded with step
        self.is_test = is_test
        # names of mapped mesh axes when tracing inside shard_map; collective
        # ops use these instead of NCCL ring ids (SURVEY.md §2.4 → ICI).
        self.axis_env = axis_env
        # AMP compute dtype for MXU ops ("bfloat16" on TPU), or None.
        self.amp_dtype = amp_dtype
        # pure-bf16 mode: MXU outputs stay bf16 (no fp32 round trip)
        self.amp_keep = amp_keep
        # concrete jax.sharding.Mesh when compiling under GSPMD — lowerings
        # that emit sharding constraints or nested shard_maps (sequence /
        # expert parallel attention and MoE) read the axis layout from here
        self.mesh = mesh
        # extra mesh axes whose index must decorrelate per-op PRNG (e.g.
        # the pipeline's 'dp' axis, which is NOT a collective ring in
        # axis_env but does shard the batch) — consumed by LowerCtx.rng
        self.extra_rng_axes = ()
        # wire-traffic log: collective lowerings append (species,
        # precision, per-device payload bytes) triples here DURING
        # tracing (shapes are static in-trace, so this costs nothing at
        # run time); the executor captures the last complete trace's log
        # per compiled block and turns it into the per-dispatch
        # collective_bytes_total counter / comm_bytes step-event field.
        # None (the default) disables recording.
        self.comm_log = None

    def record_comm(self, species, precision, nbytes, grad_bucket=False,
                    axis=None):
        """Log one collective's per-device wire payload (trace time).
        ``grad_bucket`` marks the exchange as one of the transpiler's
        coalesced GRADIENT buckets (the ``__grad_bucket__`` op attr) —
        the executor's ``comm_buckets`` overlap accounting counts only
        those, so sync-BN statistics or LocalSGD parameter averages
        can't inflate the schedulable-overlap bound.

        ``axis`` is the mesh axis (link class) the collective runs over
        ('dp'/'mp'/'ep'/...), feeding the executor's per-axis
        ``collective_bytes_total{axis}`` accounting.  A TUPLE axis — the
        hierarchical two-level ring, e.g. ``("dcn", "ici")`` — is split
        into one entry per member axis using the two-level reduction's
        movement model: the innermost axis exchanges the full payload,
        each outer level only the 1/n shard left by the levels inside
        it, and the per-axis shares are normalized so they sum to
        ``nbytes`` exactly (totals stay identical to the flat
        accounting; only the attribution gains resolution).  Member
        axes of size 1 move nothing and get no entry."""
        if self.comm_log is None:
            return
        total = int(nbytes)
        if isinstance(axis, tuple):
            # psum of a concrete 1 is constant-folded to the axis size
            # at trace time (same trick as allreduce_wire_bytes callers)
            sizes = [int(jax.lax.psum(1, ax)) for ax in axis]
            weights, shard = [], 1.0
            for ax, n in zip(reversed(axis), reversed(sizes)):
                if n > 1:
                    weights.append((ax, shard))
                shard /= max(n, 1)
            if not weights:     # degenerate all-size-1 ring
                weights = [(axis[-1], 1.0)]
            wsum = sum(w for _ax, w in weights)
            acc = 0
            for i, (ax, w) in enumerate(weights):
                b = total - acc if i == len(weights) - 1 \
                    else int(round(total * w / wsum))
                acc += b
                self.comm_log.append((species, precision, b,
                                      bool(grad_bucket), ax))
            return
        self.comm_log.append((species, precision, total,
                              bool(grad_bucket), axis))


def amp_operands(state, *vals):
    """AMP helper for matmul/conv lowerings: cast fp32 operands to the AMP
    compute dtype (MXU runs bf16 natively) and return them plus the dtype the
    op should accumulate/output in (fp32 — the 'master' activations stay
    fp32, unlike the reference's whole-graph fp16 rewrite which needed loss
    scaling; contrib/mixed_precision/decorator.py:27 is the parity API)."""
    dt = getattr(state, "amp_dtype", None)
    if not dt:
        return vals + (None,)
    cdt = jnp.dtype(dt)
    if any(v.dtype not in (jnp.float32, cdt) for v in vals) or \
            all(v.dtype == cdt for v in vals):
        # non-AMP dtypes involved, or already uniformly bf16: untouched
        return vals + (None,)
    if getattr(state, "amp_keep", False):
        # pure-bf16 activations: skip the fp32 round trip between MXU ops
        # (halves activation HBM traffic; BN still accumulates fp32)
        return tuple(v.astype(cdt) for v in vals) + (None,)
    return tuple(v.astype(cdt) for v in vals) + (jnp.float32,)


class LowerCtx:
    """Per-op view of the environment handed to lowering rules."""

    __slots__ = ("env", "op", "state", "block")

    def __init__(self, env, op, state, block):
        self.env = env
        self.op = op
        self.state = state
        self.block = block

    # -- inputs ------------------------------------------------------------
    def input(self, slot):
        return [self.env[n] for n in self.op.input(slot)]

    def i(self, slot, idx=0):
        names = self.op.input(slot)
        return self.env[names[idx]]

    def i_opt(self, slot, idx=0):
        names = self.op.input(slot)
        if len(names) <= idx or not names[idx]:
            return None
        return self.env.get(names[idx])

    def has_input(self, slot):
        names = self.op.input(slot)
        return bool(names) and names[0] in self.env

    # -- outputs -----------------------------------------------------------
    def set(self, slot, value, idx=0):
        names = self.op.output(slot)
        if names and names[idx]:
            self.env[names[idx]] = value

    def set_all(self, slot, values):
        for i, v in enumerate(values):
            self.set(slot, v, idx=i)

    # -- misc --------------------------------------------------------------
    def attr(self, name, default=None):
        return self.op.attr(name, default)

    def rng(self):
        """Per-op PRNG key: deterministic given (program seed, op, step);
        under shard_map, also folded with the device's axis index so dropout
        masks differ across data-parallel replicas."""
        key = jax.random.fold_in(self.state.base_key,
                                 self.op.attr("__op_seed__", 0))
        axes = self.state.axis_env
        names = list(axes.values() if isinstance(axes, dict) else axes)
        names += list(getattr(self.state, "extra_rng_axes", ()))
        for name in names:
            key = jax.random.fold_in(key, jax.lax.axis_index(name))
        return key

    def var_dtype(self, name):
        v = self.block._find_var_recursive(name)
        return v.dtype if v is not None else None

    def var_shape(self, name):
        v = self.block._find_var_recursive(name)
        return v.shape if v is not None else None


def run_block(block, env, state):
    """Trace every op of ``block`` through its lowering rule, in order."""
    _m_blocks.inc()
    for op in block.ops:
        dispatch(op, env, state, block)


def role_scope(role):
    """The step phase an op's ``op_role`` attribute puts it in:
    ``role_bwd`` (the loss gradient carries ``Backward | Loss``),
    ``role_opt`` (optimizer ops and the learning-rate schedule) or
    ``role_fwd``."""
    if role & OpRole.Backward:
        return "role_bwd"
    if role & (OpRole.Optimize | OpRole.LRSched):
        return "role_opt"
    return "role_fwd"


# Ops whose lowering runs a sub-block's ops through ``dispatch`` (a loop
# body; a ``recompute`` span): the inner ops carry their own ``role_*`` /
# ``fluid_<op>`` scopes, and a ``fluid_recurrent`` or ``fluid_recompute``
# around them would be every inner instruction's FIRST ``fluid_*`` match, one
# line holding the whole loop (or every layer of a checkpointed model) in
# every reader.  The loop's lowering names the body itself (``ut_loop``); a
# span's replay is named by ``jax.checkpoint`` (``rematted_computation``).
_BODY_OPS = frozenset(["recurrent", "recurrent_grad", "recompute",
                       "recompute_grad"])


def op_scopes(op):
    """The named scopes an op lowers inside, outermost first: its role,
    the ``fluid.name_scope`` it was built under (``op_namescope``, one
    scope a path segment) and ``fluid_<type>``."""
    names = [role_scope(op.op_role)]
    names += [s for s in (op.attr("op_namescope", "") or "").split("/") if s]
    if op.type not in _BODY_OPS:
        names.append("fluid_" + op.type)
    return names


def dispatch(op, env, state, block):
    if op.type in _STRUCTURAL_OPS:
        return
    ctx = LowerCtx(env, op, state, block)
    # Every op lowers inside two named scopes so HLO instruction metadata
    # (op_name="jit(..)/role_bwd/fluid_<type>/..") maps device cost back
    # to the ProgramDesc op that produced it — the attribution substrate
    # of the device-cost ledger (costmodel.op_attribution,
    # tools/cost_ledger.py) — and to the phase of the step it belongs to
    # (a device trace's forward / backward / optimizer split).  Readers
    # take the FIRST "fluid_" match of an op_name, so the role scope's
    # name must not begin with it.  Metadata only: the scopes never change
    # the lowered math, so they stay unconditional rather than joining
    # flags.trace_time_key().
    try:
        with contextlib.ExitStack() as scopes:
            for name in op_scopes(op):
                scopes.enter_context(jax.named_scope(name))
            if op.type.endswith("_grad"):
                fwd_type = op.type[:-len("_grad")]
                from .registry import OP_DEFS
                self_def = OP_DEFS.get(op.type)
                if self_def is not None and self_def.lower is not None:
                    self_def.lower(ctx, op)
                else:
                    fwd_def = OP_DEFS.get(fwd_type)
                    if fwd_def is None:
                        get_op_def(op.type)  # raises NotImplementedError
                    elif fwd_def.grad_lower is not None:
                        fwd_def.grad_lower(ctx, op)
                    else:
                        generic_grad_lower(ctx, op)
            else:
                get_op_def(op.type).lower(ctx, op)
    except Exception as e:
        _enrich_op_error(e, op, env)
        raise
    _maybe_check_nan_inf(op, env)


def _enrich_op_error(e, op, env):
    """Attach op context to lowering failures (the reference's
    PADDLE_ENFORCE messages carry the op type + var names,
    platform/enforce.h) — once, at the op that actually failed."""
    if getattr(e, "_op_context_added", False):
        return
    def fmt(slots):
        parts = []
        for slot, names in slots.items():
            if not names:
                continue
            shapes = []
            for n in names:
                v = env.get(n)
                shapes.append("%s%s" % (n, list(v.shape))
                              if hasattr(v, "shape") else n)
            parts.append("%s=%s" % (slot, shapes))
        return ", ".join(parts)
    note = ("\n[operator %s] inputs: {%s} -> outputs: {%s}"
            % (op.type, fmt(op.inputs), fmt(op.outputs)))
    e._op_context_added = True
    if e.args and isinstance(e.args[0], str):
        e.args = (e.args[0] + note,) + e.args[1:]
    else:
        e.args = e.args + (note,)


def _maybe_check_nan_inf(op, env):
    """FLAGS_check_nan_inf: assert every float output of every op is
    finite, attributed to the producing op (the reference's post-Run scan,
    ``framework/operator.cc:953-984``).  The check is a checkify user
    check: the executor wraps the step in ``checkify.checkify`` and throws
    host-side after the step when the policy is ``raise``.  Under ``skip``
    the executor guards the step functionally instead (finite-or-keep-old-
    state select, executor.py) — checkify calls must not be emitted there,
    they would fail to trace outside a checkify context."""
    from .flags import nan_inf_policy
    if nan_inf_policy() != "raise":
        return
    from jax.experimental import checkify
    for slot in op.outputs:
        for name in op.output(slot):
            v = env.get(name)
            if v is None or not hasattr(v, "dtype") or \
                    not jnp.issubdtype(v.dtype, jnp.floating):
                continue
            checkify.check(
                jnp.isfinite(v).all(),
                "Operator %s output %s contains Inf or Nan" %
                (op.type, name))


class _FwdShim:
    """Operator look-alike reconstructing a forward op inside its grad op."""

    def __init__(self, type, inputs, outputs, attrs, block):
        self.type = type
        self.inputs = inputs
        self.outputs = outputs
        self.attrs = attrs
        self.block = block

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def has_attr(self, name):
        return name in self.attrs


def generic_grad_lower(ctx, op, residual_slots=()):
    """Default grad kernel: replay the forward lowering under ``jax.vjp``.

    The grad OpDesc (built by ``backward.append_backward``) carries the
    forward op's slot maps in ``__fwd_inputs__``/``__fwd_outputs__``.  We
    rebuild the forward as a pure function of its differentiable inputs,
    vjp it, and seed the cotangents with the output grads present in the
    environment (zeros for outputs nobody differentiated).

    ``residual_slots`` names forward outputs that exist only to feed a
    custom grad lowering (``fused_attention``'s ``LSE``): the replayed
    forward is built without them, so it neither writes nor is asked for
    them.
    """
    fwd_inputs = op.attr("__fwd_inputs__")
    fwd_outputs = {slot: names
                   for slot, names in op.attr("__fwd_outputs__").items()
                   if slot not in residual_slots}
    fwd_type = op.type[:-len("_grad")]
    fwd_def = get_op_def(fwd_type)
    fwd_attrs = {k: v for k, v in op.attrs.items()
                 if not k.startswith("__fwd_")}
    shim = _FwdShim(fwd_type, fwd_inputs, fwd_outputs, fwd_attrs, ctx.block)

    env = ctx.env
    # (slot, idx, var name) triples we differentiate with respect to:
    # requested by the grad op's outputs AND float-typed AND not declared
    # non-differentiable by the op def.
    diff = []
    for slot, names in fwd_inputs.items():
        if slot in fwd_def.nondiff_inputs:
            continue
        gslot = slot + "@GRAD"
        gnames = op.output(gslot)
        for idx, name in enumerate(names):
            if idx >= len(gnames) or not gnames[idx]:
                continue
            val = env[name]
            if not jnp.issubdtype(val.dtype, jnp.floating):
                continue
            diff.append((slot, idx, name))
    if not diff:
        return

    out_order = [(slot, idx, name)
                 for slot, names in fwd_outputs.items()
                 for idx, name in enumerate(names) if name]

    def fwd_fn(diff_vals):
        sub_env = {}
        for slot, names in fwd_inputs.items():
            for n in names:
                if n:
                    sub_env[n] = env[n]
        for (slot, idx, name), v in zip(diff, diff_vals):
            sub_env[name] = v
        sub_ctx = LowerCtx(sub_env, shim, ctx.state, ctx.block)
        fwd_def.lower(sub_ctx, shim)
        return tuple(sub_env[name] for (_, _, name) in out_order)

    primal_vals = tuple(env[name] for (_, _, name) in diff)
    primals_out, vjp_fn = jax.vjp(fwd_fn, primal_vals)

    cotangents = []
    for (slot, idx, name), primal in zip(out_order, primals_out):
        gnames = op.input(slot + "@GRAD")
        gname = gnames[idx] if idx < len(gnames) else None
        if gname and gname in env:
            g = jnp.asarray(env[gname], primal.dtype)
            if g.shape != primal.shape:
                # e.g. a (1,)-shaped loss grad seeding a scalar output
                g = g.reshape(primal.shape)
            cotangents.append(g)
        else:
            cotangents.append(jnp.zeros_like(primal))

    in_grads, = vjp_fn(tuple(cotangents))
    for (slot, idx, name), g in zip(diff, in_grads):
        out_gname = op.output(slot + "@GRAD")[idx]
        env[out_gname] = g
