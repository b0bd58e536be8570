"""Control-flow op lowerings: while / cond / recurrent + tensor arrays.

Reference analogues: ``operators/controlflow/while_op.cc`` (runs a sub-block
via a nested Executor until a condition var flips), ``conditional_block_op.cc``
and the recurrent machinery behind ``layers/control_flow.py`` StaticRNN.

TPU-first redesign: sub-blocks become *traced* JAX control flow —
``lax.while_loop`` / ``lax.cond`` / ``lax.scan`` — so the whole loop compiles
into one XLA computation instead of re-entering a host interpreter each
iteration.  LoDTensorArray (``framework/lod_tensor_array.h``) becomes a
fixed-capacity device ring (static shapes are an XLA requirement): a
(buffer[max_len, ...], length) pair registered as a pytree so it can be
loop-carried.

Differentiation: ``recurrent`` (lax.scan) is reverse-differentiable and is
the training path for RNNs (StaticRNN/DynamicRNN layers emit it).  ``while``
is for decoding-style loops (beam search) and does not carry gradients, same
practical contract as the reference where while_grad was rarely exercised.
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..data_types import jnp_dtype
from ..registry import register_grad_lower, register_op
from .. import telemetry

DEFAULT_ARRAY_CAPACITY = 128


class TensorArrayVal:
    """Fixed-capacity tensor array: the static-shape stand-in for
    LoDTensorArray.  ``buffer`` is None until the first write fixes the
    element shape/dtype."""

    __slots__ = ("buffer", "length", "max_len")

    def __init__(self, buffer, length, max_len):
        self.buffer = buffer
        self.length = length
        self.max_len = max_len

    def write(self, i, x):
        i = jnp.asarray(i, jnp.int32).reshape(())
        if self.buffer is None:
            buf = jnp.zeros((self.max_len,) + tuple(x.shape), x.dtype)
        else:
            buf = self.buffer
        buf = jax.lax.dynamic_update_index_in_dim(buf, x.astype(buf.dtype),
                                                  i, 0)
        length = jnp.maximum(jnp.asarray(self.length, jnp.int32), i + 1)
        return TensorArrayVal(buf, length, self.max_len)

    def read(self, i):
        if self.buffer is None:
            raise ValueError("read from an empty tensor array")
        i = jnp.asarray(i, jnp.int32).reshape(())
        return jax.lax.dynamic_index_in_dim(self.buffer, i, 0,
                                            keepdims=False)


def _ta_flatten(ta):
    return (ta.buffer, ta.length), ta.max_len


def _ta_unflatten(max_len, children):
    buffer, length = children
    return TensorArrayVal(buffer, length, max_len)


jax.tree_util.register_pytree_node(TensorArrayVal, _ta_flatten, _ta_unflatten)


def _scalar_index(v):
    return jnp.asarray(v, jnp.int32).reshape(())


@register_op("create_array", stop_gradient=True)
def _create_array(ctx, op):
    max_len = ctx.attr("max_len", DEFAULT_ARRAY_CAPACITY)
    ctx.set("Out", TensorArrayVal(None, jnp.asarray(0, jnp.int32), max_len))


@register_op("write_to_array", nondiff_inputs=("I",), stop_gradient=True)
def _write_to_array(ctx, op):
    """X is the value, I the index, Out the array var (read-modify-write,
    as the reference's scope-resident LoDTensorArray)."""
    out_name = op.output("Out")[0]
    arr = ctx.env.get(out_name)
    if not isinstance(arr, TensorArrayVal):
        arr = TensorArrayVal(None, jnp.asarray(0, jnp.int32),
                             ctx.attr("max_len", DEFAULT_ARRAY_CAPACITY))
    ctx.set("Out", arr.write(_scalar_index(ctx.i("I")), ctx.i("X")))


@register_op("read_from_array", nondiff_inputs=("I",), stop_gradient=True)
def _read_from_array(ctx, op):
    arr = ctx.i("X")
    ctx.set("Out", arr.read(_scalar_index(ctx.i("I"))))


@register_op("lod_array_length", stop_gradient=True)
def _lod_array_length(ctx, op):
    arr = ctx.i("X")
    ctx.set("Out", jnp.asarray(arr.length, jnp_dtype("int64")).reshape((1,)))


@register_op("tensor_array_to_tensor", stop_gradient=True)
def _tensor_array_to_tensor(ctx, op):
    """Stack the array into a dense tensor.  Entries past ``length`` are the
    zero padding the fixed-capacity design implies; OutIndex carries the
    valid length (the static-shape analogue of array_to_lod_tensor)."""
    arr = ctx.i("X")
    axis = ctx.attr("axis", 0)
    use_stack = ctx.attr("use_stack", True)
    buf = arr.buffer
    if buf is None:
        raise ValueError("tensor_array_to_tensor on an empty array")
    if use_stack:
        out = jnp.moveaxis(buf, 0, axis) if axis else buf
    else:
        parts = [jax.lax.index_in_dim(buf, i, 0, keepdims=False)
                 for i in range(buf.shape[0])]
        out = jnp.concatenate(parts, axis=axis)
    ctx.set("Out", out)
    ctx.set("OutIndex", jnp.asarray(arr.length, jnp.int32).reshape((1,)))


# ---------------------------------------------------------------------------
# while op
# ---------------------------------------------------------------------------

def _block_writes(block):
    """Names written by ops of ``block`` (one level; nested control-flow ops
    surface their writes through their own output slots)."""
    out = []
    seen = set()
    for op in block.ops:
        for names in op.outputs.values():
            for n in names:
                if n and n not in seen:
                    seen.add(n)
                    out.append(n)
    return out


def block_reads(block, blocks):
    """External reads of ``block``: names read before any local write,
    recursing through sub-block attrs."""
    from ..framework import op_sub_block_indices, op_bound_var_names
    reads, written = [], set()

    def visit(blk, written):
        for op in blk.ops:
            for names in op.inputs.values():
                for n in names:
                    if n and n not in written and n not in reads:
                        reads.append(n)
            for sub_idx in op_sub_block_indices(op):
                visit(blocks[sub_idx],
                      set(written) | op_bound_var_names(op))
            for names in op.outputs.values():
                written.update(n for n in names if n)
    visit(block, written)
    return reads


def _match_spec(val, spec):
    """Cast/reshape a concrete init value to the body-output spec discovered
    by eval_shape, so lax.while_loop sees identical pytrees."""
    def fix(v, s):
        if not hasattr(s, "dtype"):
            return v
        if v is None:
            # empty tensor-array buffer: materialize at the discovered spec
            return jnp.zeros(s.shape, s.dtype)
        v = jnp.asarray(v)
        if v.dtype != s.dtype:
            v = v.astype(s.dtype)
        if tuple(v.shape) != tuple(s.shape):
            v = jnp.broadcast_to(v, s.shape)
        return v
    return jax.tree_util.tree_map(fix, val, spec,
                                  is_leaf=lambda x: x is None)


@register_op("while", stop_gradient=True)
def _while(ctx, op):
    state = ctx.state
    sub = state.blocks[ctx.attr("sub_block")]
    env = ctx.env

    cond_name = op.input("Condition")[0]
    carried = []
    for n in [cond_name] + _block_writes(sub):
        if n in env and n not in carried:
            carried.append(n)
    # a declared loop output with no pre-loop value cannot be carried by
    # lax.while_loop (no init) — fail loudly instead of dropping the write
    missing = [n for n in op.output("Out") if n and n not in env]
    if missing:
        raise ValueError(
            "while-loop outputs %s have no value before the loop; "
            "initialize them (e.g. fill_constant) before the While block "
            "so the loop carry has an init" % missing)

    init = {n: env[n] for n in carried}

    def body_fn(carry):
        e2 = dict(env)
        e2.update(carry)
        from ..lowering import run_block
        run_block(sub, e2, state)
        return {n: e2[n] for n in carried}

    def cond_fn(carry):
        return jnp.reshape(carry[cond_name], ()).astype(bool)

    # Discovery pass: fixes empty tensor-array buffers and any dtype/shape
    # the body settles differently from the init.
    spec = jax.eval_shape(body_fn, init)
    init = {n: _match_spec(init[n], spec[n]) for n in carried}

    final = jax.lax.while_loop(cond_fn, body_fn, init)
    for n in carried:
        env[n] = final[n]


# ---------------------------------------------------------------------------
# cond op (two sub-blocks, single lax.cond) + conditional_block
# ---------------------------------------------------------------------------

@register_op("cond", nondiff_inputs=("Cond",))
def _cond(ctx, op):
    state = ctx.state
    tb = state.blocks[ctx.attr("true_block")]
    fb = state.blocks[ctx.attr("false_block")]
    env = ctx.env
    out_names = op.output("Out")
    pred = jnp.reshape(ctx.i("Cond"), ()).astype(bool)

    from ..lowering import run_block

    def mk_branch(blk):
        def branch(_):
            e2 = dict(env)
            run_block(blk, e2, state)
            return tuple(e2[n] for n in out_names)
        return branch

    outs = jax.lax.cond(pred, mk_branch(tb), mk_branch(fb), operand=None)
    for n, v in zip(out_names, outs):
        env[n] = v


@register_op("conditional_block", nondiff_inputs=("Cond",))
def _conditional_block(ctx, op):
    """Run sub-block iff Cond; Out vars keep their previous value (or zeros)
    otherwise.  This is the building block of IfElse/Switch."""
    state = ctx.state
    sub = state.blocks[ctx.attr("sub_block")]
    env = ctx.env
    out_names = [n for n in op.output("Out") if n]
    conds = ctx.input("Cond")
    pred = jnp.asarray(True)
    for c in conds:
        pred = jnp.logical_and(pred, jnp.reshape(jnp.asarray(c), ()).astype(bool))

    from ..lowering import run_block

    def true_fn(_):
        e2 = dict(env)
        run_block(sub, e2, state)
        return tuple(e2[n] for n in out_names)

    spec = jax.eval_shape(true_fn, None)

    def false_fn(_):
        outs = []
        for n, s in zip(out_names, spec):
            if n in env:
                outs.append(_match_spec(env[n], s))
            else:
                outs.append(jax.tree_util.tree_map(
                    lambda t: jnp.zeros(t.shape, t.dtype), s))
        return tuple(outs)

    outs = jax.lax.cond(pred, true_fn, false_fn, operand=None)
    for n, v in zip(out_names, outs):
        env[n] = v


# ---------------------------------------------------------------------------
# recurrent op — lax.scan; the training path for RNNs
# ---------------------------------------------------------------------------

_m_recurrent = telemetry.counter(
    "recurrent_lowered_total",
    "recurrent (StaticRNN / DynamicRNN) forward scans traced, by step "
    "count and by whether the carries at each step's entry were saved for "
    "recurrent_grad ('saves': 1 or 0)")

_m_recurrent_grad = telemetry.counter(
    "recurrent_grad_lowered_total",
    "recurrent_grad ops lowered: one reverse scan each, which "
    "rematerialises every step from the forward op's saved carry")


def _scan_operands(ctx, op):
    """``(step_fn, init, xs, n_steps, reverse)`` of a ``recurrent`` op or
    its grad op (which carries the same slots and attributes).
    ``step_fn(carry, x_t, closure)`` runs the sub-block once: ``closure``
    ({outer name: value}) overrides what the body reads from outside, the
    differentiated ``Params`` in the grad op."""
    state = ctx.state
    sub = state.blocks[ctx.attr("sub_block")]
    env = ctx.env
    in_vars = ctx.attr("step_input_vars", [])     # inner names, one per Inputs
    pre_vars = ctx.attr("pre_state_vars", [])     # inner names, one per Initials
    post_vars = ctx.attr("state_vars", [])        # inner names (new state)
    out_vars = ctx.attr("step_output_vars", [])   # inner names, one per Outputs

    xs = tuple(env[n] for n in op.input("Inputs"))
    init = tuple(env[n] for n in op.input("Initials"))
    # a loop with no sequence to slice (a block applied n times) gives its
    # count itself; where there are step inputs their leading axis does
    n_steps = xs[0].shape[0] if xs else int(ctx.attr("n_steps", 0) or 0)
    if not n_steps and not xs:
        raise ValueError("recurrent: no step input and no n_steps")

    from ..lowering import run_block

    def step_fn(carry, x_t, closure=None):
        e2 = dict(env)
        if closure:
            e2.update(closure)
        for name, v in zip(in_vars, x_t):
            e2[name] = v
        for name, v in zip(pre_vars, carry):
            e2[name] = v
        with jax.named_scope("ut_loop"):
            run_block(sub, e2, state)
        new_carry = tuple(e2[n].astype(c.dtype) if e2[n].dtype != c.dtype
                          else e2[n] for n, c in zip(post_vars, carry))
        ys = tuple(e2[n] for n in out_vars)
        return new_carry, ys

    return step_fn, init, xs, n_steps, bool(ctx.attr("reverse", False))


def _saves_carries(ctx, op):
    """The forward op writes ``Carries`` only where a ``recurrent_grad`` of
    the same block reads them: a program that is not differentiated keeps
    nothing."""
    names = [n for n in op.output("Carries") if n]
    return bool(names) and any(
        o.type == "recurrent_grad" and o.input("Carries") == names
        for o in ctx.block.ops)


@register_op("recurrent")
def _recurrent(ctx, op):
    """Scan the sub-block over the leading (time) axis of every step input,
    or ``n_steps`` times where it has none.

    Slots: Inputs (time-major [T, ...] outer arrays), Initials (initial
    memory values), Params (closure reads — weights — declared so autodiff
    reaches them); Outputs (stacked [T, ...]), FinalStates, and Carries
    ([T, ...] a memory: its value at the ENTRY of every step, no gradient;
    an intermediate output like ``fused_attention``'s ``LSE``, written
    only where ``recurrent_grad`` reads it).
    Attrs map outer slots to inner sub-block var names.  Reference analogue:
    the StaticRNN machinery of ``layers/control_flow.py`` over
    ``recurrent_op.cc``, re-founded on lax.scan.
    """
    env = ctx.env
    step_fn, init, xs, n_steps, reverse = _scan_operands(ctx, op)
    saves = _saves_carries(ctx, op)
    _m_recurrent.inc(steps=str(n_steps), saves=str(int(saves)))

    def body(carry, x_t):
        new_carry, ys = step_fn(carry, x_t)
        return new_carry, (ys, carry if saves else ())

    final, (ys, carries) = jax.lax.scan(body, init, xs, length=n_steps,
                                        reverse=reverse)
    for n, v in zip(op.output("Outputs"), ys):
        env[n] = v
    for n, v in zip(op.output("FinalStates"), final):
        env[n] = v
    if saves:
        ctx.set_all("Carries", carries)


def _floating(v):
    return jnp.issubdtype(v.dtype, jnp.floating)


@register_grad_lower("recurrent")
def _recurrent_grad(ctx, op):
    """ONE reverse ``lax.scan`` over the forward op's saved ``Carries``.  A
    step takes the carry its forward step entered with, rematerialises the
    sub-block under ``jax.vjp`` (as a function of the floating carries,
    step inputs and ``Params`` that want a gradient) and pulls back the
    step's output cotangents and the running carry cotangent.  The
    ``Params``' cotangents are summed in the scan's carry, one float32
    buffer a parameter whatever the step count: a weight applied ``T``
    times gets its whole gradient from here, not ``T`` stacked
    contributions.  The forward scan is never run again, and nothing but
    the carries crosses from the forward op to this one."""
    env = ctx.env
    carries = tuple(env.get(n) for n in op.input("Carries"))
    if len(carries) != len(op.input("Initials")) or \
            any(c is None for c in carries):
        raise ValueError("recurrent_grad: the forward op's Carries are not "
                         "among its inputs (StaticRNN writes the slot)")
    _m_recurrent_grad.inc()
    step_fn, init, xs, n_steps, reverse = _scan_operands(ctx, op)

    def wanted(slot, values):
        """Per entry of ``slot``: the name its gradient goes to, where the
        grad op asks for one and the value is floating."""
        names = op.output(slot + "@GRAD")
        return [names[i] if i < len(names) and names[i] and _floating(v)
                else "" for i, v in enumerate(values)]

    param_names = op.input("Params")
    want_p = {n: g for n, g in zip(
        param_names, wanted("Params", [env[n] for n in param_names])) if g}
    diff_p = list(want_p)
    want_x, want_c = wanted("Inputs", xs), wanted("Initials", init)
    diff_x = [i for i, g in enumerate(want_x) if g]
    # the carry's cotangent runs through every floating memory, asked for
    # at the loop's entry or not
    diff_c = [i for i, v in enumerate(init) if _floating(v)]

    def cotangent(slot, i, like):
        names = op.input(slot + "@GRAD")
        name = names[i] if i < len(names) else ""
        g = env.get(name) if name else None
        return jnp.zeros(like.shape, like.dtype) if g is None \
            else jnp.asarray(g, like.dtype).reshape(like.shape)

    # what the forward step returns, for the shapes of absent cotangents
    _, spec_y = jax.eval_shape(step_fn, init, tuple(x[0] for x in xs))
    diff_y = [i for i, s in enumerate(spec_y) if _floating(s)]
    d_ys = tuple(cotangent("Outputs", i, jax.ShapeDtypeStruct(
        (n_steps,) + spec_y[i].shape, spec_y[i].dtype)) for i in diff_y)
    d_final = tuple(cotangent("FinalStates", i, init[i]) for i in diff_c)
    acc0 = tuple(jnp.zeros(env[n].shape, jnp.float32) for n in diff_p)

    def back_step(state, saved):
        d_carry, acc = state
        carry_t, x_t, d_y_t = saved

        def floating_part(c_diff, x_diff, p_diff):
            carry = list(carry_t)
            for i, v in zip(diff_c, c_diff):
                carry[i] = v
            x = list(x_t)
            for i, v in zip(diff_x, x_diff):
                x[i] = v
            new_carry, ys = step_fn(tuple(carry), tuple(x),
                                    dict(zip(diff_p, p_diff)))
            return tuple(new_carry[i] for i in diff_c), \
                tuple(ys[i] for i in diff_y)

        with jax.named_scope("ut_remat"):
            _, vjp = jax.vjp(floating_part,
                             tuple(carry_t[i] for i in diff_c),
                             tuple(x_t[i] for i in diff_x),
                             tuple(env[n] for n in diff_p))
        d_c, d_x, d_p = vjp((d_carry, d_y_t))
        # the op's own work, named as every op's is (its body's ops carry
        # their own fluid_<op> scopes)
        with jax.named_scope("fluid_recurrent_grad"):
            acc = tuple(a + g.astype(jnp.float32)
                        for a, g in zip(acc, d_p))
        return (d_c, acc), d_x

    (d_init, acc), d_xs = jax.lax.scan(
        back_step, (d_final, acc0), (carries, xs, d_ys), length=n_steps,
        reverse=not reverse)
    for i, g in zip(diff_c, d_init):
        if want_c[i]:
            env[want_c[i]] = g
    for i, g in zip(diff_x, d_xs):
        env[want_x[i]] = g
    for n, g in zip(diff_p, acc):
        env[want_p[n]] = g.astype(env[n].dtype)


@register_op("print", stop_gradient=True)
def _print(ctx, op):
    x = ctx.i("In")
    msg = ctx.attr("message", "")
    jax.debug.print(msg + "{x}", x=x)
    ctx.set("Out", x)


# The scope ``jax.checkpoint`` itself puts around the forward it builds
# again inside a backward: every operation of a ``recompute`` span's replay
# carries ``.../checkpoint/rematted_computation/...`` in its ``op_name``
# (the span's transposes carry ``checkpoint/`` alone), so a device trace
# tells the price of fitting from the backward proper with no scope of ours.
REPLAY_SCOPE = "rematted_computation"

_m_recompute_lowered = telemetry.counter(
    "recompute_lowered_total",
    "recompute spans lowered, by the number of ops in the span (a training "
    "step traces each span twice: the forward op, and its grad op, which "
    "differentiates a second run of the span and replays its forward under "
    "'rematted_computation')")


@register_op("recompute")
def _recompute(ctx, op):
    """Rematerialized forward segment (``jax.checkpoint``): run the
    sub-block on the declared inputs and expose only the declared outputs;
    the generic vjp then RECOMPUTES the segment's intermediates in the
    backward pass instead of keeping them live in HBM — the
    memory-for-FLOPs trade of the reference's (1.6+) RecomputeOptimizer,
    re-founded on jax.checkpoint.  RNG ops inside the segment replay
    identically on recompute (per-op counter keys, lowering.py rng).

    Inside a span the backward is the generic vjp of the span, not the grad
    ops' own lowerings: a ``fused_attention`` goes through the JAX-level
    ``custom_vjp`` of its kernels (``flash_fwd`` in the span and again in
    its replay, the backward kernels once) and a ``routed_experts`` through
    ``_ladder`` (its forward conditional twice, its backward one once); the
    op-level hand-overs (``LSE``, ``Kept``) stay inside the span.  A
    persistable the span writes (``ExpertLoad``, batch norm's statistics)
    is one of its outputs."""
    state = ctx.state
    sub = state.blocks[ctx.attr("sub_block")]
    _m_recompute_lowered.inc(ops=len(sub.ops))
    in_names = ctx.attr("input_vars")
    out_names = ctx.attr("output_vars")
    # append_backward cuts grad flow at stop_gradient/no_grad vars; the
    # in-span replay must honor the same cuts or recompute would change
    # the gradients (segmentation collects the names)
    stop_names = set(ctx.attr("stop_gradient_vars", []) or [])
    env = ctx.env
    xs = tuple(env[n] for n in op.input("X"))

    from ..lowering import dispatch

    @jax.checkpoint
    def segment(*vals):
        e2 = dict(zip(in_names, vals))
        for n in in_names:
            if n in stop_names:
                e2[n] = jax.lax.stop_gradient(e2[n])
        for sub_op in sub.ops:
            dispatch(sub_op, e2, state, sub)
            for names in sub_op.outputs.values():
                for n in names:
                    if n in stop_names and n in e2:
                        e2[n] = jax.lax.stop_gradient(e2[n])
        return tuple(e2[n] for n in out_names)

    outs = segment(*xs)
    for n, v in zip(op.output("Out"), outs):
        env[n] = v
