"""Control flow: While / cond / Switch / IfElse / StaticRNN / DynamicRNN.

Mirrors the reference's test_while_op.py / test_cond.py /
test_recurrent_op.py shapes: build tiny programs, run on the executor,
compare against numpy oracles.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers


def _run(main, startup, feed, fetch_list):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        return exe.run(main, feed=feed, fetch_list=fetch_list)


def test_while_counts_and_accumulates():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        i = layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = layers.fill_constant(shape=[1], dtype="int64", value=10)
        acc = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        cond = layers.less_than(x=i, y=limit)
        w = layers.While(cond=cond)
        with w.block():
            one = layers.fill_constant(shape=[1], dtype="float32", value=2.0)
            layers.assign(acc + one, output=acc)
            layers.increment(x=i, value=1, in_place=True)
            layers.less_than(x=i, y=limit, cond=cond)
    i_out, acc_out = _run(main, startup, {}, [i, acc])
    assert int(i_out[0]) == 10
    np.testing.assert_allclose(acc_out, [20.0], rtol=1e-6)


def test_while_with_tensor_array():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        i = layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = layers.fill_constant(shape=[1], dtype="int64", value=5)
        arr = layers.create_array("float32", max_len=8)
        x = layers.fill_constant(shape=[3], dtype="float32", value=1.0)
        cond = layers.less_than(x=i, y=limit)
        w = layers.While(cond=cond)
        with w.block():
            fi = layers.cast(i, "float32")
            layers.array_write(x * fi, i, array=arr)
            layers.increment(x=i, value=1, in_place=True)
            layers.less_than(x=i, y=limit, cond=cond)
        stacked = layers.tensor.create_tensor("float32")
        n = layers.array_length(arr)
        main.current_block().append_op(
            "tensor_array_to_tensor", inputs={"X": [arr]},
            outputs={"Out": [stacked], "OutIndex": []},
            attrs={"axis": 0, "use_stack": True})
    out, n_out = _run(main, startup, {}, [stacked, n])
    assert int(n_out[0]) == 5
    expect = np.arange(5, dtype=np.float32)[:, None] * np.ones((5, 3), np.float32)
    np.testing.assert_allclose(out[:5], expect, rtol=1e-6)
    np.testing.assert_allclose(out[5:], 0.0)  # fixed-capacity zero padding


def test_cond_layer_both_branches():
    for flag, expect in [(1.0, 14.0), (0.0, 3.75)]:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="x", shape=[4], dtype="float32",
                            append_batch_size=False)
            pred_v = layers.fill_constant(shape=[1], dtype="float32",
                                          value=flag)
            half = layers.fill_constant(shape=[1], dtype="float32", value=0.5)
            pred = layers.greater_than(pred_v, half)
            out = layers.cond(pred,
                              lambda: layers.reduce_sum(x * 2.0),
                              lambda: layers.reduce_mean(x + 2.0))
        res, = _run(main, startup,
                    {"x": np.array([1, 2, 3, 1], np.float32)}, [out])
        np.testing.assert_allclose(res, expect, rtol=1e-6)


def test_cond_propagates_outer_writes():
    # assign(..., output=outer_var) inside a branch must merge through
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        lr = layers.tensor.create_global_var(
            shape=[1], value=1.0, dtype="float32", persistable=True,
            name="cond_lr")
        layers.assign(layers.fill_constant([1], "float32", 1.0), output=lr)
        one = layers.fill_constant(shape=[1], dtype="float32", value=1.0)
        zero = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        pred = layers.greater_than(one, zero)  # True

        def true_fn():
            layers.assign(layers.fill_constant([1], "float32", 42.0),
                          output=lr)

        layers.cond(pred, true_fn, lambda: None)
    res, = _run(main, startup, {}, [lr])
    np.testing.assert_allclose(res, [42.0], rtol=1e-6)


def test_switch_first_match_wins():
    # the LR-warmup shape: pick a value by which region step falls in
    for step_val, expect in [(0.0, 0.1), (5.0, 0.2), (50.0, 0.3)]:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            step = layers.fill_constant(shape=[1], dtype="float32",
                                        value=step_val)
            lr = layers.tensor.create_global_var(
                shape=[1], value=0.0, dtype="float32", persistable=True,
                name="sw_lr")
            b1 = layers.fill_constant(shape=[1], dtype="float32", value=1.0)
            b2 = layers.fill_constant(shape=[1], dtype="float32", value=10.0)
            with layers.Switch() as switch:
                with switch.case(layers.less_than(step, b1)):
                    layers.assign(layers.fill_constant(
                        shape=[1], dtype="float32", value=0.1), output=lr)
                with switch.case(layers.less_than(step, b2)):
                    layers.assign(layers.fill_constant(
                        shape=[1], dtype="float32", value=0.2), output=lr)
                with switch.default():
                    layers.assign(layers.fill_constant(
                        shape=[1], dtype="float32", value=0.3), output=lr)
        res, = _run(main, startup, {}, [lr])
        np.testing.assert_allclose(res, [expect], rtol=1e-6)


def test_ifelse_merges_by_mask():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[1], dtype="float32")
        zero = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
        cond = layers.greater_than(x, zero)
        ie = layers.IfElse(cond)
        with ie.true_block():
            ie.output(x * 2.0)
        with ie.false_block():
            ie.output(x - 1.0)
        out = ie()
    xv = np.array([[1.0], [-2.0], [3.0]], np.float32)
    res, = _run(main, startup, {"x": xv}, [out])
    np.testing.assert_allclose(res, np.where(xv > 0, xv * 2, xv - 1),
                               rtol=1e-6)


def test_static_rnn_matches_numpy_and_trains():
    T, B, D, H = 4, 2, 3, 5
    np.random.seed(0)
    x_np = np.random.randn(T, B, D).astype(np.float32)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[T, B, D], dtype="float32",
                            append_batch_size=False)
            rnn = layers.StaticRNN()
            with rnn.step():
                x_t = rnn.step_input(x)
                h_pre = rnn.memory(shape=[H], batch_ref=x_t, dtype="float32")
                h = layers.fc(input=layers.concat([x_t, h_pre], axis=1),
                              size=H, act="tanh", bias_attr=False,
                              param_attr=fluid.ParamAttr(name="rnn_w"))
                rnn.update_memory(h_pre, h)
                rnn.step_output(h)
            out = rnn()
            loss = layers.reduce_mean(out)
            opt = fluid.optimizer.SGDOptimizer(learning_rate=0.1)
            opt.minimize(loss)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        w = np.array(scope.find_var("rnn_w"))
        out_v, loss0 = exe.run(main, feed={"x": x_np},
                               fetch_list=[out, loss])
        # numpy oracle
        h = np.zeros((B, H), np.float32)
        ys = []
        for t in range(T):
            h = np.tanh(np.concatenate([x_np[t], h], axis=1) @ w)
            ys.append(h)
        np.testing.assert_allclose(out_v, np.stack(ys), rtol=2e-5, atol=2e-5)
        # gradient flowed into the weight: loss moves under SGD
        _, loss1 = exe.run(main, feed={"x": x_np}, fetch_list=[out, loss])
        assert not np.allclose(loss0, loss1)


def test_dynamic_rnn_masks_past_lengths():
    B, T, D, H = 3, 5, 2, 4
    np.random.seed(1)
    x_np = np.random.randn(B, T, D).astype(np.float32)
    len_np = np.array([5, 2, 3], np.int64)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[B, T, D], dtype="float32",
                            append_batch_size=False)
            lens = layers.data(name="lens", shape=[B], dtype="int64",
                               append_batch_size=False)
            drnn = layers.DynamicRNN()
            with drnn.block():
                x_t = drnn.step_input(x, lengths=lens)
                h_pre = drnn.memory(shape=[H], batch_ref=x_t,
                                    dtype="float32")
                h = layers.fc(input=layers.concat([x_t, h_pre], axis=1),
                              size=H, act="tanh", bias_attr=False,
                              param_attr=fluid.ParamAttr(name="drnn_w"))
                drnn.update_memory(h_pre, h)
                drnn.output(h)
            out = drnn()  # [B, T, H]

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        w = np.array(scope.find_var("drnn_w"))
        out_v, = exe.run(main, feed={"x": x_np, "lens": len_np},
                         fetch_list=[out])
    # oracle: masked recurrence; outputs zero past each length (LoD "absent")
    h = np.zeros((B, H), np.float32)
    ys = []
    for t in range(T):
        h_new = np.tanh(np.concatenate([x_np[:, t], h], axis=1) @ w)
        mask = (t < len_np)[:, None]
        h = np.where(mask, h_new, h)
        ys.append(np.where(mask, h, 0.0))
    oracle = np.stack(ys, axis=1)
    np.testing.assert_allclose(out_v, oracle, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out_v[1, 2:], 0.0)


# -- recurrent's own grad lowering (one reverse scan over saved carries) -----

def _rnn_program(kind, differentiate=True):
    """A small recurrent training program of each kind the lowering has to
    keep whole: stacked outputs, final states, ``reverse``, an integer
    memory and step input that take no gradient, a loop inside the loop,
    and a loop with a step count and no step input.  Returns what to
    fetch: outputs, final states and the gradient of every input."""
    T, B, D, H = 4, 2, 3, 5
    rng = np.random.default_rng(3)
    feed = {"x": rng.normal(size=(T, B, D)).astype(np.float32)}
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[T, B, D], dtype="float32",
                        append_batch_size=False)
        x.stop_gradient = False
        wrt, fetch = [x], []

        def cell(inp, pre, name):
            return layers.fc(input=layers.concat([inp, pre], axis=1),
                             size=H, act="tanh", bias_attr=False,
                             param_attr=fluid.ParamAttr(name=name))

        if kind == "dynamic":
            feed["x"] = np.ascontiguousarray(feed["x"].transpose(1, 0, 2))
            feed["lens"] = np.array([4, 2], np.int64)
            main.global_block().vars["x"].shape = (B, T, D)
            lens = layers.data(name="lens", shape=[B], dtype="int64",
                               append_batch_size=False)
            rnn = layers.DynamicRNN()
            with rnn.block():
                x_t = rnn.step_input(x, lengths=lens)
                pre = rnn.memory(shape=[H], batch_ref=x_t, dtype="float32")
                h = cell(x_t, pre, "w")
                rnn.update_memory(pre, h)
                rnn.output(h)
            out, inner = rnn(), rnn._rnn
        elif kind == "counted":
            h0 = layers.fc(layers.reshape(x, [T * B, D]), H, bias_attr=False,
                           param_attr=fluid.ParamAttr(name="w_in"))
            rnn = inner = layers.StaticRNN(steps=3)
            with rnn.step():
                pre = rnn.memory(init=h0)
                h = layers.fc(pre, H, act="tanh", bias_attr=False,
                              param_attr=fluid.ParamAttr(name="w"))
                rnn.update_memory(pre, h)
                rnn.step_output(layers.reduce_sum(h * h, dim=-1))
            out = rnn()
        else:
            rnn = inner = layers.StaticRNN()
            with rnn.step():
                x_t = rnn.step_input(x)
                pre = rnn.memory(shape=[H], batch_ref=x_t, dtype="float32")
                if kind == "integers":
                    count = rnn.memory(shape=[1], batch_ref=x_t,
                                       dtype="int64")
                    rnn.update_memory(count, count + 1)
                    h = cell(x_t * layers.cast(count + 1, "float32"), pre,
                             "w")
                elif kind == "nested":
                    sub = layers.StaticRNN(steps=2)
                    with sub.step():
                        s_pre = sub.memory(init=pre)
                        s_h = cell(x_t, s_pre, "w")
                        sub.update_memory(s_pre, s_h)
                        sub.step_output(s_h)
                    h = layers.reduce_sum(sub(), dim=0)
                else:
                    h = cell(x_t, pre, "w")
                rnn.update_memory(pre, h)
                rnn.step_output(h)
            out = rnn()
            if kind == "reverse":
                main.global_block().ops[-1].attrs["reverse"] = True
        finals = [f for f in inner._final_vars if f.dtype == "float32"
                  or "float" in str(f.dtype)]
        loss = layers.reduce_mean(out * out)
        for f in finals:
            loss = loss + layers.reduce_sum(f * f)
        if not differentiate:
            return main, startup, feed, [out]
        params_grads = fluid.backward.append_backward(loss)
        fetch = [out] + list(inner._final_vars) + \
            [main.global_block().var(main._grad_name_map[v.name])
             for v in wrt] + [g for _, g in params_grads]
    return main, startup, feed, fetch


def _close(got, want, tol=2e-5):
    """Float32 on both sides, another order of summation: ``tol`` of the
    tensor's largest entry."""
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * max(np.abs(want).max(), 1e-30))


@pytest.mark.parametrize("kind", ["plain", "reverse", "dynamic", "integers",
                                  "nested", "counted"])
def test_recurrent_grad_lowering_equals_the_replay(kind, monkeypatch):
    """``recurrent_grad``'s own lowering (a reverse scan that
    rematerialises each step from the saved carry) against
    ``generic_grad_lower`` (``jax.vjp`` of a second run of the whole scan)
    on the same program: outputs, final states, and the gradient of the
    step inputs and of every parameter."""
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.fluid.registry import OP_DEFS

    counted = telemetry.registry().counter("recurrent_grad_lowered_total")
    before = counted.value()
    own = _run(*_rnn_program(kind))
    assert counted.value() == before + 1
    monkeypatch.setattr(OP_DEFS["recurrent"], "grad_lower", None)
    replayed = _run(*_rnn_program(kind))
    assert counted.value() == before + 1                 # generic: uncounted
    assert len(own) == len(replayed) >= 4
    for got, want in zip(own, replayed):
        _close(got, want)
    assert all(np.abs(g).max() > 0 for g in own[-2:])


def test_recurrent_grad_without_the_carries_says_so():
    """``StaticRNN`` always writes the ``Carries`` slot; a grad op that lost
    it (a program description edited by hand) is refused, not guessed at."""
    main, startup, feed, fetch = _rnn_program("plain")
    for op in main.global_block().ops:
        if op.type == "recurrent_grad":
            del op.inputs["Carries"]
    main._bump_version()
    with pytest.raises(Exception, match="Carries"):
        _run(main, startup, feed, fetch)


def test_carries_are_written_only_where_a_grad_op_reads_them():
    from paddle_tpu.fluid import telemetry

    counted = telemetry.registry().counter("recurrent_lowered_total")
    main, startup, feed, fetch = _rnn_program("plain")
    before = counted.value(steps="4", saves="1"), \
        counted.value(steps="4", saves="0")
    _run(main, startup, feed, fetch)
    assert counted.value(steps="4", saves="1") == before[0] + 1
    _run(*_rnn_program("plain", differentiate=False))
    assert counted.value(steps="4", saves="0") == before[1] + 1


def test_static_rnn_takes_a_step_count_where_it_has_no_step_input():
    B, H = 2, 3
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        h0 = layers.data(name="h0", shape=[B, H], dtype="float32",
                         append_batch_size=False)
        rnn = layers.StaticRNN(steps=5)
        with rnn.step():
            pre = rnn.memory(init=h0)
            nxt = pre * 0.5 + 1.0
            rnn.update_memory(pre, nxt)
            rnn.step_output(nxt)
        out = rnn()
        assert tuple(out.shape) == (5, B, H)
        with pytest.raises(ValueError, match="steps"):
            bad = layers.StaticRNN()
            with bad.step():
                pre = bad.memory(init=h0)
                bad.update_memory(pre, pre * 2.0)
                bad.step_output(pre)
    h = np.arange(B * H, dtype=np.float32).reshape(B, H)
    got, = _run(main, startup, {"h0": h}, [out])
    want = []
    for _ in range(5):
        h = h * 0.5 + 1.0
        want.append(h)
    np.testing.assert_allclose(got, np.stack(want), rtol=1e-6)
