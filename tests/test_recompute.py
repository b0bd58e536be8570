"""RecomputeOptimizer (gradient checkpointing on jax.checkpoint).

Parity: training losses must be bit-identical with and without
rematerialization; the jaxpr must actually contain remat regions; RNG ops
inside a rematerialized span must replay identically.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid


def _mlp(recompute, dropout=False, wrap=None):
    """4-layer MLP; ``wrap(opt, h2) -> opt`` lets callers add decorators
    (AMP etc.) around the (possibly recompute-wrapped) optimizer."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h1 = fluid.layers.fc(x, size=32, act="relu")
            if dropout:
                h1 = fluid.layers.dropout(h1, dropout_prob=0.3)
            h2 = fluid.layers.fc(h1, size=32, act="relu")
            h3 = fluid.layers.fc(h2, size=32, act="relu")
            pred = fluid.layers.fc(h3, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            opt = fluid.optimizer.SGDOptimizer(0.1)
            if recompute:
                opt = fluid.optimizer.RecomputeOptimizer(opt)
                opt._set_checkpoints([h2])
            if wrap is not None:
                opt = wrap(opt, h2)
            opt.minimize(loss)
    return main, startup, loss


def _train(main, startup, loss, steps=5):
    rng = np.random.RandomState(0)
    xv = rng.randn(8, 16).astype(np.float32)
    yv = rng.randn(8, 1).astype(np.float32)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        return [float(np.asarray(exe.run(main, feed={"x": xv, "y": yv},
                                         fetch_list=[loss])[0]).reshape(()))
                for _ in range(steps)]


def test_recompute_loss_parity():
    plain = _train(*_mlp(False))
    remat = _train(*_mlp(True))
    np.testing.assert_allclose(plain, remat, rtol=0, atol=0)
    assert remat[-1] < remat[0]          # it actually trains


def test_recompute_structure_and_remat_in_jaxpr():
    import jax
    from paddle_tpu.fluid import executor as _exec
    from paddle_tpu.fluid.lowering import ExecState, run_block

    main, startup, loss = _mlp(True)
    ops = [o.type for o in main.global_block().ops]
    assert "recompute" in ops and "recompute_grad" in ops
    # intermediates of the packed span are gone from the main block
    assert ops.index("recompute") == 0

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        block = main.global_block()
        reads, _ = _exec._block_reads_writes(block, ["x", "y"])
        state_names = [n for n in reads
                       if scope.find_var(n) is not None]
        vals = [scope.find_var(n) for n in state_names]

        def step(state_vals, xv, yv):
            env = dict(zip(state_names, state_vals))
            env["x"], env["y"] = xv, yv
            st = ExecState(main.blocks, np.int32(0),
                           jax.random.PRNGKey(0))
            run_block(block, env, st)
            return env[loss.name]

        rng = np.random.RandomState(0)
        jaxpr = jax.make_jaxpr(step)(
            vals, rng.randn(8, 16).astype(np.float32),
            rng.randn(8, 1).astype(np.float32))
    assert "remat" in str(jaxpr), "jax.checkpoint did not engage"


def test_recompute_with_dropout_in_span_is_deterministic():
    """The RNG inside a rematerialized span must replay the same mask in
    forward and recomputed-backward (counter-based keys), so training is
    deterministic per (seed, step) AND bit-identical to the
    non-recompute baseline."""
    a = _train(*_mlp(True, dropout=True))
    b = _train(*_mlp(True, dropout=True))
    base = _train(*_mlp(False, dropout=True))
    np.testing.assert_allclose(a, b, rtol=0, atol=0)
    np.testing.assert_allclose(a, base, rtol=0, atol=0)
    assert a[-1] < a[0]


def test_recompute_leaves_a_learning_rate_schedule_outside_the_spans():
    """A schedule built BEFORE the model (its ops lead the block, in the
    LRSched role) is not packed into the first span: the optimizer ops that
    read the rate are appended after the segmentation, so a span would not
    hand it out.  The scheduled step equals the unsegmented one."""
    def build(recompute):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            rate = fluid.layers.linear_lr_warmup(0.1, 4, 0.0, 0.1)
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h1 = fluid.layers.fc(x, size=32, act="relu")
            h2 = fluid.layers.fc(h1, size=32, act="relu")
            loss = fluid.layers.mean(fluid.layers.square_error_cost(
                fluid.layers.fc(h2, size=1), y))
            opt = fluid.optimizer.SGDOptimizer(rate)
            if recompute:
                opt = fluid.optimizer.RecomputeOptimizer(opt)
                opt._set_checkpoints([h2])
            opt.minimize(loss)
        return main, startup, loss
    main, _, _ = build(True)
    kinds = [op.type for op in main.global_block().ops]
    assert "recompute" in kinds and kinds.index("recompute") > \
        kinds.index("increment")
    span, = [op for op in main.global_block().ops if op.type == "recompute"]
    assert "increment" not in [
        op.type for op in main.blocks[span.attr("sub_block")].ops]
    remat, plain = _train(*build(True)), _train(*build(False))
    np.testing.assert_allclose(remat, plain, rtol=0, atol=0)
    assert remat[0] == remat[1] > remat[-1]      # the first step's rate is 0


def test_recompute_requires_checkpoints():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, size=1))
            opt = fluid.optimizer.RecomputeOptimizer(
                fluid.optimizer.SGDOptimizer(0.1))
            with pytest.raises(ValueError):
                opt.minimize(loss)


def test_recompute_preserves_bn_running_stats():
    """Persistable in-place writes (batch_norm moving mean/variance)
    inside a span must survive as recompute outputs and keep updating."""
    def build(recompute):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name="x", shape=[8], dtype="float32")
                y = fluid.layers.data(name="y", shape=[1], dtype="float32")
                h = fluid.layers.fc(x, size=16)
                h = fluid.layers.batch_norm(h)
                h = fluid.layers.relu(h)
                h2 = fluid.layers.fc(h, size=16, act="relu")
                pred = fluid.layers.fc(h2, size=1)
                loss = fluid.layers.mean(
                    fluid.layers.square_error_cost(pred, y))
                opt = fluid.optimizer.SGDOptimizer(0.05)
                if recompute:
                    opt = fluid.optimizer.RecomputeOptimizer(opt)
                    opt._set_checkpoints([h2])
                opt.minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(1)
    xv = (rng.randn(16, 8) * 2 + 3).astype(np.float32)
    yv = rng.randn(16, 1).astype(np.float32)
    stats = {}
    from paddle_tpu.fluid.executor import global_scope
    for rc in (False, True):
        main, startup, loss = build(rc)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(4):
                exe.run(main, feed={"x": xv, "y": yv}, fetch_list=[loss])
            scope = global_scope()
            mean_name = [v.name for v in main.list_vars()
                         if v.name.endswith(".mean")][0]
            stats[rc] = np.array(scope.find_var_numpy(mean_name))
    assert np.abs(stats[True]).max() > 1e-3, "BN stats frozen at init"
    np.testing.assert_allclose(stats[False], stats[True], rtol=1e-5,
                               atol=1e-6)


def test_recompute_respects_stop_gradient():
    """A stop_gradient var interior to a span must cut grad flow exactly
    as append_backward does without recompute."""
    def build(recompute):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name="x", shape=[8], dtype="float32")
                y = fluid.layers.data(name="y", shape=[1], dtype="float32")
                h = fluid.layers.fc(x, size=16, act="relu")
                detached = fluid.layers.scale(h, scale=2.0)
                detached.stop_gradient = True
                h2 = fluid.layers.fc(h + detached, size=16, act="relu")
                pred = fluid.layers.fc(h2, size=1)
                loss = fluid.layers.mean(
                    fluid.layers.square_error_cost(pred, y))
                opt = fluid.optimizer.SGDOptimizer(0.1)
                if recompute:
                    opt = fluid.optimizer.RecomputeOptimizer(opt)
                    opt._set_checkpoints([h2])
                opt.minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(2)
    xv = rng.randn(8, 8).astype(np.float32)
    yv = rng.randn(8, 1).astype(np.float32)
    res = {}
    for rc in (False, True):
        main, startup, loss = build(rc)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            res[rc] = [float(np.asarray(
                exe.run(main, feed={"x": xv, "y": yv},
                        fetch_list=[loss])[0]).reshape(()))
                for _ in range(4)]
    np.testing.assert_allclose(res[False], res[True], rtol=0, atol=0)




def test_recompute_composes_with_amp_and_dp_mesh():
    """Recompute x pure-bf16 AMP x 8-device data parallel in one program
    (the composability bar the other optimizer wrappers meet)."""
    import paddle_tpu.fluid.contrib.mixed_precision as mp
    main, startup, loss = _mlp(
        True, wrap=lambda opt, h2: mp.decorate(
            opt, use_pure_bf16=True, use_dynamic_loss_scaling=False,
            init_loss_scaling=1.0))
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(3)
    xv = rng.randn(16, 16).astype(np.float32)
    yv = rng.randn(16, 1).astype(np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        ls = [float(np.asarray(exe.run(compiled, feed={"x": xv, "y": yv},
                                       fetch_list=[loss])[0]).mean())
              for _ in range(6)]
    assert all(np.isfinite(ls)) and ls[-1] < ls[0], ls


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-q"]))
