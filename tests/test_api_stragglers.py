"""Reference API surface stragglers: name_scope/places/unique_name.switch,
WeightedAverage, ParallelExecutor, BilinearInitializer, dygraph LR
schedulers (+ per-step optimizer integration), dygraph Conv3DTranspose /
TreeConv, profiler.reset_profiler."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import dygraph


def test_framework_helpers():
    assert fluid.is_compiled_with_cuda() is False
    assert len(fluid.cpu_places(3)) == 3
    assert len(fluid.cuda_pinned_places(2)) == 2
    with fluid.name_scope("outer"):
        with fluid.name_scope("inner"):
            from paddle_tpu.fluid.framework import current_name_scope
            assert current_name_scope() == "outer/inner"
    gen = fluid.unique_name.switch()
    n1 = fluid.unique_name.generate("x")
    fluid.unique_name.switch(gen)
    assert n1 == "x_0"


def test_weighted_average():
    w = fluid.average.WeightedAverage()
    w.add(2.0, 1.0)
    w.add(4.0, 3.0)
    assert abs(w.eval() - 3.5) < 1e-12
    w.reset()
    with pytest.raises(ValueError):
        w.eval()


def test_parallel_executor_facade():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.fc(x, size=2)
            loss = fluid.layers.reduce_mean(y)
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                    main_program=main)
        rng = np.random.RandomState(0)
        v, = pe.run(fetch_list=[loss.name],
                    feed={"x": rng.rand(8, 4).astype(np.float32)})
        assert np.isfinite(np.asarray(v)).all()
        assert pe.device_count >= 1


def test_bilinear_initializer():
    from paddle_tpu.fluid.initializer import Bilinear
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[1, 4, 4],
                                  dtype="float32")
            up = fluid.layers.conv2d_transpose(
                x, num_filters=1, filter_size=4, stride=2, padding=1,
                param_attr=fluid.ParamAttr(name="bw",
                                           initializer=Bilinear()),
                bias_attr=False)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w = fluid.global_scope().find_var_numpy("bw")
    # symmetric center-heavy bilinear stencil
    np.testing.assert_allclose(w[0, 0], w[0, 0].T, atol=1e-6)
    assert w[0, 0, 1, 1] > w[0, 0, 0, 0]


def test_dygraph_lr_schedulers_values():
    from paddle_tpu.fluid.dygraph import (
        ExponentialDecay, NaturalExpDecay, InverseTimeDecay,
        PolynomialDecay, CosineDecay, NoamDecay, PiecewiseDecay)
    e = ExponentialDecay(0.1, decay_steps=2, decay_rate=0.5)
    assert [round(e(), 6) for _ in range(3)] == \
        [0.1, round(0.1 * 0.5 ** 0.5, 6), 0.05]
    p = PiecewiseDecay([2, 4], [1.0, 0.5, 0.25], begin=0)
    assert [p() for _ in range(5)] == [1.0, 1.0, 0.5, 0.5, 0.25]
    n = NoamDecay(d_model=512, warmup_steps=10, begin=1)
    v1, v2 = n(), n()
    assert v2 > v1                     # warmup ramps up
    i = InverseTimeDecay(1.0, 1, 1.0)
    assert abs(i() - 1.0) < 1e-9 and abs(i() - 0.5) < 1e-9
    pd = PolynomialDecay(1.0, decay_steps=10, end_learning_rate=0.0)
    first = pd()
    assert abs(first - 1.0) < 1e-9 and pd() < first
    c = CosineDecay(1.0, step_each_epoch=1, epochs=4)
    vals = [c() for _ in range(4)]
    assert vals[0] == 1.0 and vals[-1] < vals[0]
    ne = NaturalExpDecay(1.0, 1, 1.0)
    ne()
    assert abs(ne() - np.exp(-1.0)) < 1e-9


def test_dygraph_scheduler_drives_optimizer():
    from paddle_tpu.fluid.dygraph import ExponentialDecay
    with dygraph.guard():
        model = dygraph.nn.FC(size=1, input_dim=3)
        sched = ExponentialDecay(0.5, decay_steps=1, decay_rate=0.1)
        opt = fluid.optimizer.SGDOptimizer(learning_rate=sched)
        x_np = np.ones((2, 3), np.float32)
        w_hist = []
        for _ in range(2):
            x = dygraph.to_variable(x_np)
            out = model(x)
            loss, = dygraph.trace_op(
                "reduce_mean", {"X": [out]},
                {"Out": 1}, {"dim": None, "keep_dim": False,
                             "reduce_all": True})["Out"]
            loss.backward()
            opt.minimize(loss, parameter_list=model.parameters())
            model.clear_gradients()
            w_hist.append(np.asarray(model.parameters()[0].value).copy())
        assert sched.step_num == 2
        # step-2 update is 10x smaller than step-1 (lr decayed 0.5 → 0.05)
        d1 = np.abs(w_hist[0]).max()
        d2 = np.abs(w_hist[1] - w_hist[0]).max()
        assert d2 < d1


def test_dygraph_conv3d_transpose_and_tree_conv():
    with dygraph.guard():
        m = dygraph.Conv3DTranspose(num_channels=2, num_filters=3,
                                    filter_size=3)
        x = dygraph.to_variable(
            np.random.RandomState(0).rand(1, 2, 4, 4, 4)
            .astype(np.float32))
        out = m(x)
        assert out.numpy().shape[1] == 3

        tc = dygraph.TreeConv(feature_size=4, output_size=3,
                              bias_attr=False)
        nodes = dygraph.to_variable(np.eye(4, dtype=np.float32)[None])
        edges = dygraph.to_variable(
            np.array([[[1, 2], [1, 3]]], np.int64))
        o = tc(nodes, edges)
        assert o.numpy().shape == (1, 4, 3)


def test_reset_profiler():
    from paddle_tpu.fluid import profiler
    profiler.start_profiler()
    with profiler.RecordEvent("evt"):
        pass
    profiler.reset_profiler()
    trace = profiler.stop_profiler(profile_path=None)
    assert not [e for e in trace["traceEvents"] if e.get("cat") == "host"]


def test_utils_ploter_and_image(tmp_path):
    from paddle_tpu.utils import Ploter, image_util
    p = Ploter("train_cost", "test_cost")
    p.append("train_cost", 0, 2.0)
    p.append("train_cost", 1, 1.0)
    p.plot(str(tmp_path / "c.png"))
    p.reset()
    assert p.__plot_data__["train_cost"].step == []

    im = np.arange(6 * 6 * 3, dtype=np.uint8).reshape(6, 6, 3)
    out = image_util.simple_transform(im, crop_size=4,
                                      mean=[0.0, 0.0, 0.0], scale=1 / 255.)
    assert out.shape == (3, 4, 4)
    assert out.dtype == np.float32 and out.max() <= 1.0
