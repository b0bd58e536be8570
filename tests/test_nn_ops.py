"""Op tests for conv/pool/norm/loss lowerings (reference test_conv2d_op.py,
test_pool2d_op.py, test_batch_norm_op.py, test_softmax_with_cross_entropy_op.py
style: numpy oracle + finite-difference grads)."""

import numpy as np

from op_test import OpTest

rng = np.random.RandomState(1)


def conv2d_ref(x, w, stride, pad):
    n, c, h, ww = x.shape
    oc, ic, kh, kw = w.shape
    xp = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (ww + 2 * pad - kw) // stride + 1
    out = np.zeros((n, oc, oh, ow), np.float64)
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, :, i * stride:i * stride + kh,
                       j * stride:j * stride + kw]
            out[:, :, i, j] = np.tensordot(patch, w, axes=([1, 2, 3],
                                                           [1, 2, 3]))
    return out.astype(np.float32)


class TestConv2d(OpTest):
    op_type = "conv2d"

    def test_basic(self):
        x = rng.uniform(-1, 1, (2, 3, 7, 7)).astype(np.float32)
        w = rng.uniform(-1, 1, (4, 3, 3, 3)).astype(np.float32)
        self.inputs = {"Input": x, "Filter": w}
        self.outputs = {"Output": conv2d_ref(x, w, 1, 1)}
        self.attrs = {"strides": [1, 1], "paddings": [1, 1],
                      "dilations": [1, 1], "groups": 1}
        self.check_output(atol=1e-4)

    def test_stride2(self):
        x = rng.uniform(-1, 1, (1, 2, 8, 8)).astype(np.float32)
        w = rng.uniform(-1, 1, (3, 2, 3, 3)).astype(np.float32)
        self.inputs = {"Input": x, "Filter": w}
        self.outputs = {"Output": conv2d_ref(x, w, 2, 0)}
        self.attrs = {"strides": [2, 2], "paddings": [0, 0],
                      "dilations": [1, 1], "groups": 1}
        self.check_output(atol=1e-4)

    def test_grad(self):
        x = rng.uniform(-1, 1, (1, 2, 5, 5)).astype(np.float32)
        w = rng.uniform(-1, 1, (2, 2, 3, 3)).astype(np.float32)
        self.inputs = {"Input": x, "Filter": w}
        self.outputs = {"Output": None}
        self.attrs = {"strides": [1, 1], "paddings": [1, 1],
                      "dilations": [1, 1], "groups": 1}
        self.check_grad(["Input", "Filter"], "Output",
                        max_relative_error=2e-2)


class TestPool2d(OpTest):
    op_type = "pool2d"

    def test_max(self):
        x = rng.uniform(-1, 1, (2, 3, 6, 6)).astype(np.float32)
        ref = x.reshape(2, 3, 3, 2, 3, 2).max(axis=(3, 5))
        self.inputs = {"X": x}
        self.outputs = {"Out": ref}
        self.attrs = {"pooling_type": "max", "ksize": [2, 2],
                      "strides": [2, 2], "paddings": [0, 0]}
        self.check_output()
        self.check_grad(["X"], "Out")

    def test_avg(self):
        x = rng.uniform(-1, 1, (2, 3, 6, 6)).astype(np.float32)
        ref = x.reshape(2, 3, 3, 2, 3, 2).mean(axis=(3, 5))
        self.inputs = {"X": x}
        self.outputs = {"Out": ref}
        self.attrs = {"pooling_type": "avg", "ksize": [2, 2],
                      "strides": [2, 2], "paddings": [0, 0]}
        self.check_output()

    def test_global(self):
        x = rng.uniform(-1, 1, (2, 3, 5, 5)).astype(np.float32)
        ref = x.mean(axis=(2, 3), keepdims=True)
        self.inputs = {"X": x}
        self.outputs = {"Out": ref}
        self.attrs = {"pooling_type": "avg", "ksize": [1, 1],
                      "strides": [1, 1], "paddings": [0, 0],
                      "global_pooling": True}
        self.check_output()


class TestBatchNorm(OpTest):
    op_type = "batch_norm"

    def test_train_stats(self):
        x = rng.uniform(-1, 1, (4, 3, 5, 5)).astype(np.float32)
        scale = np.ones(3, np.float32)
        bias = np.zeros(3, np.float32)
        mean0 = np.zeros(3, np.float32)
        var0 = np.ones(3, np.float32)
        bm = x.mean(axis=(0, 2, 3))
        bv = x.var(axis=(0, 2, 3))
        y = (x - bm.reshape(1, 3, 1, 1)) / np.sqrt(
            bv.reshape(1, 3, 1, 1) + 1e-5)
        self.inputs = {"X": x, "Scale": scale, "Bias": bias,
                       "Mean": mean0, "Variance": var0}
        self.outputs = {"Y": y,
                        "MeanOut": [("mean_out", 0.9 * mean0 + 0.1 * bm)],
                        "VarianceOut": [("var_out", 0.9 * var0 + 0.1 * bv)],
                        "SavedMean": [("saved_mean", bm)],
                        "SavedVariance": [("saved_var", None)]}
        self.attrs = {"momentum": 0.9, "epsilon": 1e-5, "is_test": False}
        self.check_output(atol=1e-4)

    def test_infer(self):
        x = rng.uniform(-1, 1, (2, 3, 4, 4)).astype(np.float32)
        scale = rng.uniform(0.5, 1.5, 3).astype(np.float32)
        bias = rng.uniform(-0.5, 0.5, 3).astype(np.float32)
        mean0 = rng.uniform(-0.1, 0.1, 3).astype(np.float32)
        var0 = rng.uniform(0.5, 1.5, 3).astype(np.float32)
        y = (x - mean0.reshape(1, 3, 1, 1)) / np.sqrt(
            var0.reshape(1, 3, 1, 1) + 1e-5) * scale.reshape(1, 3, 1, 1) \
            + bias.reshape(1, 3, 1, 1)
        self.inputs = {"X": x, "Scale": scale, "Bias": bias,
                       "Mean": mean0, "Variance": var0}
        self.outputs = {"Y": y,
                        "MeanOut": [("mean_out", None)],
                        "VarianceOut": [("var_out", None)],
                        "SavedMean": [("saved_mean", None)],
                        "SavedVariance": [("saved_var", None)]}
        self.attrs = {"momentum": 0.9, "epsilon": 1e-5, "is_test": True}
        self.check_output(atol=1e-4)


class TestLayerNorm(OpTest):
    op_type = "layer_norm"

    def test(self):
        x = rng.uniform(-1, 1, (4, 6)).astype(np.float32)
        scale = rng.uniform(0.5, 1.5, 6).astype(np.float32)
        bias = rng.uniform(-0.5, 0.5, 6).astype(np.float32)
        mu = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        y = (x - mu) / np.sqrt(var + 1e-5) * scale + bias
        self.inputs = {"X": x, "Scale": scale, "Bias": bias}
        self.outputs = {"Y": y, "Mean": [("m", mu.reshape(4))],
                        "Variance": [("v", var.reshape(4))]}
        self.attrs = {"epsilon": 1e-5, "begin_norm_axis": 1}
        self.check_output(atol=1e-4)
        self.check_grad(["X", "Scale", "Bias"], "Y",
                        max_relative_error=2e-2)


class TestSoftmaxWithCE(OpTest):
    op_type = "softmax_with_cross_entropy"

    def test_hard_label(self):
        logits = rng.uniform(-2, 2, (5, 7)).astype(np.float32)
        label = rng.randint(0, 7, (5, 1)).astype(np.int64)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        loss = -np.log(sm[np.arange(5), label.ravel()]).reshape(5, 1)
        self.inputs = {"Logits": logits, "Label": label}
        self.outputs = {"Softmax": sm, "Loss": loss.astype(np.float32)}
        self.attrs = {"soft_label": False}
        self.check_output(atol=1e-5)
        self.check_grad(["Logits"], "Loss")

    def test_soft_label(self):
        logits = rng.uniform(-2, 2, (4, 6)).astype(np.float32)
        label = rng.uniform(0, 1, (4, 6)).astype(np.float32)
        label /= label.sum(-1, keepdims=True)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        loss = -(label * np.log(sm)).sum(-1, keepdims=True)
        self.inputs = {"Logits": logits, "Label": label}
        self.outputs = {"Softmax": sm, "Loss": loss.astype(np.float32)}
        self.attrs = {"soft_label": True}
        self.check_output(atol=1e-5)


class TestCrossEntropy(OpTest):
    op_type = "cross_entropy"

    def test(self):
        probs = rng.uniform(0.05, 1, (4, 5)).astype(np.float32)
        probs /= probs.sum(-1, keepdims=True)
        label = rng.randint(0, 5, (4, 1)).astype(np.int64)
        loss = -np.log(probs[np.arange(4), label.ravel()]).reshape(4, 1)
        self.inputs = {"X": probs, "Label": label}
        self.outputs = {"Y": loss.astype(np.float32)}
        self.attrs = {}
        self.check_output(atol=1e-5)


class TestLookupTable(OpTest):
    op_type = "lookup_table"

    def test(self):
        w = rng.uniform(-1, 1, (10, 4)).astype(np.float32)
        ids = rng.randint(0, 10, (5, 1)).astype(np.int64)
        self.inputs = {"W": w, "Ids": ids}
        self.outputs = {"Out": w[ids.ravel()]}
        self.attrs = {"padding_idx": -1}
        self.check_output()
        self.check_grad(["W"], "Out")

    def test_padding_idx(self):
        w = rng.uniform(-1, 1, (6, 3)).astype(np.float32)
        ids = np.array([[0], [2], [2], [5]], np.int64)
        ref = w[ids.ravel()].copy()
        ref[ids.ravel() == 2] = 0
        self.inputs = {"W": w, "Ids": ids}
        self.outputs = {"Out": ref}
        self.attrs = {"padding_idx": 2}
        self.check_output()


class TestDropoutInfer(OpTest):
    op_type = "dropout"

    def test_is_test(self):
        x = rng.uniform(-1, 1, (4, 5)).astype(np.float32)
        self.inputs = {"X": x}
        self.outputs = {"Out": x * 0.5, "Mask": [("mask", None)]}
        self.attrs = {"dropout_prob": 0.5, "is_test": True}
        self.check_output()

    def test_upscale_train_mean_preserving(self):
        # statistical check: E[out] ≈ x for upscale_in_train
        import paddle_tpu.fluid as fluid
        x = np.ones((1000,), np.float32)
        data = fluid.layers.data(name="xd", shape=[1000],
                                 append_batch_size=False, dtype="float32")
        out = fluid.layers.dropout(data, 0.3,
                                   dropout_implementation="upscale_in_train")
        exe = fluid.Executor(fluid.CPUPlace())
        res, = exe.run(feed={"xd": x}, fetch_list=[out])
        assert abs(res.mean() - 1.0) < 0.1
        assert set(np.round(np.unique(res), 4)) <= {0.0, np.float32(
            np.round(1 / 0.7, 4))}


