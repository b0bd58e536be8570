"""Layer-surface batch 4: smoke + oracle checks for the wrappers closing
the reference layers/nn.py __all__ gap."""

import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

_REFERENCE = "/root/reference/python/paddle/fluid"
needs_reference = pytest.mark.skipif(
    not os.path.isdir(_REFERENCE),
    reason="the reference checkout (%s) is not mounted on this machine"
    % _REFERENCE)


def _run(build, feeds):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            fetch = build()
    if not isinstance(fetch, (list, tuple)):
        fetch = [fetch]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return [np.asarray(v) for v in
                exe.run(main, feed=feeds, fetch_list=list(fetch))]


@needs_reference
def test_surface_parity_with_reference_nn():
    """The FULL reference layers/nn.py __all__ resolves here (171/171
    since r2 second half — similarity_focus, tree_conv, deformable_conv,
    deformable_roi_pooling were the last four)."""
    import re
    src = open(_REFERENCE + "/layers/nn.py").read()
    m = re.search(r"__all__ = \[(.*?)\]", src, re.S)
    ref = re.findall(r"'([a-z0-9_]+)'", m.group(1))
    have = [n for n in ref if hasattr(layers, n)]
    missing = [n for n in ref if n not in have]
    assert not missing, missing


def test_pool_and_logic_wrappers():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 6, 6).astype(np.float32)

    def build():
        xv = layers.data(name="x", shape=[2, 3, 6, 6], dtype="float32",
                         append_batch_size=False)
        ap = layers.adaptive_pool2d(xv, [2, 2], pool_type="avg")
        mx = layers.adaptive_pool2d(xv, [3, 3], pool_type="max")
        a = layers.reduce_all(layers.logical_not(
            layers.logical_and(xv > 100.0, xv > 100.0)))
        return ap, mx, a

    ap, mx, allv = _run(build, {"x": x})
    np.testing.assert_allclose(ap[0, 0, 0, 0], x[0, 0, :3, :3].mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(mx[0, 0, 0, 0], x[0, 0, :2, :2].max(),
                               rtol=1e-5)
    assert bool(allv)


def test_ctc_greedy_decoder_and_hash():
    probs = np.zeros((1, 5, 3), np.float32)
    for t, c in enumerate([1, 1, 0, 2, 2]):
        probs[0, t, c] = 1.0

    def build():
        pv = layers.data(name="p", shape=[1, 5, 3], dtype="float32",
                         append_batch_size=False)
        ln = layers.data(name="l", shape=[1], dtype="int64",
                         append_batch_size=False)
        ids, oln = layers.ctc_greedy_decoder(pv, blank=0, length=ln)
        iv = layers.data(name="i", shape=[4, 1], dtype="int64",
                         append_batch_size=False)
        h = layers.hash(iv, hash_size=100)
        return ids, oln, h

    ids, oln, h = _run(build, {"p": probs,
                               "l": np.array([5], np.int64),
                               "i": np.arange(4).reshape(4, 1)})
    np.testing.assert_array_equal(ids[0, :2], [1, 2])   # collapse 1 1 _ 2 2
    assert int(oln[0]) == 2
    assert h.min() >= 0 and h.max() < 100
    assert len(np.unique(h)) > 1


def test_dynamic_lstmp_and_stacked_lstm():
    rng = np.random.RandomState(1)
    B, T, D, P = 2, 5, 8, 4
    x = rng.randn(B, T, 4 * D).astype(np.float32)
    lens = np.array([5, 3], np.int64)

    def build():
        xv = layers.data(name="x", shape=[B, T, 4 * D], dtype="float32",
                         append_batch_size=False)
        ln = layers.data(name="len", shape=[B], dtype="int64",
                         append_batch_size=False)
        proj, cell = layers.dynamic_lstmp(xv, 4 * D, P, length=ln)
        raw = layers.data(name="raw", shape=[B, T, 6], dtype="float32",
                          append_batch_size=False)
        out, last_h, _ = layers.lstm(raw, None, None, T, hidden_size=D,
                                     num_layers=2, length=ln)
        return proj, cell, out, last_h

    proj, cell, out, last_h = _run(
        build, {"x": x, "len": lens,
                "raw": rng.randn(B, T, 6).astype(np.float32)})
    assert proj.shape == (B, T, P) and cell.shape == (B, T, D)
    assert proj[1, 3:].max() == 0          # masked past length
    assert out.shape == (B, T, D) and last_h.shape == (B, D)


def test_data_norm_affine_grid_psroi():
    rng = np.random.RandomState(2)
    x = rng.randn(8, 4).astype(np.float32) * 3 + 1

    def build():
        xv = layers.data(name="x", shape=[8, 4], dtype="float32",
                         append_batch_size=False)
        dn = layers.data_norm(xv)
        th = layers.data(name="th", shape=[1, 2, 3], dtype="float32",
                         append_batch_size=False)
        grid = layers.affine_grid(th, [1, 1, 4, 4])
        fm = layers.data(name="fm", shape=[1, 8, 6, 6], dtype="float32",
                         append_batch_size=False)
        rois = layers.data(name="r", shape=[1, 4], dtype="float32",
                           append_batch_size=False)
        ps = layers.psroi_pool(fm, rois, output_channels=2,
                               spatial_scale=1.0, pooled_height=2,
                               pooled_width=2)
        return dn, grid, ps

    theta = np.array([[[1, 0, 0], [0, 1, 0]]], np.float32)  # identity
    dn, grid, ps = _run(build, {
        "x": x, "th": theta,
        "fm": rng.randn(1, 8, 6, 6).astype(np.float32),
        "r": np.array([[0, 0, 5, 5]], np.float32)})
    assert dn.shape == x.shape and np.isfinite(dn).all()
    # identity grid spans [-1, 1]
    np.testing.assert_allclose(grid[0, 0, 0], [-1, -1], atol=1e-6)
    np.testing.assert_allclose(grid[0, -1, -1], [1, 1], atol=1e-6)
    assert ps.shape == (1, 2, 2, 2)


def test_composed_losses():
    rng = np.random.RandomState(3)

    def build():
        p = layers.data(name="p", shape=[4, 6], dtype="float32",
                        append_batch_size=False)
        m = layers.data(name="m", shape=[4, 6], dtype="int64",
                        append_batch_size=False)
        dl = layers.dice_loss(p, m)
        a = layers.data(name="a", shape=[6, 8], dtype="float32",
                        append_batch_size=False)
        pos = layers.data(name="pos", shape=[6, 8], dtype="float32",
                          append_batch_size=False)
        lab = layers.data(name="lab", shape=[6], dtype="int64",
                          append_batch_size=False)
        npl = layers.npair_loss(a, pos, lab)
        f1 = layers.data(name="f1", shape=[2, 3, 4, 4], dtype="float32",
                         append_batch_size=False)
        f2 = layers.data(name="f2", shape=[2, 5, 4, 4], dtype="float32",
                         append_batch_size=False)
        fsp = layers.fsp_matrix(f1, f2)
        return dl, npl, fsp

    probs = rng.rand(4, 6).astype(np.float32)
    mask = (rng.rand(4, 6) > 0.5).astype(np.int64)
    dl, npl, fsp = _run(build, {
        "p": probs, "m": mask,
        "a": rng.randn(6, 8).astype(np.float32),
        "pos": rng.randn(6, 8).astype(np.float32),
        "lab": np.array([0, 0, 1, 1, 2, 2], np.int64),
        "f1": rng.randn(2, 3, 4, 4).astype(np.float32),
        "f2": rng.randn(2, 5, 4, 4).astype(np.float32)})
    inter = (probs * mask).sum()
    want_dice = 1 - 2 * inter / (probs.sum() + mask.sum() + 1e-5)
    np.testing.assert_allclose(float(dl), want_dice, rtol=1e-4)
    assert np.isfinite(npl).all() and float(npl) > 0
    assert fsp.shape == (2, 3, 5)


def test_install_check_runs():
    assert fluid.install_check.run_check(use_device="cpu")


def _reference_all(path):
    """Extract a reference module's literal __all__ list."""
    import ast
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", SyntaxWarning)
        tree = ast.parse(path.read_text())
    for node in tree.body:
        if isinstance(node, ast.Assign) and \
                getattr(node.targets[0], "id", "") == "__all__":
            return [ast.literal_eval(e) for e in node.value.elts]
    return []


@needs_reference
def test_all_reference_layer_modules_resolve():
    """Every name in every reference layers/<mod>.py __all__ resolves on
    fluid.layers (nn.py is asserted separately above)."""
    import pathlib
    import paddle_tpu.fluid as fluid

    base = pathlib.Path(_REFERENCE) / "layers"
    missing = {}
    for mod in ["control_flow", "tensor", "io", "detection", "metric_op",
                "learning_rate_scheduler"]:
        names = _reference_all(base / (mod + ".py"))
        gone = [n for n in names if not hasattr(fluid.layers, n)]
        if gone:
            missing[mod] = gone
    assert not missing, missing


@needs_reference
def test_all_reference_fluid_module_surfaces_resolve():
    """Every __all__ name in the reference's top-level fluid modules
    resolves on the matching paddle_tpu module (the r2 surface audit,
    frozen as a test)."""
    import pathlib
    import paddle_tpu.fluid as fluid

    base = pathlib.Path(_REFERENCE)

    targets = {
        "optimizer": fluid.optimizer, "initializer": fluid.initializer,
        "regularizer": fluid.regularizer, "clip": fluid.clip,
        "metrics": fluid.metrics, "nets": fluid.nets,
        "profiler": fluid.profiler, "framework": fluid,
        "parallel_executor": fluid, "unique_name": fluid.unique_name,
        "average": fluid.average, "backward": fluid.backward,
        "data_feeder": fluid, "executor": fluid, "param_attr": fluid,
        "dygraph/nn": fluid.dygraph,
        "dygraph/learning_rate_scheduler": fluid.dygraph,
        "dygraph/base": fluid.dygraph,
        "dygraph/checkpoint": fluid.dygraph,
    }
    missing = {}
    for mod, tgt in targets.items():
        names = _reference_all(base / (mod + ".py"))
        # dygraph names must live on fluid.dygraph itself; the fluid
        # top-level fallback is only for modules whose surface the
        # reference re-exports there (framework/executor/param_attr...)
        allow_fluid_fallback = not mod.startswith("dygraph/")
        gone = [n for n in names
                if not hasattr(tgt, n) and
                not (allow_fluid_fallback and hasattr(fluid, n))]
        if gone:
            missing[mod] = gone
    assert not missing, missing
