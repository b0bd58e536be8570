"""Wired FLAGS_* behavior: check_nan_inf attribution, benchmark timing.

Reference: ``framework/operator.cc:953-984`` (per-op nan/inf scan) and the
executor FLAGS_benchmark sync/timing contract.
"""

import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import flags, profiler


def _linreg():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    pred = fluid.layers.fc(x, size=2)
    # log applied to the raw (negative) input, not to pred: the nan must
    # not depend on the sign of the randomly-initialized fc output
    out = fluid.layers.log(x) + fluid.layers.reduce_mean(pred)
    loss = fluid.layers.mean(out)
    return loss


def test_check_nan_inf_raises_with_op_attribution():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            loss = _linreg()
    flags.set_flag("check_nan_inf", True)
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            xv = -np.ones((8, 4), np.float32)   # forces log(neg) = nan
            with pytest.raises(Exception) as ei:
                exe.run(main, feed={"x": xv}, fetch_list=[loss])
            assert "log" in str(ei.value)
            assert "Inf or Nan" in str(ei.value)
    finally:
        flags.set_flag("check_nan_inf", False)


def test_check_nan_inf_passes_on_finite_graph():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, size=2))
    flags.set_flag("check_nan_inf", True)
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            out = exe.run(main, feed={"x": np.ones((8, 4), np.float32)},
                          fetch_list=[loss])
            assert np.isfinite(np.asarray(out[0])).all()
    finally:
        flags.set_flag("check_nan_inf", False)


def test_check_nan_inf_skip_policy_keeps_state_and_counts_bad_steps():
    """FLAGS_check_nan_inf=skip: a poisoned batch must NOT kill the job —
    the step's persistable state stays untouched, a profiler bad-step
    counter bumps, and the next (finite) batch trains normally."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            loss = _linreg()
            fluid.optimizer.SGD(0.1).minimize(loss)
    pnames = [v.name for v in main.list_vars()
              if isinstance(v, fluid.Parameter)]
    assert pnames
    flags.set_flag("check_nan_inf", "skip")
    profiler.reset_bad_step_count()
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            sc = fluid.global_scope()
            exe.run(startup)
            before = {n: np.asarray(sc.find_var(n)).copy()
                      for n in pnames}
            bad = -np.ones((8, 4), np.float32)     # log(neg) -> nan loss
            out = exe.run(main, feed={"x": bad}, fetch_list=[loss])
            assert np.isnan(np.asarray(out[0])).all()
            for n in pnames:                       # state untouched
                np.testing.assert_array_equal(
                    np.asarray(sc.find_var(n)), before[n])
            assert profiler.bad_step_count() == 1
            good = np.ones((8, 4), np.float32)
            out = exe.run(main, feed={"x": good}, fetch_list=[loss])
            assert np.isfinite(np.asarray(out[0])).all()
            changed = any(
                not np.array_equal(np.asarray(sc.find_var(n)), before[n])
                for n in pnames)
            assert changed                         # finite step trains
            assert profiler.bad_step_count() == 1  # no new bad steps
    finally:
        flags.set_flag("check_nan_inf", "off")
        profiler.reset_bad_step_count()


def test_check_nan_inf_policy_normalization():
    for raw, want in ((False, "off"), ("off", "off"), ("0", "off"),
                      (True, "raise"), ("1", "raise"), ("raise", "raise"),
                      ("skip", "skip")):
        flags.set_flag("check_nan_inf", raw)
        try:
            assert flags.nan_inf_policy() == want, raw
        finally:
            flags.set_flag("check_nan_inf", "off")
    flags.set_flag("check_nan_inf", "bogus")
    try:
        import pytest as _pytest
        with _pytest.raises(ValueError, match="check_nan_inf"):
            flags.nan_inf_policy()
    finally:
        flags.set_flag("check_nan_inf", "off")


def test_benchmark_flag_records_step_times():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, size=2))
    flags.set_flag("benchmark", True)
    profiler.reset_benchmark_stats()
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(3):
                exe.run(main, feed={"x": np.ones((8, 4), np.float32)},
                        fetch_list=[loss])
        stats = profiler.benchmark_stats()
        # startup + 3 training steps, all synced and timed
        assert stats["steps"] >= 3
        assert stats["total_s"] > 0
        assert stats["mean_s"] > 0
    finally:
        flags.set_flag("benchmark", False)
        profiler.reset_benchmark_stats()


def test_removed_flags_are_gone():
    with pytest.raises(KeyError):
        flags.get_flag("cpu_deterministic")


def test_steps_per_run_flag_validation():
    """FLAGS_steps_per_run must be a positive int — every rejection
    names the flag so the error is actionable."""
    assert flags.steps_per_run_value() == 1          # default
    assert flags.steps_per_run_value(16) == 16       # explicit override
    for bad in (0, -4, 2.5, "16", True):
        with pytest.raises(ValueError, match="FLAGS_steps_per_run"):
            flags.steps_per_run_value(bad)
    flags.set_flag("steps_per_run", 0)
    try:
        with pytest.raises(ValueError, match="FLAGS_steps_per_run"):
            flags.steps_per_run_value()
    finally:
        flags.set_flag("steps_per_run", 1)


def test_steps_per_run_env_parse_rejects_garbage(monkeypatch):
    """FLAGS_steps_per_run=abc in the environment fails with an error
    naming the flag, not a bare int() ValueError."""
    monkeypatch.setenv("FLAGS_steps_per_run", "abc")
    flags._cache.pop("steps_per_run", None)
    try:
        with pytest.raises(ValueError, match="FLAGS_steps_per_run"):
            flags.get_flag("steps_per_run")
    finally:
        flags._cache.pop("steps_per_run", None)
        monkeypatch.delenv("FLAGS_steps_per_run")
        flags.set_flag("steps_per_run", 1)


def test_steps_per_run_window_rejects_per_step_numpy_fetches():
    """K>1 + return_numpy=True would put a host sync back on the fused
    hot path — the error must name the flag."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, size=2))
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        stacked = {"x": np.ones((4, 8, 4), np.float32)}
        with pytest.raises(RuntimeError, match="FLAGS_steps_per_run"):
            exe.run_window(main, feed=stacked, fetch_list=[loss],
                           steps_per_run=4, return_numpy=True)
        # the async contract works on the same plan
        out = exe.run_window(main, feed=stacked, fetch_list=[loss],
                             steps_per_run=4)
        assert np.asarray(out[0]).shape[0] == 4


def test_new_executor_surface_is_deprecation_free():
    """CI-visible check: exercising the steps_per_run surface
    (run_window, train_from_dataset kwarg, stack helpers, flag
    validator) emits no DeprecationWarning/FutureWarning — the new API
    must not lean on deprecated jax/numpy idioms."""
    import warnings as _warnings
    from paddle_tpu.fluid.dataset import (stack_batch_windows,
                                          stack_feed_dicts)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, size=2))
            fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with _warnings.catch_warnings():
            _warnings.simplefilter("error", DeprecationWarning)
            _warnings.simplefilter("error", FutureWarning)
            assert callable(exe.run_window)
            flags.steps_per_run_value(4)
            wins = list(stack_batch_windows(
                iter([{"x": np.ones((8, 4), np.float32)}] * 4), 2))
            assert len(wins) == 2
            stacked = stack_feed_dicts(
                [{"x": np.ones((8, 4), np.float32)}] * 2)
            out = exe.run_window(main, feed=stacked, fetch_list=[loss],
                                 steps_per_run=2)
            assert np.asarray(out[0]).shape[0] == 2


def test_prng_impl_flag_recompiles_and_is_deterministic():
    """FLAGS_prng_impl is part of the executor cache key: flipping it
    between runs must retrace (different mask stream), and the same impl
    must reproduce the same masks for the same (seed, step)."""
    import jax

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name="x", shape=[64], dtype="float32")
                out = fluid.layers.dropout(x, dropout_prob=0.5)
        return main, startup, out

    xv = np.ones((4, 64), np.float32)
    main, startup, out = build()
    exe = fluid.Executor(fluid.CPUPlace())

    def run_once():
        # fresh scope → step counter (and so the mask stream) restarts
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            res, = exe.run(main, feed={"x": xv}, fetch_list=[out])
        return res

    orig = flags.get_flag("prng_impl")
    try:
        flags.set_flag("prng_impl", "threefry")
        a1, a2 = run_once(), run_once()
        flags.set_flag("prng_impl", "rbg")
        b1 = run_once()
        np.testing.assert_array_equal(a1, a2)  # deterministic per (impl, step)
        assert not np.array_equal(a1, b1)      # impl flip retraced
        assert jax.config.jax_default_prng_impl == "rbg"
    finally:
        flags.set_flag("prng_impl", orig)


# names split in two so that a grep for a deleted flag finds uses only
@pytest.mark.parametrize("name", [
    "dispatch_" "plan",       # PR 30: the per-step-key tail it kept went
    "amp_keep_" "activations",  # PR 30: program._amp_keep says it
    "conv_layout",            # PR 30: NHWC, im2col and the Pallas forward
    "conv_im2col",            # each lost their chip run to XLA's NCHW
    "conv_pallas",            # convolution (PERF.md §6)
])
def test_removed_flag_is_refused(name, monkeypatch):
    """A flag that was deleted is an error to set and to read, from code
    and with its environment variable present: a stale FLAGS_* in a
    launch script must not look honoured."""
    monkeypatch.setenv("FLAGS_" + name, "1")
    with pytest.raises(KeyError):
        flags.set_flag(name, True)
    with pytest.raises(KeyError):
        flags.get_flag(name)
    flags.trace_time_key()      # reads no flag that is gone


def test_pe_profile_fname_dumps(tmp_path, monkeypatch):
    """FLAGS_pe_profile_fname (reference parallel_executor.cc:38
    gperftools hook): a subprocess with the flag set writes a pstats
    file at exit."""
    import subprocess
    import sys
    import pstats

    out = tmp_path / "pe.prof"
    code = (
        "import numpy as np\n"
        "import paddle_tpu.fluid as fluid\n"
        "main, startup = fluid.Program(), fluid.Program()\n"
        "with fluid.program_guard(main, startup):\n"
        "    x = fluid.layers.data(name='x', shape=[4], dtype='float32')\n"
        "    y = fluid.layers.fc(x, size=2)\n"
        "exe = fluid.Executor(fluid.CPUPlace())\n"
        "exe.run(startup)\n"
        "exe.run(main, feed={'x': np.ones((2, 4), np.float32)},"
        " fetch_list=[y])\n"
    )
    env = dict(os.environ, FLAGS_pe_profile_fname=str(out),
               JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=300)
    stats = pstats.Stats(str(out))
    assert stats.total_calls > 0


def test_check_nan_inf_on_sharded_program():
    """FLAGS_check_nan_inf must compose with model-parallel sharding
    (r5: the checkify jit shares the normal path's in/out shardings —
    previously it dropped them, so the debug flag silently disabled
    sharding and broke on multi-process meshes)."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import flags as _flags
    from paddle_tpu.fluid.transpiler import TensorParallelTranspiler

    _flags.set_flag("check_nan_inf", True)
    try:
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 1
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[32], dtype="float32")
            label = fluid.layers.data(name="label", shape=[1],
                                      dtype="int64")
            h = fluid.layers.fc(x, size=64, act="gelu")
            logits = fluid.layers.fc(h, size=8)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
        TensorParallelTranspiler(2).transpile(main, startup)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            feed = {"x": np.zeros((8, 32), np.float32),
                    "label": np.zeros((8, 1), np.int64)}
            lv, = exe.run(main, feed=feed, fetch_list=[loss])
            assert np.isfinite(float(np.asarray(lv).reshape(-1)[0]))
            # the NaN path still throws with op attribution
            feed["x"] = np.full((8, 32), np.nan, np.float32)
            import pytest
            with pytest.raises(Exception, match="Inf or Nan"):
                exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        _flags.set_flag("check_nan_inf", False)
