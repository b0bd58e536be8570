"""One run of one cell: set-up, the measured window, the checks.

The loop is chip_smoke.py's ``train()`` (it ran on the v5e in PR 21) with
the per-step fence replaced by a one-step-lagged one and the clock read at
every completion.  The program is built the way a user builds it and fed by
a program-bound ``fluid.DataLoader`` that cycles a pool of host numpy
batches: the window never passes ``feed=``.
"""

import contextlib
import importlib.metadata
import itertools
import math
import os
import shutil
import time

import numpy as np

from . import stats
from .peaks import peaks_for
from .spec import BENCH_DIR, load_module

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
FIRST_LOSS_TOL = 0.03
# steady state: this many warm-up steps in a row compiled nothing, after the
# batches staged before the executor existed (capacity + 2, counted on the
# CPU in PR 21) have been consumed
QUIET_STEPS = 2
MAX_WARM_STEPS = 32
TRACED_STEPS = 10
TRACE_DIR = os.path.join(BENCH_DIR, ".trace")


def log(msg):
    print(msg, flush=True)


class CompileLog:
    """Every XLA compile jit performs, a persistent-cache hit included, with
    its duration.  ``exe.compile_count()`` does not see jit compiling the
    same step again for changed argument shardings; this does."""

    def __init__(self):
        import jax.monitoring
        self.durations = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kw):
        if event == COMPILE_EVENT:
            self.durations.append(duration_secs)

    def count(self):
        return len(self.durations)


def pick_devices(chips, allow_cpu):
    """The cell's devices and the fingerprint as JAX reports it.  Anything
    but a TPU, or fewer chips than the cell asks for, ends the run."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" and not allow_cpu:
        raise SystemExit(
            "benchmark: needs a TPU, JAX found platform=%r (device_kind=%r, "
            "%d device(s)); there is no CPU fallback"
            % (d0.platform, d0.device_kind, len(devs)))
    if len(devs) < chips:
        raise SystemExit("benchmark: the cell needs %d chip(s), JAX found "
                         "%d" % (chips, len(devs)))
    return devs[:chips], {"platform": d0.platform, "kind": d0.device_kind,
                          "count": len(devs)}


def versions():
    def v(pkg):
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return "absent"
    return "jax %s  jaxlib %s  libtpu %s" % (v("jax"), v("jaxlib"),
                                             v("libtpu"))


# The program's ``random_seed`` is a constant of the compiled startup and
# step programs: a new value compiles both anew (a minute and more), whatever
# the compile cache holds.  So it stays fixed, and ``--seed`` reaches the
# weights through ``reseed_state`` and the inputs through the pool.
PROGRAM_SEED = 1


def reseed_state(scope, seed):
    """Weights from ``--seed`` in ONE jitted call on the device: the startup
    program has drawn every persistable from its initializer; this rolls the
    elements of each floating tensor by a shift drawn from the seed, which
    is a run-time argument (one executable for every seed).  A roll keeps an
    i.i.d. initializer's distribution exactly and leaves constant tensors
    (zeros, ones, the learning rate) as they are."""
    import jax
    import jax.numpy as jnp

    state = {n: scope.find_var(n) for n in scope.var_names()}
    state = {n: v for n, v in state.items()
             if isinstance(v, jax.Array) and v.size > 1
             and jnp.issubdtype(v.dtype, jnp.floating)}

    @jax.jit
    def roll(values, shift):
        return {n: jnp.roll(v.reshape(-1), shift % v.size).reshape(v.shape)
                for n, v in values.items()}

    shift = np.int32(np.random.default_rng(seed).integers(1, 2 ** 31 - 1))
    for n, v in roll(state, shift).items():
        scope.set_var(n, v)
    return len(state)


def device_peak_bytes(device):
    """Peak HBM use of one chip.  The TPU runtime keeps two books: buffers
    (``peak_bytes_in_use``: state, staged feeds, fetches) and the region it
    reserves for the compiled programs' scratch (``peak_bytes_reserved``:
    the step's temporaries, most of a training step's memory); what is free
    is the limit less both.  A backend without the statistics gives 0."""
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) + \
        stats.get("peak_bytes_reserved", 0)


def check_state_on(scope, devices, names):
    """Every named persistable is a jax.Array living on exactly the cell's
    devices.  Returns the faults found."""
    import jax

    want = set(devices)
    faults = []
    for n in names:
        v = scope.find_var(n)
        if not isinstance(v, jax.Array):
            faults.append("state %r is %s, not a jax.Array"
                          % (n, type(v).__name__))
        elif v.sharding.device_set != want:
            faults.append("state %r lives on %s" % (
                n, sorted(map(str, v.sharding.device_set))))
    return faults


def check_feed_on(feed, devices):
    """What the loader hands over in steady state: every feed already a
    jax.Array split along dim 0 into one shard per device."""
    import jax

    faults = []
    for k, v in feed.items():
        if not isinstance(v, jax.Array):
            faults.append("feed %r handed over as %s" % (k, type(v).__name__))
            continue
        shards = v.addressable_shards
        if {s.device for s in shards} != set(devices) or \
                len(shards) != len(devices) or \
                any(s.data.shape[0] * len(devices) != v.shape[0]
                    for s in shards):
            faults.append("feed %r %s is not one shard per device: %s" % (
                k, v.shape, [(str(s.device), s.data.shape) for s in shards]))
    return faults


class Stepper:
    """Dispatches steps with a one-step-lagged fence.  After dispatching
    step i it waits for the loss of step i-1 and stamps the host clock: the
    device always has one step queued, so the fence does not drain it, and
    the stamps give one completion interval per step."""

    def __init__(self, exe, prog, loss, annotate=False):
        import jax
        self._jax = jax
        self._exe, self._prog, self._loss = exe, prog, loss
        self._annotate = annotate
        self.begin = None
        self.stamps = []        # host clock at each completion
        self.dispatch_s = []    # host seconds inside each exe.run call
        self.losses = []        # device scalars, read after the window

    def _span(self, name):
        if self._annotate:
            return self._jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def _fence(self, i):
        with self._span("bench.fence"):
            self._jax.block_until_ready(self.losses[i])
        self.stamps.append(time.perf_counter())

    def run(self, until):
        """Dispatch until ``until(stepper)`` says stop (asked after every
        completion), then fence the step still in flight."""
        self.begin = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            with self._span("bench.exe_run"):
                out = self._exe.run(self._prog, fetch_list=[self._loss],
                                    return_numpy=False)
            self.dispatch_s.append(time.perf_counter() - t0)
            self.losses.append(out[0])
            n = len(self.losses)
            if n > 1:
                self._fence(n - 2)
                if until(self):
                    break
        self._fence(len(self.losses) - 1)

    def host_losses(self):
        # data parallelism through explicit collectives fetches one loss
        # per replica: the mean stands for the step
        return [float(np.mean(np.asarray(x))) for x in self.losses]


def run_cell(cell, seed, seconds, trace, t_start, tiny=False):
    """Set up ``cell``, measure for ``seconds`` and return the result
    object (the last stdout line) — the end-to-end metrics, or with
    ``trace`` the per-layer ones.  ``tiny`` (CPU tests only) takes the
    configuration's tiny sizes and lets the CPU stand in."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import telemetry

    params = cell.params(tiny)
    devices, fingerprint = pick_devices(cell.chips, allow_cpu=tiny)
    log(versions())
    log("device: platform=%s kind=%r count=%d; cell %s on %s" % (
        fingerprint["platform"], fingerprint["kind"], fingerprint["count"],
        cell.name, ", ".join(map(str, devices))))
    peaks = None if tiny else peaks_for(fingerprint["kind"])
    compiles = CompileLog()
    builder = cell.builder
    faults = []
    phases = [("import+devices", time.perf_counter() - t_start)]

    def phase(name):
        phases.append((name, time.perf_counter() - t_start))

    # -- set-up ----------------------------------------------------------
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = PROGRAM_SEED
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, loss = builder.build(params)
        loader = fluid.DataLoader.from_generator(
            feed_list=feeds, capacity=params["loader_capacity"],
            iterable=False)
    phase("build")
    rng = np.random.default_rng(seed)
    pool = [builder.make_batch(rng, params) for _ in range(params["pool"])]
    loader.set_batch_generator(lambda: itertools.cycle(pool))
    prog = cell.wrap.wrap(main, startup, loss, len(devices))
    wait_total = telemetry.registry().counter("data_wait_seconds_total")
    reputs = telemetry.registry().counter("executor_feed_reputs_total")
    batch_mb = sum(v.nbytes for v in pool[0].values()) / 1e6
    log("pool: %d host batches of %.1f MB from seed %d, loader capacity %d"
        % (len(pool), batch_mb, seed, params["loader_capacity"]))

    phase("pool")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace() if tiny else fluid.TPUPlace())
        exe.run(startup)
        n_reseeded = reseed_state(scope, seed)
        phase("startup")
        loader.start()
        try:
            # warm-up, fenced step by step: until the early host-side
            # batches are consumed and nothing compiles any more
            warm_losses, quiet = [], 0
            while len(warm_losses) < params["loader_capacity"] + 4 or \
                    quiet < QUIET_STEPS:
                if len(warm_losses) >= MAX_WARM_STEPS:
                    raise SystemExit("benchmark: still compiling after %d "
                                     "warm-up steps" % MAX_WARM_STEPS)
                before = compiles.count()
                out = exe.run(prog, fetch_list=[loss], return_numpy=False)
                warm_losses.append(float(np.mean(np.asarray(out[0]))))
                if len(warm_losses) == 1:
                    phase("first_step")
                quiet = quiet + 1 if compiles.count() == before else 0
            phase("warm_steps(%d)" % len(warm_losses))
            # what the loader hands over now, and the compiled step
            feed = loader.next_feed()
            faults += check_feed_on(feed, devices)
            wanted = builder.expects_in_hlo(params) + \
                cell.wrap.EXPECTS_IN_HLO
            if wanted:
                hlo = exe.compiled_hlo(prog, feed=feed, fetch_list=[loss])
                faults += ["no %r in the compiled step" % w
                           for w in wanted if w not in hlo]
                del hlo
            del feed
            names = scope.var_names() if len(devices) == 1 else \
                [p.name for p in main.global_block().all_parameters()]
            faults += check_state_on(scope, devices, names)
            reference = getattr(builder, "reference", None)
            if reference is not None:
                faults += reference(params, scope, main) or []

            phase("checks")
            setup_compiles = compiles.count()
            setup_compile_s = sum(compiles.durations)
            reputs_before, wait_before = reputs.value(), wait_total.value()
            setup_s = time.perf_counter() - t_start

            # -- the window: nothing below is set-up ---------------------
            window = Stepper(exe, prog, loss)
            window.run(lambda s: s.stamps[-1] - s.begin >= seconds)
            window_wait_s = wait_total.value() - wait_before
            window_compiles = compiles.count() - setup_compiles
            window_reputs = reputs.value() - reputs_before
            mem_peak = max(map(device_peak_bytes, devices))

            traced = None
            if trace:
                traced = trace_stretch(exe, prog, loss, devices, cell.name,
                                       loader)
        finally:
            loader.reset()

    # -- after the window: arithmetic and checks -----------------------------
    losses = window.host_losses()
    n_steps = len(window.stamps)
    window_s = window.stamps[-1] - window.begin
    bad = sum(1 for x in losses if not math.isfinite(x))
    rate = stats.samples_per_s_per_chip(window.begin, window.stamps,
                                        params["batch"], cell.chips)
    ivals = stats.intervals(window.begin, window.stamps)
    first, want = warm_losses[0], builder.first_loss(params)
    cycles = stats.cycle_means(losses, len(pool))
    if bad:
        faults.append("%d non-finite loss(es) in the window" % bad)
    if window_compiles:
        faults.append("%d XLA compile(s) inside the window" % window_compiles)
    if abs(first - want) > FIRST_LOSS_TOL * want:
        faults.append("first training loss %.4f, an untrained model gives "
                      "%.4f" % (first, want))
    # "some later cycle below the first", not "the last below the first":
    # Adam at the recipe's rate without warm-up memorises the pool in
    # spikes, and a spike in the window's last cycle says nothing about the
    # system (bert flash, seed 4242: 10.82 at the start, 11.54 at the end)
    if len(cycles) < 2 or not min(cycles[1:]) < cycles[0]:
        faults.append("no later cycle of the pool has a mean loss below the "
                      "first cycle's (%s): the optimizer is not learning the "
                      "pool" % " ".join("%.3f" % c for c in cycles))

    flops = builder.flops_per_sample(params)
    log("losses: first (untrained) %.4f vs analytic %.4f; mean of each "
        "cycle of the pool over the window's %d steps: %s" % (
            first, want, n_steps, " ".join("%.3f" % c for c in cycles)))
    log("window: %.3f s, %d steps of %d samples on %d chip(s); %.2f "
        "samples/s/chip; interval ms p50 %.3f p90 %.3f max %.3f" % (
            window_s, n_steps, params["batch"], cell.chips, rate,
            1e3 * stats.percentile(ivals, 50),
            1e3 * stats.percentile(ivals, 90), 1e3 * max(ivals)))
    if peaks:
        log("model FLOPs: %.4f GFLOP a sample (%s) -> %.2f TFLOP/s/chip = "
            "%.2f%% of the %s bf16 peak %.0f TFLOP/s; %.0f tokens-or-"
            "images/s/chip" % (
                flops / 1e9, params["sample"], rate * flops / 1e12,
                100 * rate * flops / peaks["bf16_flops_per_s"],
                fingerprint["kind"], peaks["bf16_flops_per_s"] / 1e12,
                rate * params.get("seq_len", 1)))
    log("set-up: %.2f s, of it %d XLA compile(s) taking %.2f s; window: %d "
        "compile(s), %d feed re-put(s), loader wait %.4f s, exe.run host "
        "%.3f ms a step" % (
            setup_s, setup_compiles, setup_compile_s, window_compiles,
            window_reputs, window_wait_s,
            1e3 * sum(window.dispatch_s) / len(window.dispatch_s)))
    log("set-up phases, s since process start: " + "; ".join(
        "%s %.2f" % p for p in phases))
    log("weights: %d floating persistables rolled by the seed's shift"
        % n_reseeded)
    log("memory_stats of %s: %s" % (devices[0], devices[0].memory_stats()))
    for f in faults:
        log("FAULT: " + f)

    ctx = {
        "params": params, "builder": builder, "peaks": peaks,
        "chips": cell.chips, "steps": n_steps, "window_s": window_s,
        "wait_s": window_wait_s, "dispatch_s": window.dispatch_s,
        "setup_compile_s": setup_compile_s, "setup_compiles": setup_compiles,
        "trace": traced,
    }
    values = {
        "samples_per_s_per_chip": rate,
        "step_ms_p90": 1e3 * stats.percentile(ivals, 90),
        "hbm_peak_gb": mem_peak / 1e9,
        "setup_s": setup_s,
    }
    device = dict(fingerprint, memory_peak_bytes=mem_peak)
    result = {"correct": not faults, "attempted": len(window.losses),
              "failed": bad}
    if trace:
        values = read_layer_metrics(cell, ctx)
        device["busy_s"] = traced.busy_s
        device["window_s"] = traced.window_s
        result["breakdown"] = traced.breakdown()
        wanted_metrics = [n for n, _ in cell.per_layer]
    else:
        wanted_metrics = cell.end_to_end
    result["metrics"] = {n: {"value": values[n], "unit": cell.units[n]}
                         for n in wanted_metrics if n in values}
    result["device"] = device
    return result


def read_layer_metrics(cell, ctx):
    """Each per-layer metric is a reader of its own, ``layer_metrics/
    <name>.py`` with ``read(ctx)``; one that finds nothing to read returns
    None and the metric is left out of the line."""
    values = {}
    for name, path in cell.per_layer:
        value = load_module(path).read(ctx)
        if value is not None:
            values[name] = value
    return values


def trace_stretch(exe, prog, loss, devices, cell_name, loader):
    """A short steady stretch under ``jax.profiler``, in a run of its own
    after the untraced window, reduced by ``harness/trace.py``."""
    import jax

    from . import trace as trace_mod

    out_dir = os.path.join(TRACE_DIR, cell_name)
    shutil.rmtree(out_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the benchmark's own spans only
    options.host_tracer_level = 2
    stepper = Stepper(exe, prog, loss, annotate=True)
    jax.profiler.start_trace(out_dir, profiler_options=options)
    try:
        stepper.run(lambda s: len(s.stamps) >= TRACED_STEPS - 1)
    finally:
        jax.profiler.stop_trace()
    # the trace's events carry no scope: the compiled step's HLO names one
    # per instruction (a second lowering of the step, after the stretch)
    hlo = exe.compiled_hlo(prog, feed=loader.next_feed(), fetch_list=[loss])
    reduced = trace_mod.reduce_trace(
        trace_mod.find_xplane(out_dir), [d.id for d in devices],
        steps=len(stepper.stamps), scopes=trace_mod.scope_map(hlo))
    log("trace: %s" % reduced.summary())
    return reduced
