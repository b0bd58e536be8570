"""Executor: compile-and-run programs on a Place.

Reference contract: ``python/paddle/fluid/executor.py:294`` (Executor.run →
C++ ``framework/executor.cc:150``), where the C++ side interprets OpDescs
one-by-one per place.  Here ``Executor(TPUPlace())`` lowers the program's
global block through the op lowering registry (lowering.py) into ONE jitted
XLA executable per (program fingerprint, feed signature, fetch list), cached
like the reference's ExecutorPrepareContext + NgraphEngine cache
(``executor.cc:327``, ``ngraph_engine.h:42``).

Scope semantics: persistable variables (parameters, optimizer state, LR,
step counters) live in a host-side Scope (reference ``framework/scope.h``)
as device arrays; each run threads them through the compiled function with
buffer donation, so in-place optimizer updates stay in-place on device.
"""

import collections
import itertools
import math
import os
import threading
import time
import contextlib
import warnings

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

# Fetched-but-donated state buffers (e.g. fetching a param) are expected;
# XLA falls back to a copy, which is correct — don't spam the user.
warnings.filterwarnings("ignore",
                        message="Some donated buffers were not usable")

from . import costmodel
from . import framework
from . import flags
from . import preemption
from . import profiler
from . import telemetry
from . import watchdog
from .data_types import np_dtype

# reusable stateless no-op context for the cached-hit dispatch (a fresh
# nullcontext() per step would cost an allocation on the hot path)
_NULL_CTX = contextlib.nullcontext()
from .lowering import ExecState, run_block, step_prng_key

# -- telemetry instruments (module-level so the hot path pays a closure
# read, not a registry lookup; see docs/observability.md) ------------------
_m_plan = telemetry.counter(
    "executor_plan_lookups_total", "dispatch-plan cache lookups, by result")
_m_exec_cache = telemetry.counter(
    "executor_executable_cache_total",
    "compiled-executable cache lookups, by result")
_m_compiles = telemetry.counter(
    "executor_compiles_total",
    "executable builds (Executor._compile), by persistent_cache on/off")
_m_compile_s = telemetry.histogram(
    "executor_compile_seconds",
    "wall seconds of trace+XLA compile (first dispatch / introspection)")
_m_dispatch_s = telemetry.histogram(
    "executor_dispatch_host_seconds",
    "host wall seconds per dispatch enqueue, by kind",
    buckets=(1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0))
_m_ckpt_inflight = telemetry.gauge(
    "checkpoint_async_in_flight",
    "1 while an async checkpoint save is serializing/committing")
_m_rollbacks = telemetry.counter(
    "rollback_total",
    "automatic rollback-to-last-checkpoint restores "
    "(FLAGS_bad_step_rollback)")
_m_rollback_step = telemetry.gauge(
    "rollback_last_step", "step the most recent rollback restored to")
_m_feed_reputs = telemetry.counter(
    "executor_feed_reputs_total",
    "device-resident feeds re-put at dispatch because their layout "
    "mismatched the compiled in_shardings (should be ~0 in steady "
    "state: the input pipeline lands feeds pre-sharded)")
_m_comm_bytes = telemetry.counter(
    "collective_bytes_total",
    "explicit-collective wire payload bytes per device, by species, "
    "wire precision and mesh axis / link class (allreduce counted as "
    "its canonical two-phase reduce-scatter + all-gather movement — "
    "quantized_collectives.allreduce_wire_bytes; a hierarchical "
    "two-level ring splits per member axis, 'ici' vs 'dcn', totals "
    "preserved — ExecState.record_comm)")
_m_xla_compile_s = telemetry.counter(
    "xla_compile_seconds_total",
    "seconds jit spent inside the executor's calls, by phase (trace: "
    "Fluid program -> jaxpr; lower: jaxpr -> MLIR; backend: XLA/Mosaic "
    "compile or persistent-cache load) and why (dispatch: a step's "
    "call; introspection: compiled_hlo/_cost/_memory)")
_m_xla_compiles = telemetry.counter(
    "xla_backend_compiles_total",
    "XLA backend compiles (cache loads included) inside the executor's "
    "calls, by why: dispatch = the first call of a fresh executable, "
    "introspection = compiled_hlo/_cost/_memory, recompile = jit "
    "compiling a step AGAIN for changed argument shardings or "
    "commitment (compile_count() cannot see these)")
_m_opt_state_bytes = telemetry.gauge(
    "optimizer_state_bytes",
    "per-device bytes of optimizer state (accumulators / moments) of "
    "the most recent training dispatch — under weight-update sharding "
    "each device stores only its 1/N shard, so this drops ~1/N")
_m_bucket_overlap = telemetry.gauge(
    "comm_bucket_overlap_frac",
    "schedulable backward/collective overlap of the most recent "
    "gradient-exchanging dispatch: 1 - 1/buckets — each bucket's "
    "exchange is emitted at its last-producer position with no "
    "cross-bucket data dependence, so all but the final bucket's wire "
    "time can hide under remaining backward compute")


# ---------------------------------------------------------------------------
# What jit compiles inside the executor's calls (jax.monitoring)
# ---------------------------------------------------------------------------

_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
# why the calling thread is inside jit right now: None (not in an
# executor call: the event is someone else's), "dispatch", "recompile"
# (the call of an executable that has run before) or "introspection".
# jax reports an event on the thread that compiled.
_compiling = threading.local()
_compile_listener = []


def _on_compile_event(event, duration_secs, **_):
    phase = _COMPILE_PHASES.get(event)
    why = getattr(_compiling, "why", None)
    if phase is None or why is None:
        return
    _m_xla_compile_s.inc(
        duration_secs, phase=phase,
        why="introspection" if why == "introspection" else "dispatch")
    if phase == "backend":
        _m_xla_compiles.inc(why=why)


def _listen_for_compiles():
    """Register the ONE listener behind ``xla_compile_seconds_total`` /
    ``xla_backend_compiles_total``; idempotent (``Executor.__init__``)."""
    if not _compile_listener:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
        _compile_listener.append(_on_compile_event)


class _compiling_for:
    """What jit compiles on this thread inside the block is counted under
    ``why`` (a class, not a generator: it sits on the dispatch path)."""

    __slots__ = ("why",)

    def __init__(self, why):
        self.why = why

    def __enter__(self):
        _compiling.why = self.why

    def __exit__(self, *exc):
        _compiling.why = None
        return False


# ---------------------------------------------------------------------------
# Persistent XLA compilation cache
# ---------------------------------------------------------------------------

# <checkout>/.jax_cache: the cache key includes the directory, so the path
# is fixed (never a tempdir, pid or timestamp) and git-ignored
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def maybe_enable_compile_cache(device):
    """Give an executor on a TPU a persistent compilation cache, so a
    second process compiling the same step deserializes the executable
    instead of re-running XLA (minutes on a real model).

    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins: JAX reads it
    itself and no directory is set in code.  Without it a TPU executor
    uses the fixed ``<checkout>/.jax_cache``; a CPU executor gets no
    persistent cache (the test suite neither slows nor grows the tree).
    Launcher children inherit the environment, so one cache serves a
    pack.  Idempotent; called from ``Executor.__init__``.

    Whatever directory serves, its key covers the instructions' metadata:
    JAX leaves ``op_name`` out of the key by default, and a cache warmed
    by a checkout whose lowering named its scopes differently then hands
    back an executable with THAT checkout's names (seen on the CPU and
    on the v5e, PR 26) — every reader of ``fluid_<op>`` / ``role_*``
    scopes would attribute device time by another program's map.  The
    metadata is an instruction's scopes and the line of its lowering rule,
    NOT the Python stack that called the step: with JAX's default of ten
    frames a location, ``exe.compiled_hlo`` misses the entry the dispatch
    of the same step wrote (31 s of ``checks`` against 5.5 s in the
    four-chip cell's cold set-up, PR 26) and an edit that moves the
    caller's ``exe.run`` line recompiles everything."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    if "JAX_COMPILATION_CACHE_DIR" in os.environ or \
            device.platform != "tpu" or \
            jax.config.jax_compilation_cache_dir:
        return
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_COMPILE_CACHE)


# ---------------------------------------------------------------------------
# Places (reference: paddle/fluid/platform/place.h:26-79)
# ---------------------------------------------------------------------------

class Place:
    def __eq__(self, other):
        return type(self) is type(other) and self.__dict__ == other.__dict__

    def __hash__(self):
        return hash((type(self).__name__, tuple(sorted(self.__dict__.items()))))


class CPUPlace(Place):
    def __repr__(self):
        return "CPUPlace"


class TPUPlace(Place):
    """The north-star addition (BASELINE.json): a first-class TPU place."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return "TPUPlace(%d)" % self.device_id


# Alias kept so reference-style scripts using CUDAPlace run unchanged on TPU.
CUDAPlace = TPUPlace


def _device_for_place(place):
    """The device a Place names.  ``None`` is the default backend's first
    local device (what JAX itself would pick); ``CPUPlace`` a CPU device;
    an explicit ``TPUPlace`` resolves to a TPU or raises — it never
    continues on a CPU device."""
    # under jax.distributed, jax.devices() is the GLOBAL list — computation
    # placed on another process's device is not addressable here, so pick
    # from this process's devices only (mesh_utils.local_devices is THE
    # resolver every placement site shares; meshes alone span the globe)
    from .mesh_utils import local_devices as local

    if place is None:
        return local()[0]
    if isinstance(place, CPUPlace):
        return local("cpu")[0]
    try:
        devs = local("tpu")
    except RuntimeError as e:
        raise RuntimeError(
            "%r needs a TPU, but JAX found none: the default backend is "
            "%r with devices %s.  Use CPUPlace() (or Executor() for the "
            "default backend's device) to run on this machine."
            % (place, jax.default_backend(), jax.devices())) from e
    return devs[place.device_id % len(devs)]


# ---------------------------------------------------------------------------
# Scope (reference: framework/scope.h; pybind _global_scope)
# ---------------------------------------------------------------------------

class Scope:
    def __init__(self, parent=None):
        self.vars = {}
        self.parent = parent
        self.step_counter = 0

    def find_var(self, name):
        s = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        return None

    def has_var(self, name):
        return self.find_var(name) is not None

    def set_var(self, name, value):
        self.vars[name] = value

    def var_names(self):
        return list(self.vars)

    def new_scope(self):
        return Scope(parent=self)

    def find_var_numpy(self, name):
        v = self.find_var(name)
        return None if v is None else np.asarray(v)

    def snapshot(self, names=None):
        """Host snapshot of named vars — the checkpoint extraction point
        (checkpoint.py): returns {name: host ndarray}.  Device arrays are
        copied D2H here, synchronously, so the caller may mutate the
        scope immediately after; the whole extraction is accounted as ONE
        host sync (tag ``checkpoint_snapshot``).  Names missing from the
        scope are skipped (never-initialized persistables carry nothing
        to save)."""
        if names is None:
            names = self.var_names()
        out = {}
        for n in names:
            v = self.find_var(n)
            if v is not None:
                out[n] = np.asarray(v)
        if out:
            profiler.record_host_sync("checkpoint_snapshot")
        return out


_global_scope = Scope()


def global_scope():
    return _global_scope


@contextlib.contextmanager
def scope_guard(scope):
    global _global_scope
    prev, _global_scope = _global_scope, scope
    try:
        yield
    finally:
        _global_scope = prev


# ---------------------------------------------------------------------------
# Block analysis: which scope vars a block reads/writes
# ---------------------------------------------------------------------------

def _block_reads_writes(block, feed_names, written=None):
    """Return (reads-before-write, writes) over persistable vars, recursing
    into sub-blocks referenced by control-flow op attrs (framework.proto BLOCK
    attrs)."""
    reads, writes = [], []
    written = set(written or ())
    written |= set(feed_names)

    def visit(blk, written):
        for op in blk.ops:
            if op.type in ("feed", "fetch"):
                continue
            for names in op.inputs.values():
                for n in names:
                    if n and n not in written:
                        reads.append(n)
                        written.add(n)  # dedupe further reads
            for sub_idx in framework.op_sub_block_indices(op):
                # names the control-flow op binds inside its sub-block
                # (recurrent step inputs / carried state) are not scope reads
                visit(blk.program.blocks[sub_idx],
                      set(written) | framework.op_bound_var_names(op))
            for names in op.outputs.values():
                for n in names:
                    if n:
                        writes.append(n)
                        written.add(n)

    visit(block, written)
    # preserve order, dedupe
    return list(dict.fromkeys(reads)), list(dict.fromkeys(writes))


def coerce_feed_value(block, name, val):
    """Cast a fed value to the declared variable dtype (executor.py feed
    contract); jax arrays pass through untouched."""
    if isinstance(val, jax.Array):
        return val
    var = block._find_var_recursive(name)
    want = np_dtype(var.dtype) if var is not None else None
    return np.asarray(val, dtype=want)


def _feed_coercer(want):
    """Pre-bound steady-state form of coerce_feed_value: the variable's
    declared dtype is resolved once at plan build, so the per-step path is
    an isinstance check — device-resident and already-typed numpy feeds
    pass through without touching numpy at all."""
    def coerce(val):
        if isinstance(val, jax.Array):
            return val
        if isinstance(val, np.ndarray) and (want is None or
                                            val.dtype == want):
            return val
        return np.asarray(val, dtype=want)
    return coerce


def _feed_val_sig(val):
    """(shape, dtype) of a feed value from attribute reads alone when the
    value is an array; materializing scalars/lists through numpy is the
    slow fallback.  The np.dtype OBJECT (hashable, and what both numpy
    and jax arrays expose) avoids per-step dtype stringification.  Keyed
    on the RAW value (pre-coercion): two raw dtypes coercing to the same
    declared dtype get two plan entries that share one compiled
    executable."""
    if isinstance(val, (jax.Array, np.ndarray)):
        return (val.shape, val.dtype)
    a = np.asarray(val)
    return (a.shape, a.dtype)


# What Executor._target hands to _resolve: the Program to compile, the
# target's own part of both cache keys, the executable and plan caches to
# use, and (data-parallel CompiledProgram only) ``in_shardings(device,
# scope)`` giving Executor._compile's argument of that name.
_Target = collections.namedtuple(
    "_Target", "program extra cache plans in_shardings")


def _key_extra(target, K):
    return target.extra if K is None else target.extra + ("window", int(K))


def _executable_key(program, feed_names, feed_vals, fetch_names, extra=()):
    """Cache key for a compiled executable (``extra``: the target's own
    key part and the window size, ``_key_extra``).

    Trace-time flags and program annotations change the lowered
    computation: fold them in so toggling FLAGS_* (or mutating
    program._amp_* / transpiler annotations directly — read fresh, NOT
    via the version-cached fingerprint) between runs recompiles instead
    of silently reusing the stale executable.  Device-resident feeds
    read dtype from the attribute — np.asarray on a jax.Array would
    force a blocking D2H copy of the batch.  The dtype is the one jit
    will see (canonicalized: an int64 host label and the int32
    jax.Array a loader staged from it are the same feed), so a
    program-bound loader moving from host batches to staged ones does
    not compile the step a second time."""
    feed_sig = tuple((n, tuple(np.shape(v)),
                      str(jax.dtypes.canonicalize_dtype(
                          v.dtype if isinstance(v, jax.Array)
                          else np.asarray(v).dtype)))
                     for n, v in zip(feed_names, feed_vals))
    return (program.fingerprint, feed_sig, tuple(fetch_names),
            getattr(program, "_amp_dtype", None),
            getattr(program, "_amp_keep", False), tuple(extra),
            framework.annotation_key(program),
            flags.trace_time_key())


def feed_sharding_fits(sharding, shape):
    """True when ``shape`` can be laid out under ``sharding`` (every
    sharded dim divisible) — the producer-side guard before a sharded
    ``jax.device_put``: shapes the plan never compiled (a ragged
    trailing window) fall back to a plain single-device put instead of
    raising inside the producer thread."""
    try:
        sharding.shard_shape(tuple(shape))
        return True
    except Exception:
        return False


def sharded_put(d, shardings, device, coerce=None):
    """Stage one host feed dict device-side: values already on device
    pass through untouched; every other value is ``jax.device_put``
    with ITS bound plan sharding when one exists and fits
    (``feed_sharding_fits`` — ragged trailing windows fall back), else
    onto ``device``.  ONE helper shared by the DataLoader producer
    (reader.py) and ``Executor._prefetch_feeds`` so the staging
    contract cannot drift between the two pipelines.

    The single-device put is UNCOMMITTED (placed as the default device,
    not pinned): the executor dispatches under ``jax.default_device(its
    device)`` with uncommitted state, and jit keys its executables on
    which arguments are committed — a pinned feed makes it compile the
    step a second time when staged batches replace host ones, and a
    third time when the (then committed) outputs come back as state."""
    out = {}
    for k, v in d.items():
        if isinstance(v, jax.Array):
            out[k] = v
            continue
        if coerce is not None:
            v = coerce(k, v)
        tgt = (shardings or {}).get(k)
        if tgt is not None and feed_sharding_fits(tgt, np.shape(v)):
            out[k] = jax.device_put(v, tgt)
        elif device is not None:
            with jax.default_device(device):
                out[k] = jax.device_put(v)
        else:
            out[k] = v
    return out


def prefetch_ahead(put, batches, depth=None, stop_when=None):
    """Input staging ahead of consumption, for the DataLoader producer
    (reader.py) and ``train_from_dataset``.

    ``depth`` (default ``FLAGS_feed_ring_depth``) selects the pipeline:

    - ``depth >= 1`` — the device-resident feed ring
      (:class:`reader.FeedRing`): a producer THREAD applies ``put``
      (typically a sharded async ``jax.device_put``) up to ``depth``
      windows ahead, so the host-side window fill and the H2D transfer
      both overlap the consumer's device compute, and the consumer
      blocks only when the ring is empty (starvation, counted).
    - ``depth == 0`` — a one-batch lookahead on the CALLING thread (the
      buffered_reader.cc double buffer, XLA style): ``put`` is applied
      to the NEXT batch before the current one is yielded.  This is the
      staging a program-bound loader's worker thread does (reader.py:
      the worker IS the producer and the capacity queue its buffer), so
      it is the input path of every benchmark cell.  Same feeds, bit
      for bit, as the ring's.

    The returned iterator supports ``close()`` (via the generator
    protocol at depth 0): closing it closes the source iterator and, on
    the ring path, joins the producer thread.  ``stop_when`` is an
    extra drain predicate threaded to the ring (the DataLoader worker's
    stop event)."""
    if depth is None:
        depth = int(flags.get_flag("feed_ring_depth"))
    if depth and depth > 0:
        from .reader import FeedRing
        return FeedRing(put, batches, depth, stop_when=stop_when)
    return _prefetch_ahead_sync(put, batches)


def feed_nbytes(feed):
    """Host bytes of one feed dict (attribute reads)."""
    return int(sum(getattr(v, "nbytes", 0) for v in feed.values())) \
        if isinstance(feed, dict) else 0


def device_nbytes(value, sharding=None):
    """Bytes ``value`` takes on ONE device: a sharded array counts one
    shard (``shard_shape``: no shard is touched), a replicated or
    single-device one counts whole.  A host value counts as it would lie
    under ``sharding`` (the compiled step's, for a feed not staged yet),
    whole without one."""
    shape = np.shape(value)
    sharding = getattr(value, "sharding", None) or sharding
    if sharding is not None:
        shape = sharding.shard_shape(shape)
    dtype = getattr(value, "dtype", None)
    if dtype is None:
        dtype = np.asarray(value).dtype
    return math.prod(shape) * np.dtype(dtype).itemsize


def _prefetch_ahead_sync(put, batches):
    """Depth 0 of ``prefetch_ahead`` (see there).  Each batch is drawn
    from the source and ``put`` inside one ``fluid.feed_stage`` span on
    the calling thread (a program-bound
    loader's worker), numbered as the consumer's ``fluid.feed_wait``
    numbers the batch it is handed."""
    it = iter(batches)
    done = object()

    def stage(batch):
        with telemetry.span("feed_stage", batch=batch) as staging:
            host = next(it, done)
            if host is done:
                return done
            staging.label(bytes=feed_nbytes(host))
            return put(host)

    try:
        ahead = stage(0)
        for batch in itertools.count(1):
            if ahead is done:
                return
            nxt = stage(batch)   # transfer overlaps consumer's compute
            yield ahead
            ahead = nxt
    finally:
        # generator .close() / GC must release the source too (its own
        # finally blocks may hold reader threads or open shards)
        if hasattr(it, "close"):
            it.close()


def _make_skip_fn(fn, state_mut, state_out):
    """FLAGS_check_nan_inf=skip guard around ONE step: run the step, then
    a single device-side finiteness reduction over every float scalar
    fetch + updated persistable gates a select — a non-finite step keeps
    the OLD persistable state (in-trace, so it composes with buffer
    donation AND with the multi-step window scan, where the guard runs
    per INNER step on that step's carried state).  Returns
    ``(fetches, guarded_state, ok)``."""
    old_by_name = dict(zip(state_mut, range(len(state_mut))))

    def fn_skip(mut_vals, ro_vals, feed_vals, step):
        fetches, new_state = fn(mut_vals, ro_vals, feed_vals, step)
        ok = jnp.asarray(True)
        # the verdict scans every float of the UPDATED persistable
        # state (poisoned grads poison the update) plus SCALAR
        # float fetches (the loss) — non-scalar fetches are
        # diagnostics that may be legitimately non-finite (-inf
        # attention masks) and must not freeze training
        scan = [x for x in fetches
                if hasattr(x, "dtype") and x.size == 1]
        scan += list(new_state)
        for x in scan:
            if hasattr(x, "dtype") and \
                    jnp.issubdtype(x.dtype, jnp.floating):
                ok = jnp.logical_and(ok, jnp.isfinite(x).all())
        guarded = []
        for name, new in zip(state_out, new_state):
            idx = old_by_name.get(name)
            # write-only persistables have no old value in the
            # trace; they commit unconditionally
            guarded.append(new if idx is None else
                           jnp.where(ok, new, mut_vals[idx]))
        return fetches, guarded, ok
    return fn_skip


def _make_window_fn(inner, state_mut, state_out, steps_per_run,
                    has_ok=False):
    """Fuse K steps of ``inner`` into ONE computation: a ``lax.scan``
    over K stacked feed batches, carrying the persistable state and the
    in-trace step counter through the loop — the TF iterations_per_loop
    / MLPerf-TPU multi-step contract, XLA-style.  One host dispatch then
    runs K steps, so host overhead per step is ~1/K.

    ``inner`` is the single-step fn (``(mut, ro, feeds, step) ->
    (fetches, new_state[, ok])``); feeds arrive stacked ``[K, ...]`` and
    per-step fetches return stacked ``[K, ...]``.  State semantics
    mirror K consecutive ``Executor.run`` calls exactly:

    - names in both ``state_mut`` and ``state_out`` are carried (each
      inner step reads the previous inner step's update);
    - read-only ``state_mut``-not-in-``state_out`` names stay at their
      scope value for the whole window (the scope is only written back
      from ``state_out``, so per-step runs re-read the same value too);
    - write-only ``state_out`` names start from a zeros placeholder the
      block can never observe (read-before-write analysis) and return
      their LAST inner step's value.
    """
    K = int(steps_per_run)
    out_idx = {n: i for i, n in enumerate(state_out)}
    mut_idx = {n: i for i, n in enumerate(state_mut)}

    def window_fn(mut_vals, ro_vals, stacked_feeds, step0):
        mut_vals = tuple(mut_vals)
        ro_vals = tuple(ro_vals)
        stacked_feeds = tuple(stacked_feeds)
        step0 = jnp.asarray(step0, jnp.int32)
        if all(n in mut_idx for n in state_out):
            init_out = tuple(mut_vals[mut_idx[n]] for n in state_out)
        else:
            # write-only persistables need a placeholder of the output
            # aval for a fixed carry structure; one abstract trace of a
            # single step supplies the shapes/dtypes
            feeds0 = tuple(v[0] for v in stacked_feeds)
            out_avals = jax.eval_shape(
                lambda m, r, f, s: inner(m, r, f, s)[1],
                mut_vals, ro_vals, feeds0, step0)
            init_out = tuple(
                mut_vals[mut_idx[n]] if n in mut_idx
                else jnp.zeros(a.shape, a.dtype)
                for n, a in zip(state_out, out_avals))

        def body(carry, feeds):
            out_vals, step = carry
            mut = tuple(out_vals[out_idx[n]] if n in out_idx
                        else mut_vals[mut_idx[n]] for n in state_mut)
            res = inner(mut, ro_vals, feeds, step)
            ys = (tuple(res[0]),)
            if has_ok:
                ys = ys + (res[2],)
            return (tuple(res[1]), step + 1), ys

        (final_out, _), ys = lax.scan(body, (init_out, step0),
                                      stacked_feeds, length=K)
        fetches = list(ys[0])
        if has_ok:
            return fetches, list(final_out), ys[1]
        return fetches, list(final_out)
    return window_fn


def _window_feed_sharding(sh):
    """Shift a per-step feed NamedSharding one dim right for the stacked
    ``[K, ...]`` window feed: the window dim rides unsharded, the batch
    (and sp) axes keep their per-step placement — so the dp/mp/sp/ep
    GSPMD layouts compose unchanged inside the outer scan."""
    if sh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(sh.mesh, P(*((None,) + tuple(sh.spec))))


class _DispatchPlan:
    """Everything Executor.run resolves per (program fingerprint, feed
    signature, fetch set, flags) key, materialized ONCE so the steady-state
    step is one dict lookup plus the jitted call: the compiled block, the
    feed-name order with pre-bound dtype coercers, and whether feeds need
    the multi-process globalization pass.  The mutable/read-only state
    name tuples live on the compiled block; scope VALUES are read fresh
    each step (they change every step by design)."""

    __slots__ = ("compiled", "bind", "needs_globalize")

    def __init__(self, compiled, block):
        self.compiled = compiled
        bind = []
        for n in compiled.feed_names:
            var = block._find_var_recursive(n)
            want = np_dtype(var.dtype) if var is not None else None
            bind.append((n, _feed_coercer(want)))
        self.bind = tuple(bind)
        self.needs_globalize = (jax.process_count() > 1 and
                                (bool(compiled.feed_shardings) or
                                 compiled.feed_local_specs is not None))


def _mp_state_specs(program, mesh):
    """NamedShardings for tensor-parallel state: every weight annotated in
    ``program._mp_shardings`` plus its same-shaped optimizer accumulators
    (named ``<param>_<suffix>``, e.g. velocity/moment) get the weight's
    'mp'-axis layout so updates stay sharded between steps.

    Accumulators resolve to their LONGEST parameter-name prefix (the
    _zero_sharded_state method, compiler.py) so a sibling parameter like
    ``emb_2`` is never mistaken for an accumulator of ``emb``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ann = getattr(program, "_mp_shardings", None) or {}
    if not ann:
        return {}
    # annotations whose axis the compiling mesh does not carry (a
    # caller-supplied mesh missing the axis, or a degree-1 transpile
    # that stamped shardings without growing the mesh) degrade to
    # replicated storage instead of crashing the NamedSharding
    # construction — the lowering-side gates degrade the same way, so
    # the math stays correct, just unsharded.  (Since r5 the pipeline
    # mesh carries sp/ep too, so composition is NOT the cause here.)
    missing = {a for a, _ in ann.values()} - set(mesh.axis_names)
    if missing:
        warnings.warn(
            "model-parallel annotations over axes %s are ignored: the "
            "compiling mesh carries only %s — the state stays "
            "replicated on those axes"
            % (sorted(missing), list(mesh.axis_names)), stacklevel=2)
        ann = {n: (a, d) for n, (a, d) in ann.items() if a not in missing}
        if not ann:
            return {}
    # the annotation keys are parameters too (startup programs hold plain
    # persistable vars, not Parameter instances)
    params = param_names(program)
    params.update(ann)
    shapes = {}
    for v in program.list_vars():
        if getattr(v, "persistable", False) and v.shape:
            shapes[v.name] = tuple(v.shape)

    def sharding_for(pname, pshape):
        axis, dim = ann[pname]
        parts = [None] * len(pshape)
        parts[dim] = axis
        return NamedSharding(mesh, P(*parts))

    specs = {}
    unresolved = []
    for n, sh in shapes.items():
        if n in ann:
            specs[n] = sharding_for(n, sh)
            continue
        if n in params:
            continue                    # a parameter, not an accumulator
        base = resolve_state_param(n, params, program)
        if base is not None:
            if base in ann and shapes.get(base) == sh:
                specs[n] = sharding_for(base, sh)
        else:
            unresolved.append(n)
    # name-heuristic blind spot (VERDICT r3 weak #7): an optimizer
    # accumulator whose name doesn't follow <param>_<suffix> silently
    # falls back to replicated — correct but memory-wasting.  Make it
    # visible: warn for state vars whose prefix walk matched NO param
    # yet whose shape matches an annotated param (a var that resolved to
    # a non-annotated param is correctly replicated — no warning).
    ann_shapes = {}
    for pname in ann:
        if pname in shapes:
            ann_shapes.setdefault(shapes[pname], []).append(pname)
    for n in unresolved:
        sh = shapes[n]
        if sh not in ann_shapes:
            continue
        warnings.warn(
            "tensor-parallel: state var %r (shape %s) matches annotated "
            "param(s) %s by shape but not by <param>_<suffix> naming; "
            "leaving it replicated (extra memory per device)"
            % (n, list(sh), ann_shapes[sh]), stacklevel=2)
    return specs


def _globalize_feed(val, sharding):
    """Multi-process feed contract: a numpy feed is THE GLOBAL value,
    identical on every process (the reference's multi-trainer feed
    semantics); when its compiled sharding is non-trivial, jax requires
    an explicit jax.Array — materialize each process's addressable
    shards from the global value."""
    if isinstance(val, jax.Array) or sharding is None:
        return val
    if getattr(sharding, "is_fully_replicated", True):
        return val
    arr = np.asarray(val)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def _aval_sig(val):
    """(shape, dtype) of a scope-state value — the aval component of the
    introspection-cache key."""
    dt = getattr(val, "dtype", None)
    if dt is None:
        val = np.asarray(val)
        dt = val.dtype
    return (tuple(np.shape(val)), str(dt))


def _stop_consensus():
    """Stream-end stop check of the training loop, pod-safe: local
    ``preemption.stop_requested()`` single-process; multi-process, the
    global OR across every process (``fluid.distributed.any_process``).
    Called at ONE deterministic point — after every process's batch
    stream ended at the same count — so the whole pod agrees whether
    the ending was a drain (in-loop boundaries use the amortized
    consensus schedule instead; see train_from_dataset)."""
    local = preemption.stop_requested()
    from . import distributed as dist
    if dist.process_count() <= 1:
        return local
    return dist.any_process(local)


def _scope_state(scope, names):
    """Materialize scope variables for an executable's state signature;
    shared by Executor.run and Executor.compiled_hlo so both always see
    the same state source."""
    vals = []
    for n in names:
        v = scope.find_var(n)
        if v is None:
            raise RuntimeError(
                "Variable %r is not initialized in the scope. Run the "
                "startup program first (exe.run(fluid."
                "default_startup_program()))." % n)
        vals.append(v)
    return tuple(vals)


def param_names(program):
    """Every name that denotes a PARAMETER (as opposed to optimizer
    state) in ``program``: Parameter instances, startup-program mirrors
    marked parameter-backed (layer_helper.create_parameter), and anything
    a structural state link points at.  Shared by every state-resolution
    consumer (TP/EP specs, ZeRO-1, pp-ZeRO) so the param set cannot drift
    between them."""
    gb = program.global_block()
    names = {p.name for p in gb.all_parameters()}
    names.update(v.name for v in gb.vars.values()
                 if getattr(v, "is_parameter", False))
    names.update((getattr(program, "_opt_state_of", None) or {}).values())
    return names


def resolve_state_param(name, params, program=None):
    """Resolve an optimizer-state var to its parameter.

    The structural link recorded at accumulator creation
    (``program._opt_state_of`` — optimizer.py ``_add_accumulator``,
    clone-carried via framework.PROGRAM_ANNOTATIONS) is authoritative;
    the <param>_<suffix> longest-prefix naming rule remains only as the
    fallback for legacy/hand-built programs whose state vars were not
    created through the optimizer machinery.  Returns the parameter name
    (must be in ``params``) or None.  Single source of truth for every
    consumer (TP/EP state specs here, pipeline pp-ZeRO set, ZeRO-1)."""
    if program is not None:
        link = (getattr(program, "_opt_state_of", None) or {}).get(name)
        if link is not None:
            return link if link in params else None
    return longest_param_prefix(name, params)


def longest_param_prefix(name, params):
    """Resolve an optimizer-state var to its parameter by the
    <param>_<suffix> naming rule: longest '_'-prefix of ``name`` that is
    in ``params`` (handles the ``emb`` vs ``emb_2`` trap).  Returns the
    parameter name or None.  Fallback path of resolve_state_param."""
    base = name
    while True:
        cut = base.rfind("_")
        if cut <= 0:
            return None
        base = base[:cut]
        if base in params:
            return base


def _model_parallel_axes(program):
    """Mesh axes (beyond 'dp') demanded by the program's parallelism
    annotations: ('mp', d) Megatron TP (transpiler/tensor_parallel.py),
    ('sp', d) sequence parallel (transpiler/sequence_parallel.py),
    ('ep', d) expert parallel (transpiler/expert_parallel.py)."""
    axes = []
    for name, attr in (("mp", "_mp_degree"), ("sp", "_sp_degree"),
                       ("ep", "_ep_degree")):
        d = getattr(program, attr, 0) or 0
        if d > 1:
            axes.append((name, d))
    return axes


def _resident_bytes(program, compiled, mut, ro, feed_vals):
    """``{kind: bytes on one device}`` of the values a step is called
    with, for ``step_resident_bytes``: the persistables it takes as
    ``parameter`` (``param_names``), ``optimizer_state`` (the
    accumulators ``program._opt_state_of`` links to a parameter) or
    ``other_state`` (the rest: learning rate, batch-norm statistics,
    selection biases, counters), and its ``feed``."""
    params = param_names(program)
    opt_state = getattr(program, "_opt_state_of", None) or {}
    resident = dict.fromkeys(
        ("parameter", "optimizer_state", "other_state", "feed"), 0)
    for name, value in zip(
            itertools.chain(compiled.state_mut, compiled.state_ro),
            mut + ro):
        kind = "optimizer_state" if name in opt_state else \
            "parameter" if name in params else "other_state"
        resident[kind] += device_nbytes(value)
    for value, sharding in zip(
            feed_vals, compiled.feed_shardings or itertools.repeat(None)):
        resident["feed"] += device_nbytes(value, sharding)
    return resident


class _CompiledBlock:
    """One jitted executable + its scope-variable signature.

    ``state_mut`` (read and overwritten — donated), ``state_ro`` (read-only —
    NOT donated, the scope keeps referencing them), ``state_out`` (written;
    stored back into the scope after each run).
    """

    def __init__(self, fn, state_mut, state_ro, state_out, feed_names,
                 fetch_names):
        self.fn = fn
        self.state_mut = state_mut
        self.state_ro = state_ro
        self.state_out = state_out
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        # is_window: this executable is a fused steps_per_run-step
        # window (lax.scan) — feeds stacked [K, ...], fetches stacked
        # [K, ...], the scope step counter advances by K per dispatch
        self.steps_per_run = 1
        self.is_window = False
        # telemetry: the first dispatch of a fresh executable carries
        # trace + XLA compile — _dispatch times it and stamps the
        # step-event's compile_s, then clears the flag
        self._fresh = True
        # skip-policy executables hand [K] device verdicts to the lazy
        # bad-step pool per dispatch; step-events count them by K
        self._has_verdicts = False
        # set by the compile paths that pass in_shardings: per-feed
        # shardings, consulted by globalize_feeds
        self.feed_shardings = None
        # explicit-collective multi-process contract (the pod-scale
        # runtime, docs/distributed.md): the mesh spanning the global
        # device list plus per-feed PartitionSpecs under which each
        # process's LOCAL batch assembles into the global sharded array
        # (multihost_utils.host_local_array_to_global_array — the
        # reference's per-trainer reader → collective world, jax-style).
        # None on every other path.
        self.collective_mesh = None
        self.feed_local_specs = None
        # single-process explicit-collective dialect: the mesh layout
        # feeds should land on (prefetch puts + dispatch-time fixes) —
        # a feed committed to ONE device would make the shard_map'd
        # executable refuse the implicit transfer
        self.feed_placement_shardings = None
        # per-read-only-state in_shardings + the cache of placed
        # copies: RO state never changes between dispatches, so its
        # mesh placement is done ONCE per (executable, source array)
        # instead of pjit implicitly re-broadcasting it every step
        self.state_ro_shardings = None
        self._ro_placed = {}
        # wire-traffic cell shared with the traced step fn: the lowering
        # appends (species, precision, bytes) per collective DURING
        # tracing, the fn overwrites the cell with each complete trace
        # (idempotent across retraces), and comm_bytes_per_step()
        # aggregates it once for the dispatch-time counters
        self._comm_cell = None
        self._comm_agg = None
        # fingerprint of the program this executable was compiled from:
        # producers that read the executor's ``_last_compiled`` (the
        # dataset prefetcher) match on it so an interleaved dispatch of
        # a DIFFERENT program (an eval step between training windows)
        # can never leak its feed shardings into this program's pipeline
        self.program_fingerprint = None
        # optimizer-state accounting (set by _annotate_opt_state from
        # the program's _opt_state_of links + weight-update-sharding
        # metadata): accumulator var names, which of them are stored
        # sharded P('dp'), the sharding degree, and the lazily computed
        # per-device byte total
        self.opt_state_names = ()
        self.sharded_state = frozenset()
        self.shard_degree = None
        self._opt_bytes = None
        # the underlying jax.jit callable, for HLO/memory/cost
        # introspection — ``fn`` may be a plain closure wrapping it
        # (checkify runner, shard_map call) that has no .lower
        self._jitted = None
        # lazily compiled XLA executables for introspection, keyed by the
        # scope-state avals: a later call with a reinitialized scope whose
        # state shapes/dtypes differ re-lowers instead of returning stale
        # analysis
        self._xla_executables = {}

    def comm_bytes_per_step(self):
        """Per-INNER-step wire traffic of this executable, aggregated
        from the trace-time comm log: ``{(species, precision): bytes}``.
        None until the step fn has traced (i.e. before its first
        dispatch/introspection); {} for a step with no explicit
        collectives.  The aggregate is keyed on the cell's entries
        OBJECT: a shape-driven retrace overwrites the cell with a fresh
        tuple, so the next dispatch re-aggregates instead of stamping
        the first trace's bytes forever."""
        cell = self._comm_cell
        entries = cell.get("entries") if cell else None
        if entries is None:
            return None
        agg = self.comm_bytes_by_axis()
        if agg is None:
            return None
        out = {}
        for (species, precision, _axis), nbytes in agg.items():
            key = (species, precision)
            out[key] = out.get(key, 0) + nbytes
        return out

    def comm_bytes_by_axis(self):
        """Per-INNER-step wire traffic keyed ``(species, precision,
        axis)`` — the link-class-resolved view behind
        ``collective_bytes_total{axis}`` and the ``comm_by_axis``
        step-event field.  Same None/{} contract and entries-identity
        cache as :meth:`comm_bytes_per_step` (which sums this over
        axes)."""
        cell = self._comm_cell
        entries = cell.get("entries") if cell else None
        if entries is None:
            return None
        cached = self._comm_agg
        if cached is not None and cached[0] is entries:
            return cached[1]
        agg = {}
        for species, precision, nbytes, _grad_bucket, axis in entries:
            key = (species, precision, axis or "unmapped")
            agg[key] = agg.get(key, 0) + nbytes
        self._comm_agg = (entries, agg)
        return agg

    def annotate_opt_state(self, program):
        """Record the program's optimizer-state vars (the structural
        param→state links of optimizer._add_accumulator) plus the
        weight-update-sharding metadata, for the per-device
        optimizer_state_bytes gauge/step-event field."""
        links = getattr(program, "_opt_state_of", None) or {}
        self.opt_state_names = tuple(sorted(links))
        self.sharded_state = frozenset(
            getattr(program, "_dp_sharded_state", ()) or ())
        degree = getattr(program, "_wus_degree", None)
        self.shard_degree = int(degree) if degree else None
        return self

    def comm_grad_exchanges(self):
        """Number of independent gradient-exchange collectives (buckets)
        this step emits — the trace-time comm log entries carrying the
        transpiler's ``__grad_bucket__`` marker, so sync-BN statistic or
        LocalSGD averaging allreduces never count.  0 until traced / for
        non-collective steps.  Feeds the ``comm_buckets`` step-event
        field and the ``comm_bucket_overlap_frac`` gauge (overlap bound
        = 1 - 1/b: bucket i's exchange can hide under buckets i+1..b's
        backward compute; the last one cannot)."""
        cell = self._comm_cell
        entries = cell.get("entries") if cell else None
        if not entries:
            return 0
        return sum(1 for _s, _p, _b, grad_bucket, _axis in entries
                   if grad_bucket)

    def opt_state_bytes(self, scope):
        """Per-device bytes of this executable's optimizer state, from
        the live scope arrays (sharded names count 1/degree).  Cached —
        state sizes are fixed for the life of the executable."""
        if self._opt_bytes is not None:
            return self._opt_bytes
        total = 0
        degree = self.shard_degree or 1
        for n in self.opt_state_names:
            v = scope.find_var(n)
            nb = getattr(v, "nbytes", None)
            if nb is None:
                continue
            total += nb // degree if n in self.sharded_state else nb
        self._opt_bytes = int(total)
        return self._opt_bytes

    def globalize_feeds(self, feed_vals):
        """Multi-process feed contract (every caller of ``fn`` must use
        this).  Two dialects, selected by which attribute the compile
        path set:

        - explicit-collective (``feed_local_specs``): each process feeds
          its LOCAL batch; the global sharded array spanning all hosts
          is assembled from the per-process shards
          (``host_local_array_to_global_array`` — the reference's
          per-trainer reader → NCCL-ring world, jax-style);
        - GSPMD (``feed_shardings``): numpy feeds are THE GLOBAL value,
          identical per process; jax refuses numpy args with non-trivial
          shardings there, so materialize each process's addressable
          shards from the global value."""
        if jax.process_count() <= 1:
            return feed_vals
        if self.feed_local_specs is not None:
            from jax.experimental import multihost_utils
            mesh = self.collective_mesh
            out = []
            for v, spec in zip(feed_vals, self.feed_local_specs):
                if isinstance(v, jax.Array) and not v.is_fully_addressable:
                    out.append(v)   # already assembled (a re-dispatch)
                    continue
                out.append(multihost_utils.host_local_array_to_global_array(
                    np.asarray(v), mesh, spec))
            return out
        if not self.feed_shardings:
            return feed_vals
        return [_globalize_feed(v, sh)
                for v, sh in zip(feed_vals, self.feed_shardings)]

    def place_ro_state(self, ro_vals):
        """Single-process GSPMD: read-only state arrays committed (or
        resident) on one device are placed onto the compiled mesh
        layout ONCE and the placed copy reused every dispatch — without
        this, pjit re-broadcasts e.g. the LR scalar across the mesh on
        every step (a per-step d2d transfer), and a COMMITTED
        single-device value would make it raise outright.  The cache
        keys on source-array identity, so a restore/assignment that
        replaces the scope value re-places naturally."""
        shs = self.state_ro_shardings
        if not shs:
            return ro_vals
        out = list(ro_vals)
        for i, (v, sh) in enumerate(zip(ro_vals, shs)):
            if sh is None or not isinstance(v, jax.Array) or \
                    v.sharding == sh:
                continue
            cached = self._ro_placed.get(i)
            if cached is not None and cached[0] is v:
                out[i] = cached[1]
                continue
            placed = jax.device_put(v, sh)
            self._ro_placed[i] = (v, placed)
            out[i] = placed
        return tuple(out)

    def fix_feed_placements(self, feed_vals):
        """Single-process GSPMD placement guard: a COMMITTED device
        feed whose layout differs from the compiled in_sharding makes
        pjit raise (jax refuses implicit transfers of committed
        arrays) — re-put it explicitly with the expected sharding.
        Feeds the input pipeline already landed correctly (the bound
        feed-sharding path) compare equal and pass through untouched;
        every correction is counted (``executor_feed_reputs_total``)
        so tests/dashboards can pin steady state at zero.  The
        explicit-collective dialect (``feed_placement_shardings``)
        shares this guard: its shard_map'd executable refuses a feed
        committed to one device just like pjit does."""
        shardings = self.feed_shardings or self.feed_placement_shardings
        if not shardings:
            return feed_vals
        out = []
        for v, sh in zip(feed_vals, shardings):
            if sh is not None and isinstance(v, jax.Array) and \
                    v.sharding != sh:
                v = jax.device_put(v, sh)
                _m_feed_reputs.inc()
            out.append(v)
        return out


class Executor:
    """Compile-and-run executor for one place (executor.py:294 contract)."""

    def __init__(self, place=None):
        self._device = _device_for_place(place)
        if place is None:
            place = TPUPlace() if self._device.platform == "tpu" \
                else CPUPlace()
        self.place = place
        self._cache = {}
        # dispatch-plan cache: steady-state run() is one lookup here plus
        # the jitted call (no per-step sorting/coercion/key hashing)
        self._plans = {}
        self._plan_hits = 0
        self._compile_count = 0   # test hook: recompile detection
        # whether the dispatch in flight found its plan cached — read by
        # the step-event
        self._last_plan_hit = False
        # the executable behind the most recent dispatch: input-pipeline
        # producers read its feed shardings so feeds land already
        # sharded (GSPMD) / on the right device ahead of the next pull
        self._last_compiled = None
        maybe_enable_compile_cache(self._device)
        _listen_for_compiles()
        # FLAGS_pe_profile_fname (parallel_executor.cc:38 gperftools
        # hook): whole-process host profile, dumped at exit
        profiler.maybe_start_pe_profile()

    # -- public API --------------------------------------------------------
    def compile_count(self):
        """Executables this executor has compiled so far.  A steady-state
        delta of 0 across dispatches is the "no recompiles" proof — the
        serving executor's ``serving_recompiles_total`` pin and the
        recompile-detection test hook read it here."""
        return self._compile_count

    def _target(self, program):
        """What a run of ``program`` compiles and where it caches it.  A
        ``CompiledProgram`` describes itself (``_compile_spec``: the
        data-parallel GSPMD step with caches of its own, or, plain, just
        its Program); a Program compiles as it is, into this executor's
        caches."""
        program = program or framework.default_main_program()
        spec = getattr(program, "_compile_spec", None)
        if spec is not None:
            target = spec()
            if target.in_shardings is not None:
                return target
            program = target.program
        return _Target(program, (), self._cache, self._plans, None)

    def _lookup_compiled(self, target, feed, fetch_list, scope, K):
        """(compiled block, coerced feeds) from the target's executable
        cache, compiling on a miss: the lower half of ``_resolve``, and
        where introspection enters.  ``K`` (not None) is the fused
        K-step WINDOW executable (feed values stacked [K, ...]; K=1 is a
        window of one, still scanned); None is the per-step one."""
        program = target.program
        fetch_names = [v.name if isinstance(v, framework.Variable) else v
                       for v in fetch_list or ()]
        feed_names = sorted(feed)
        block = program.global_block()
        feed_vals = [coerce_feed_value(block, n, feed[n]) for n in feed_names]
        key = _executable_key(program, feed_names, feed_vals, fetch_names,
                              extra=_key_extra(target, K))
        compiled = target.cache.get(key)
        _m_exec_cache.inc(result="miss" if compiled is None else "hit")
        if compiled is None:
            compiled = target.cache[key] = self._compile(
                program, feed_names,
                [tuple(np.shape(v)) for v in feed_vals], fetch_names,
                in_shardings=target.in_shardings and
                target.in_shardings(self._device, scope),
                steps_per_run=K)
        return compiled, feed_vals

    def _resolve_compiled(self, program, feed, fetch_list, scope,
                          steps_per_run=None):
        """(compiled block, coerced feeds) that ``run`` would dispatch
        for ``program``: a raw Program's through this executor's cache,
        a data-parallel CompiledProgram's GSPMD one through its own."""
        return self._lookup_compiled(
            self._target(program), feed or {}, fetch_list,
            scope or global_scope(), steps_per_run)

    def _lowered_executable(self, program, feed, fetch_list, scope,
                            steps_per_run=None):
        """Compile (or fetch from cache) and return the jax Compiled
        object for this (program, feed-signature, fetches, scope-state
        avals) tuple."""
        return self._step_executable(program, feed, fetch_list, scope,
                                     steps_per_run)[0]

    def _step_executable(self, program, feed, fetch_list, scope,
                         steps_per_run=None):
        """``(executable, its memory_analysis())`` behind every
        introspection call, and the one place the step's memory record is
        stamped (``step_memory_bytes`` / ``step_resident_bytes``, by
        signature): XLA's analysis is read once an executable, the bytes
        of the values in hand (attribute reads) at every call.  Only an
        introspection call comes here; a dispatch never does."""
        scope = scope or global_scope()
        target = self._target(program)
        compiled, feed_vals = self._lookup_compiled(
            target, feed or {}, fetch_list, scope, steps_per_run)
        mut = _scope_state(scope, compiled.state_mut)
        ro = _scope_state(scope, compiled.state_ro)
        aval_key = tuple(_aval_sig(v) for v in mut + ro)
        executable, analysis = compiled._xla_executables.get(
            aval_key, (None, None))
        if executable is None:
            # multi-host feeds carry LOCAL shapes; the executable (on
            # every path) is compiled against GLOBAL avals — globalize
            # before building/lowering
            feed_vals = compiled.globalize_feeds(feed_vals)
            jitted = compiled._jitted
            if jitted is None:
                # explicit-collective path: the shard_map'd jitted is
                # built lazily on first dispatch; its builder is exposed
                # as ensure_built so introspection works pre-dispatch
                # too (the int8/bf16 wire-precision HLO pins need it),
                # single- and multi-process alike — ONE executable per
                # compile, never rebuilt per call
                build = getattr(compiled.fn, "ensure_built", None)
                if build is not None:
                    jitted = build(mut, ro, tuple(feed_vals),
                                   np.int32(scope.step_counter))
                    compiled._jitted = jitted
            if jitted is None:
                raise RuntimeError(
                    "HLO introspection is unavailable for this program: "
                    "its execution path does not expose one jitted step "
                    "function")
            # cached on the block so compiled_hlo + compiled_cost on the
            # same (program, feeds, fetches, state avals) pay ONE XLA
            # compile
            with _compiling_for("introspection"):
                lowered = jitted.lower(mut, ro, tuple(feed_vals),
                                       np.int32(scope.step_counter))
                t0 = time.perf_counter()
                with telemetry.span(
                        "compile", why="introspection",
                        sig=costmodel.signature(
                            compiled.program_fingerprint,
                            k=compiled.steps_per_run)):
                    executable = lowered.compile()
            _m_compile_s.observe(time.perf_counter() - t0,
                                 kind="introspection")
            analysis = executable.memory_analysis()
            compiled._xla_executables[aval_key] = (executable, analysis)
        profiler.note_step_executable(executable)
        costmodel.stamp_step_memory(
            costmodel.signature(compiled.program_fingerprint,
                                k=compiled.steps_per_run),
            costmodel.memory_record(analysis),
            _resident_bytes(target.program, compiled, mut, ro, feed_vals))
        return executable, analysis

    def compiled_hlo(self, program=None, feed=None, fetch_list=None,
                     scope=None, steps_per_run=None):
        """Post-optimization HLO text of the executable this (program,
        feed-signature, fetches) pair compiles to — the substrate for
        HLO-property regression tests (collective counts per parallel
        composition, no host transfers inside the step, fusion shapes)
        that need no TPU.  ``program`` may be a raw Program or a
        ``CompiledProgram`` (the data-parallel executable its run
        dispatches).  Requires the startup program to have run in
        ``scope`` (state avals come from it).
        ``steps_per_run=K`` (feeds stacked [K, ...]) lowers the fused
        K-step window instead — the substrate for pinning that a window
        is ONE while loop with no per-inner-step host transfers."""
        return self._lowered_executable(
            program, feed, fetch_list, scope,
            steps_per_run=steps_per_run).as_text()

    def compiled_memory(self, program=None, feed=None, fetch_list=None,
                        scope=None, steps_per_run=None):
        """XLA memory analysis of the compiled step (per-device argument
        / output / temp bytes) — the chip-free substrate for memory-
        scaling claims: e.g. a sequence-parallel step's temp bytes must
        shrink vs the replicated step (activations stored S/sp), and a
        remat span must shrink them further."""
        return self._step_executable(
            program, feed, fetch_list, scope,
            steps_per_run=steps_per_run)[1]

    def compiled_cost(self, program=None, feed=None, fetch_list=None,
                      scope=None, steps_per_run=None, normalize=True):
        """XLA cost analysis of the compiled step ({'flops', 'bytes
        accessed', ...}) — the chip-free FLOP/traffic budget substrate:
        asserting counted step FLOPs against the analytic model estimate
        catches recompute/double-backward regressions without a TPU
        (reference analogue: the op_tester's per-op flop accounting,
        operators/benchmark/op_tester.h).

        ``normalize=True`` (default) returns one flat dict with
        PER-INNER-STEP semantics on every path, including
        ``steps_per_run=K`` windows: XLA's cost analysis visits the scan
        body once and never folds the trip count in, so a K-window's
        figures already mean "per inner step" and a K=64 window does NOT
        read as a 64x regression vs K=1 (pinned in
        tests/test_cost_ledger.py).  It also unwraps the backend's
        list-of-properties return so ``cost["flops"]`` works across jax
        builds.  ``normalize=False`` returns the raw backend object."""
        raw = self._lowered_executable(
            program, feed, fetch_list, scope,
            steps_per_run=steps_per_run).cost_analysis()
        if not normalize:
            return raw
        return costmodel.normalize_cost(raw)

    def cost_record(self, program=None, feed=None, fetch_list=None,
                    scope=None, steps_per_run=None, tag=None,
                    stamp=True):
        """Full device-cost ledger record for the executable this
        (program, feed-signature, fetches) tuple compiles to: FLOPs,
        transcendentals, bytes accessed, argument/output/temp/peak
        memory, instruction/fusion/collective counts, static collective
        bytes by species/axis, and the roofline ``estimated_step_s`` —
        keyed by the executable signature (docs/observability.md
        "Device-cost ledger").  Costs one ahead-of-time compile (cached
        thereafter).  ``stamp=True`` also publishes the ``hlo_*`` gauges
        and a ``kind="compile"`` ledger record.  Returns None when
        ``FLAGS_cost_ledger=0``."""
        if not costmodel.enabled():
            return None
        scope = scope or global_scope()
        executable, analysis = self._step_executable(
            program, feed, fetch_list, scope, steps_per_run=steps_per_run)
        compiled, _ = self._resolve_compiled(
            program, feed, fetch_list, scope, steps_per_run)
        k = steps_per_run or 1
        rec = costmodel.describe(
            executable, k=k,
            sig=costmodel.signature(compiled.program_fingerprint, k=k),
            comm=compiled.comm_bytes_by_axis(), tag=tag,
            memory=costmodel.memory_record(analysis))
        if stamp:
            costmodel.stamp(rec, source="full")
        return rec

    def run(self, program=None, feed=None, fetch_list=None, feed_var_name="feed",
            fetch_var_name="fetch", scope=None, return_numpy=True,
            use_program_cache=True):
        return self._as_step(scope, 1, self._run, program, feed, fetch_list,
                             scope, None, return_numpy)

    def _as_step(self, scope, k, body, *args):
        """One call of ``run`` / ``run_window``, as a reader of a trace
        sees it: ``FLAGS_device_profile``'s bracket, then the
        ``fluid.step`` span (the loader pull, the dispatch and the
        executor's own Python are its children), then ``body(*args)``.
        ``run`` and ``run_window`` enter here and nothing below them
        does, so a step is never nested in a step."""
        # FLAGS_device_profile=N: bracket the next N dispatched steps in
        # a jax.profiler trace (profiler.py) — one cached-int read when
        # the flag is 0
        profiler.device_profile_begin()
        step = (scope or global_scope()).step_counter
        with telemetry.span("step", step_num=int(step), k=k):
            out = body(*args)
        last = self._last_compiled
        profiler.device_profile_end(last.steps_per_run if last else k)
        return out

    def run_window(self, program=None, feed=None, fetch_list=None,
                   scope=None, steps_per_run=None, return_numpy=False):
        """Run K training steps in ONE jitted dispatch — the multi-step
        fused training loop (TF ``iterations_per_loop``, the MLPerf TPU
        submissions' in-loop training): the compiled computation is a
        ``lax.scan`` over K device-resident batches, carrying scope
        state, the step counter, and the PRNG derivation through the
        loop, so host overhead per step is ~1/K and the device never
        waits on the host between inner steps.

        ``feed`` values must be stacked ``[K, per-step shape...]``
        (``dataset.stack_batch_windows`` builds them from per-step feed
        dicts); fetches return stacked ``[K, ...]`` per-step values —
        one loss PER INNER STEP, as live jax.Arrays (the async-dispatch
        contract; ``np.asarray`` them when you actually need numbers).
        ``steps_per_run`` defaults to ``FLAGS_steps_per_run``.
        ``scope.step_counter`` advances by K per call, so checkpoints
        land on window boundaries.  K=1 is valid (a window of one) but
        the per-step ``run()`` remains the default."""
        K = flags.steps_per_run_value(steps_per_run)
        return self._as_step(scope, K, self._run, program, feed,
                             fetch_list, scope, K, return_numpy)

    def _run(self, program, feed, fetch_list, scope, K, return_numpy):
        """One step (``K`` None) or one fused window of K steps, of a
        Program or a CompiledProgram: pserver branch, the pull from a
        program-bound loader, ``_resolve``, dispatch."""
        target = self._target(program)
        program = target.program
        scope = scope or global_scope()
        if getattr(program, "_ps_endpoint", None) is not None and \
                not getattr(program, "_ps_applying", False):
            return self._run_pserver(program, scope)
        loader = getattr(program, "_loader", None) \
            if K is None and not feed else None
        if loader is not None:
            # non-iterable DataLoader bound to the program (the
            # reference PyReader-in-program contract, reader.py): pull
            # one staged batch; core.EOFException ends the pass.  Bind
            # this executor's device first, every pull, so the producer
            # thread device_puts upcoming batches where they will run
            # (H2D overlaps the current step; a later executor on
            # another device never gets batches committed to a stale one)
            loader._consumer_device = self._device
            feed = loader.next_feed(step=scope.step_counter)
            if getattr(loader, "_steps_per_run", 1) > 1:
                # a loader staging stacked [K, ...] windows (the last
                # may be shorter): a window never returns numpy
                K = int(np.shape(next(iter(feed.values())))[0]) \
                    if feed else 1
                return_numpy = False
        feed = feed or {}
        if K is not None:
            for n, v in feed.items():
                shape = np.shape(v)
                if not shape or shape[0] != K:
                    raise ValueError(
                        "run_window(steps_per_run=%d): feed %r must be "
                        "stacked [K, per-step shape...] with leading dim "
                        "%d, got shape %s" % (K, n, K, shape))
        plan = self._resolve(target, feed, fetch_list, scope, K)
        out = self._run_plan(plan, scope, feed, return_numpy)
        if loader is not None:
            self._bind_loader_shardings(loader)
        return out

    def _resolve(self, target, feed, fetch_list, scope, K):
        """The dispatch plan of (target, feed signature, fetches, K):
        plan cache, then the executable cache, then ``_compile``.  The
        plan key reads the RAW feed dtypes from attributes (no numpy
        coercion, no SHA: program.fingerprint is version-cached) over an
        executable keyed on the canonical ones, so a steady-state step is
        one lookup here.  annotation_key and trace_time_key ARE
        recomputed per step on purpose: direct attribute / flag mutation
        between runs must recompile, and neither is version-tracked."""
        program = target.program
        names = tuple(sorted(feed))
        key = (program.fingerprint, names,
               tuple(_feed_val_sig(feed[n]) for n in names),
               tuple(v.name if isinstance(v, framework.Variable) else v
                     for v in fetch_list or ()),
               getattr(program, "_amp_dtype", None),
               getattr(program, "_amp_keep", False),
               framework.annotation_key(program),
               flags.trace_time_key()) + _key_extra(target, K)
        plan = target.plans.get(key)
        self._last_plan_hit = plan is not None
        _m_plan.inc(result="hit" if self._last_plan_hit else "miss")
        if plan is None:
            compiled, _ = self._lookup_compiled(target, feed, fetch_list,
                                                  scope, K)
            plan = target.plans[key] = _DispatchPlan(
                compiled, program.global_block())
        else:
            self._plan_hits += 1
        return plan

    def _bind_loader_shardings(self, loader):
        """Hand the just-dispatched executable's feed shardings back to
        a program-bound DataLoader so its producer thread device_puts
        subsequent batches with the plan's layout: under GSPMD and under
        the explicit-collective dialect alike (the same pair
        ``_prefetch_feeds`` reads) the feed lands already sharded across
        the mesh (zero reshard transfers at dispatch, no whole batch on
        the first device), single-device plans keep the plain
        consumer-device put.  Multi-process feeds stay numpy (the
        global-value contract), so nothing is bound there."""
        compiled = self._last_compiled
        if compiled is None or jax.process_count() > 1:
            return
        fsh = compiled.feed_shardings or compiled.feed_placement_shardings
        sh = None
        if fsh:
            sh = {n: s for n, s in zip(compiled.feed_names, fsh)
                  if s is not None}
        loader._consumer_shardings = sh or None

    def _run_plan(self, plan, scope, feed, return_numpy):
        """Steady-state step: pre-bound coercers + the jitted call."""
        compiled = plan.compiled
        feed_vals = [c(feed[n]) for n, c in plan.bind]
        if plan.needs_globalize:
            feed_vals = compiled.globalize_feeds(feed_vals)
        return self._dispatch(compiled, scope, feed_vals, return_numpy)

    def _dispatch(self, compiled, scope, feed_vals, return_numpy):
        # fluid.dispatch less its child fluid.enqueue (the jitted call)
        # is the executor's own Python around the call: placement
        # guards, state gather and write-back, the step-event record
        with telemetry.span("dispatch", step=int(scope.step_counter),
                            k=compiled.steps_per_run,
                            fresh=compiled._fresh,
                            window=compiled.is_window):
            return self._dispatch_in_span(compiled, scope, feed_vals,
                                          return_numpy)

    def _dispatch_in_span(self, compiled, scope, feed_vals, return_numpy):
        self._last_compiled = compiled
        if (compiled.feed_shardings is not None or
                compiled.feed_placement_shardings is not None) and \
                jax.process_count() <= 1:
            feed_vals = compiled.fix_feed_placements(feed_vals)
        k = compiled.steps_per_run
        if k > 1 and return_numpy:
            raise RuntimeError(
                "steps_per_run=%d (FLAGS_steps_per_run) fuses %d steps "
                "into one dispatch; per-step numpy fetches would put a "
                "host sync back on the hot path — pass "
                "return_numpy=False and np.asarray() the stacked "
                "[K, ...] fetches only when you need the numbers "
                "(e.g. at print_period boundaries)" % (k, k))
        step = np.int32(scope.step_counter)
        scope.step_counter += k
        if compiled.is_window:
            profiler.record_window(k)
            # window-boundary marker: checkpoint saves must land exactly
            # here (checkpoint.py validates counter == marker — robust
            # against the startup run's own counter increment, which
            # makes absolute multiples-of-K wrong in the standard flow)
            scope._window_end = scope.step_counter
        benchmark = flags.get_flag("benchmark")
        fresh = compiled._fresh
        syncs0 = profiler.host_sync_count()
        # hang-detection stamp BEFORE the jitted call: a dispatch that
        # parks (dead collective peer, wedged device) is the hang the
        # watchdog names "dispatch".  One dict read + return when the
        # watchdog is off — the zero-overhead contract
        telemetry.record_progress("dispatch")
        t0 = time.perf_counter_ns()
        with jax.default_device(self._device):
            ro_vals = _scope_state(scope, compiled.state_ro)
            if compiled.state_ro_shardings is not None and \
                    jax.process_count() <= 1:
                ro_vals = compiled.place_ro_state(ro_vals)
            mut_vals = _scope_state(scope, compiled.state_mut)
            # first call = trace + XLA compile (legitimately minutes
            # on real models): phase-aware grace so an armed watchdog
            # doesn't call a long compile a hang, and a fluid.compile
            # span inside fluid.enqueue; the cached-hit path enters the
            # shared no-op context instead (one call site — the dispatch
            # arguments can never diverge between paths).  What jit
            # compiles inside the call is counted by why: a compile in
            # the call of an executable that has run before is a
            # recompile
            with _compiling_for("dispatch" if fresh else "recompile"), \
                    telemetry.span("enqueue", step=int(step)), \
                    watchdog.extend_deadline(
                        "compile",
                        flags.get_flag("watchdog_compile_grace_s")) \
                    if fresh else _NULL_CTX, \
                    telemetry.span(
                        "compile", why="dispatch",
                        sig=costmodel.signature(
                            compiled.program_fingerprint, k=k)) \
                    if fresh else _NULL_CTX:
                fetches, new_state = compiled.fn(
                    mut_vals, ro_vals, tuple(feed_vals), step)
        t1 = time.perf_counter_ns()
        compile_s = None
        if fresh:
            # the first call of a fresh executable carries trace + XLA
            # compile — its host wall time IS the compile cost (with a
            # warm persistent cache it collapses to deserialize)
            compiled._fresh = False
            compile_s = (t1 - t0) / 1e9
            _m_compile_s.observe(compile_s, kind="dispatch")
        if benchmark:
            # FLAGS_benchmark (reference executor.cc flag): synchronise the
            # device each step and record wall time per program; a fused
            # window's entry covers its K inner steps (window-aware mean)
            jax.block_until_ready((fetches, new_state))
            profiler.record_benchmark_step(
                (time.perf_counter_ns() - t0) / 1e9, k)
            profiler.record_host_sync("benchmark")
        for n, v in zip(compiled.state_out, new_state):
            scope.set_var(n, v)
        # wire-traffic accounting: per-step payload bytes were logged at
        # trace time (the first fn call above traced, filling the cell),
        # so this is pure host arithmetic — k inner steps each move the
        # step's bytes
        comm = compiled.comm_bytes_by_axis()
        comm_bytes = 0
        comm_by = None
        comm_by_axis = None
        if comm:
            comm_by, comm_by_axis = {}, {}
            for (species, precision, ax), nb in comm.items():
                _m_comm_bytes.inc(nb * k, species=species,
                                  precision=precision, axis=ax)
                key = "%s_%s" % (species, precision)
                comm_by[key] = comm_by.get(key, 0) + nb * k
                comm_by_axis[ax] = comm_by_axis.get(ax, 0) + nb * k
                comm_bytes += nb * k
        # optimizer-memory + overlap accounting (weight-update sharding
        # / bucketed-collective telemetry): per-device optimizer-state
        # bytes and the independent-bucket count — gauges track the most
        # recent relevant dispatch, step-events carry both per dispatch
        comm_buckets = compiled.comm_grad_exchanges()
        opt_bytes = compiled.opt_state_bytes(scope) \
            if compiled.opt_state_names else 0
        if opt_bytes:
            _m_opt_state_bytes.set(opt_bytes)
        if comm_buckets:
            _m_bucket_overlap.set(round(1.0 - 1.0 / comm_buckets, 4))
        if fresh and costmodel.enabled():
            # device-cost ledger, dispatch stamp: host scalars already in
            # hand (signature, compile seconds, trace-time collective
            # bytes) — no second compile, no sync.  Full HLO analytics
            # ride cost_record()/tools/cost_ledger.py on demand.
            costmodel.stamp_compile_event(
                sig=costmodel.signature(compiled.program_fingerprint,
                                        k=k),
                k=k, window=compiled.is_window, compile_s=compile_s,
                comm=comm,
                feed_bytes=int(sum(getattr(v, "nbytes", 0)
                                   for v in feed_vals)),
                fetch_count=len(compiled.fetch_names))
        if return_numpy:
            if fetches:
                profiler.record_host_sync("fetch_numpy")
            out = [np.asarray(f) for f in fetches]
        else:
            # async fetch contract: live jax.Array futures, no device
            # sync — np.asarray(result) (or .block_until_ready())
            # materializes later
            out = list(fetches)
        # step-event record: pure host bookkeeping (attribute reads and
        # counter deltas — provably sync-free; tests/test_telemetry.py)
        _m_dispatch_s.observe((t1 - t0) / 1e9,
                              kind="window" if compiled.is_window
                              else "step")
        telemetry.record_step_event(
            ts_ns=t0, dur_ns=t1 - t0, step=int(step), k=k,
            window=compiled.is_window, plan_hit=self._last_plan_hit,
            compile_s=compile_s,
            feed_bytes=int(sum(getattr(v, "nbytes", 0)
                               for v in feed_vals)),
            fetch_count=len(compiled.fetch_names),
            syncs=profiler.host_sync_count() - syncs0,
            verdicts=k if compiled._has_verdicts else 0,
            ckpt_overlap=bool(_m_ckpt_inflight.value()),
            data_wait_s=telemetry.take_pending_data_wait(),
            comm_bytes=comm_bytes, comm_by=comm_by,
            comm_by_axis=comm_by_axis,
            comm_buckets=comm_buckets, opt_state_bytes=opt_bytes)
        return out

    def _run_pserver(self, program, scope):
        """pserver main program (transpiler get_pserver_program): exe.run
        blocks in the server loop — the reference's listen_and_serv op
        (operators/distributed_ops/listen_and_serv_op.cc).  Parameters
        already initialized in the current scope
        (exe.run(pserver_startup)) seed the server's own scope."""
        from ..distributed.ps import ParameterServer
        init = {}
        for name in program.global_block().vars:
            v = scope.find_var(name)
            if v is not None:
                init[name] = np.asarray(v)
        server = ParameterServer(
            program._ps_endpoint, program, None,
            trainers=getattr(program, "_ps_trainers", 1),
            sync_mode=getattr(program, "_ps_sync", True),
            init_weights=init)
        server.join()
        # copy trained state back so save_persistables after the
        # server loop sees the trained values (the reference's
        # listen_and_serv optimizes in the executor's own scope).
        # _ps_applying stays True: in-flight handler threads may
        # still run the program; re-serving needs a fresh
        # get_pserver_program() call.
        for name, val in server._scope.vars.items():
            scope.set_var(name, val)
        return []

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           steps_per_run=None, checkpoint_manager=None,
                           checkpoint_period=None, rollback_reseed=False):
        """Consume every sample in ``dataset`` through the compiled step
        (reference executor.py:926 → executor.cc:120 RunFromDataset).

        The reference runs `thread` Hogwild workers; on TPU one XLA step is
        the engine, so `thread` caps the dataset's reader threads and
        batches stream back-to-back with async dispatch: feeds move
        host→device ONCE via jax.device_put with a one-batch prefetch
        (the next batch's H2D transfer is issued before the current
        batch's result is consumed, double-buffering transfer under
        compute), and the only host syncs are the ``print_period`` loss
        pulls and the final drain.

        ``steps_per_run=K`` (default ``FLAGS_steps_per_run``) engages
        the multi-step fused loop: K batches are staged ahead as ONE
        stacked [K, ...] device array (the same one-window lookahead)
        and ``run_window`` runs them in one dispatch — host overhead
        per step drops ~1/K and a ``print_period`` pull costs one sync
        per WINDOW.  The trailing partial window (fewer than K batches
        left) runs as a smaller window, so every sample is consumed.

        Self-healing (docs/checkpointing.md "Preemption and
        self-healing"): with a ``checkpoint_manager``, the loop saves
        every ``checkpoint_period`` steps (at window boundaries by
        construction); a preemption stop request
        (``fluid.preemption.install()`` / ``request_stop()``) drains the
        current window, takes a final save, waits out any async save,
        and returns cleanly; and under ``FLAGS_check_nan_inf=skip`` with
        ``FLAGS_bad_step_rollback=K``, K consecutive bad-step verdicts
        restore the last checkpoint and resume (``rollback_reseed=True``
        additionally derives a fresh program seed so the replay draws
        different PRNG streams), capped at ``FLAGS_rollback_limit``
        attempts before raising.

        Returns a status dict ``{"steps", "preempted", "rollbacks"}``
        (previously None): ``preempted`` is the loop's own stop
        verdict — on a pod it is the CONSENSUS answer, so the elastic
        driver (fluid/elastic.py) can read it directly instead of
        asking another collective round."""
        if dataset is None:
            raise RuntimeError("dataset is need and should be initialized")
        K = flags.steps_per_run_value(steps_per_run)
        program = program or framework.default_main_program()
        scope = scope or global_scope()
        manager = checkpoint_manager
        roll_k = int(flags.get_flag("bad_step_rollback") or 0)
        if roll_k:
            if manager is None:
                raise ValueError(
                    "FLAGS_bad_step_rollback=%d needs a "
                    "checkpoint_manager= to restore from" % roll_k)
            if flags.nan_inf_policy() != "skip":
                raise ValueError(
                    "FLAGS_bad_step_rollback needs FLAGS_check_nan_inf="
                    "skip — no other policy produces the bad-step "
                    "verdicts it counts")
        roll_limit = int(flags.get_flag("rollback_limit"))
        rollbacks = 0
        preempted = False
        if thread:
            # thread>0 sets the reader thread count directly (the reference
            # takes min() with the dataset's own setting, but its default of
            # 1 would make this argument a silent no-op)
            dataset.set_thread(thread)
        fetch_list = fetch_list or []
        fetch_names = [v.name if isinstance(v, framework.Variable) else v
                       for v in fetch_list]
        fetch_info = fetch_info or fetch_names
        dataset._prepare_to_run()
        # multi-process feeds must stay numpy (THE GLOBAL value per
        # process — globalize_feeds shards them); single-process feeds
        # prefetch to the device
        source = iter(dataset)
        if K > 1:
            # stage K batches per window, stacked on the host so the
            # whole window moves H2D as one array per slot
            from .dataset import stack_batch_windows
            source = stack_batch_windows(source, K)
        batches = source if jax.process_count() > 1 else \
            self._prefetch_feeds(program.global_block(), source)
        # multi-process: stop/rollback decisions are COLLECTIVE (one
        # small allgather folding both flags) taken on a DETERMINISTIC
        # boundary schedule every process computes identically — every
        # checkpoint-due boundary (a poisoned streak must never be
        # checkpointed, and the pod save's barriers need unanimous
        # participation) plus every ``consensus_every``-th boundary
        # (amortizing the collective off the K=1 hot path; a stop
        # drains at the next consensus point, still the SAME boundary
        # on every process).  Single-process keeps the per-boundary
        # local checks unchanged.
        from . import distributed as dist
        world = dist.process_count()
        consensus_every = max(1, 16 // K)
        boundary = 0
        n = 0
        try:
            import time as _time
            t0 = _time.perf_counter()
            for batch in batches:
                if K > 1:
                    k = int(np.shape(next(iter(batch.values())))[0]) \
                        if batch else K
                    out = self.run_window(program, feed=batch,
                                          fetch_list=fetch_names,
                                          scope=scope, steps_per_run=k,
                                          return_numpy=False)
                else:
                    k = 1
                    out = self.run(program, feed=batch,
                                   fetch_list=fetch_names,
                                   scope=scope, return_numpy=False)
                prev, n = n, n + k
                boundary += 1
                save_due = (manager is not None and checkpoint_period and
                            n // checkpoint_period !=
                            prev // checkpoint_period)
                stop = preemption.stop_requested()
                streak, roll_hit = 0, False
                if roll_k:
                    # reading the streak drains the pending verdict pool
                    # (materializes the device verdicts — the one host
                    # cost of the rollback policy, per boundary); checked
                    # BEFORE the periodic save so a poisoned streak can
                    # never be checkpointed as if it were healthy
                    streak = profiler.bad_step_streak()
                    roll_hit = streak >= roll_k
                if world > 1:
                    # pod consensus: a SIGTERM delivered to (or a bad
                    # streak observed on) ONE process acts on EVERY
                    # process at the SAME boundary, so nobody parks
                    # inside a collective — or a pod save's barrier —
                    # whose peer already left (docs/distributed.md)
                    if save_due or boundary % consensus_every == 0:
                        stop, roll_hit = dist.consensus_flags(stop,
                                                              roll_hit)
                    else:
                        stop = roll_hit = False
                rolled = False
                if roll_hit:
                    rollbacks += 1
                    self._rollback_restore(manager, scope, program,
                                           streak, rollbacks,
                                           roll_limit, rollback_reseed,
                                           remote=streak < roll_k)
                    rolled = True
                if save_due and not rolled:
                    # lands right after a dispatch, so windowed jobs are
                    # at their boundary marker; snapshot sync, I/O async
                    manager.save(scope=scope, main_program=program)
                if stop:
                    # graceful stop: the window that was in flight has
                    # fully committed — drain, checkpoint, exit clean
                    preempted = True
                    break
                if fetch_names and n // print_period != prev // print_period:
                    # ONE sync per window even when the window crosses a
                    # print boundary: the stacked fetch materializes all
                    # K per-step values in a single pull
                    profiler.record_host_sync("print_period")
                    vals = [np.asarray(v) for v in out]
                    if K > 1:   # last inner step's value
                        vals = [v[-1] for v in vals]
                    msg = ", ".join("%s=%s" % (k2, np.ravel(v)[:8])
                                    for k2, v in zip(fetch_info, vals))
                    print("[train_from_dataset] batch %d: %s" % (n, msg))
                if debug and n // print_period != prev // print_period:
                    dt = _time.perf_counter() - t0
                    print("[train_from_dataset] %d batches, %.1f batch/s"
                          % (n, n / dt))
            # drain the dispatch queue so scope state is materialized
            for v in scope.vars.values():
                if isinstance(v, jax.Array):
                    profiler.record_host_sync("drain")
                    v.block_until_ready()
                    break
            if not preempted and _stop_consensus():
                # a stop request that landed while the consumer was
                # parked on the (preemption-drained) feed ring ends the
                # batch stream without reaching the per-batch check —
                # it still gets the full drain + final-save treatment
                # (consensus again: every process's stream ended at the
                # same count, so all reach this point together)
                preempted = True
            if preempted:
                # preemption-safe shutdown: final checkpoint + durability
                # barrier before handing control back — the caller exits
                # 0 with zero lost work (docs/checkpointing.md)
                t_d0 = time.perf_counter_ns()
                if manager is not None:
                    # the periodic save may have just checkpointed this
                    # very boundary — don't serialize the full state
                    # twice inside the scheduler's grace window (wait()
                    # first: an async save's last_step lands on commit)
                    manager.wait()
                    if manager.last_step != int(scope.step_counter):
                        # forced synchronous: the process exits after the
                        # drain, so the final save must be COMMITTED (not
                        # in flight) before control returns — and an
                        # abandoned async commit leaves last_step unset,
                        # which is exactly what re-triggers this save
                        manager.save(scope=scope, main_program=program,
                                     sync=True)
                        manager.wait()
                preemption.record_drain(
                    step=scope.step_counter,
                    dur_ns=time.perf_counter_ns() - t_d0,
                    saved=manager is not None)
        finally:
            if hasattr(batches, "close"):
                # stop the prefetch/staging generator stack promptly so
                # producer threads (dataset shard readers) see their stop
                # event now, not at GC time — the preemption clean-drain
                # contract
                batches.close()
            dataset._finish_to_run()
        return {"steps": int(n), "preempted": bool(preempted),
                "rollbacks": int(rollbacks)}

    def _rollback_restore(self, manager, scope, program, streak, attempt,
                          limit, reseed, remote=False):
        """Self-healing rollback (FLAGS_bad_step_rollback): ``streak``
        consecutive bad-step verdicts mean the state or input stream is
        poisoned beyond what per-step skipping heals — restore the last
        complete checkpoint and let the loop resume.  Bounded by
        ``FLAGS_rollback_limit`` attempts per train_from_dataset call,
        after which the job fails loudly.  ``remote=True`` marks a
        pod-consensus trigger whose qualifying streak was observed on a
        PEER process (this process's local ``streak`` is below the
        threshold — honest diagnostics, not a contradiction)."""
        t0 = time.perf_counter_ns()
        where = " (qualifying streak observed on a peer process)" \
            if remote else ""
        if attempt > limit:
            raise RuntimeError(
                "bad-step rollback limit reached: %d rollback(s) "
                "(FLAGS_rollback_limit) did not clear the %d-consecutive"
                "-bad-step condition%s (FLAGS_bad_step_rollback) — the "
                "input stream or model is persistently poisoned"
                % (limit, streak, where))
        # an in-flight async save must land before "latest" is chosen,
        # and a failed one must surface here, not after the restore
        manager.wait()
        meta = manager.resume(scope=scope, main_program=program)
        if meta is None:
            raise RuntimeError(
                "bad-step rollback triggered (%d consecutive bad steps) "
                "but %r holds no complete checkpoint to restore — save "
                "one before relying on FLAGS_bad_step_rollback (e.g. "
                "checkpoint_period=, or an explicit save at start)"
                % (streak, manager.dirname))
        if reseed:
            # a bit-exact replay of the poisoned trajectory would fail
            # again; a fresh program seed re-keys every step-keyed PRNG
            # stream from the restored step on (the seed is part of the
            # executable fingerprint, so this recompiles — rollback is
            # already off the hot path)
            program.random_seed = \
                (program.random_seed * 1000003 + attempt) % (2 ** 31 - 1)
            program._bump_version()
        # the restored state starts a fresh streak — the verdicts that
        # triggered this rollback are history
        profiler.reset_bad_step_streak()
        _m_rollbacks.inc()
        _m_rollback_step.set(int(meta["step"]))
        telemetry.record_lifecycle_event(
            "rollback", step=int(meta["step"]), streak=int(streak),
            attempt=int(attempt), dur_ns=time.perf_counter_ns() - t0,
            reseeded=bool(reseed), remote=bool(remote))
        return meta

    def _prefetch_feeds(self, block, batches):
        """Device prefetch for the dataset path: batches are coerced
        and device_put ahead of consumption (prefetch_ahead — the
        FLAGS_feed_ring_depth async ring, or the depth-0 one-step
        lookahead).  ``_last_compiled`` is read fresh per batch so
        feeds follow the plan's shardings from the second window on
        (GSPMD feeds land already sharded).  device_put is async —
        nothing here syncs the device."""
        fingerprint = block.program.fingerprint

        def put(d):
            compiled = self._last_compiled
            shardings = None
            if compiled is not None and \
                    compiled.program_fingerprint == fingerprint:
                fsh = compiled.feed_shardings or \
                    compiled.feed_placement_shardings
                if fsh:
                    shardings = dict(zip(compiled.feed_names, fsh))
            return sharded_put(
                d, shardings, self._device,
                coerce=lambda k, v: coerce_feed_value(block, k, v))

        return prefetch_ahead(put, batches)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           steps_per_run=None):
        """Inference twin of train_from_dataset (executor.py:849): same
        streaming loop — pass an inference/test program."""
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period,
                                       steps_per_run=steps_per_run)

    def close(self):
        self._cache.clear()
        self._plans.clear()

    # -- compilation -------------------------------------------------------
    def _compile(self, program, feed_names, feed_shapes, fetch_names,
                 in_shardings=None, steps_per_run=None):
        self._compile_count += 1
        # build count by persistent-cache state: with a cache directory
        # the XLA compile riding the first dispatch deserializes from
        # disk when warm — compare executor_compile_seconds between the
        # two labels to see the cache's effect
        _m_compiles.inc(persistent_cache=(
            "on" if jax.config.jax_compilation_cache_dir else "off"))
        windowed = steps_per_run is not None
        K = int(steps_per_run) if windowed else 1
        if windowed:
            # feed_shapes arrive stacked [K, ...]; every per-step shape
            # decision below (dp divisibility, sp dims) uses the inner
            # step's view
            feed_shapes = [tuple(s)[1:] for s in feed_shapes]
        block = program.global_block()
        reads, writes = _block_reads_writes(block, feed_names)

        state_in, state_out = [], []
        for n in reads:
            var = block._find_var_recursive(n)
            if var is None or var.persistable or n in fetch_names:
                state_in.append(n)
            else:
                raise RuntimeError(
                    "Op input %r is neither fed, produced by a prior op, nor "
                    "persistable — the program reads an undefined temporary."
                    % n)
        for n in writes:
            var = block._find_var_recursive(n)
            if var is not None and var.persistable:
                state_out.append(n)
        # fetched persistables that are never written still need to pass
        # through; fetched names must exist in env.
        for n in fetch_names:
            var = block._find_var_recursive(n)
            if (n not in writes and n not in feed_names and n not in state_in):
                state_in.append(n)

        write_set = set(writes)
        state_mut = [n for n in state_in if n in write_set]
        state_ro = [n for n in state_in if n not in write_set]

        seed = program.random_seed
        blocks = program.blocks
        is_test = program._is_test
        amp_dtype = getattr(program, "_amp_dtype", None)
        amp_keep = getattr(program, "_amp_keep", False)
        use_collective = getattr(program, "_use_collective", False)

        # shared with the traced fn below: each complete trace overwrites
        # "entries" with its collective wire-traffic log, so retraces are
        # idempotent and the dispatch path reads exact per-step bytes
        comm_cell = {"entries": None}

        def make_fn(axis_env=(), mesh=None):
            def fn(mut_vals, ro_vals, feed_vals, step):
                env = dict(zip(state_mut, mut_vals))
                env.update(zip(state_ro, ro_vals))
                env.update(zip(feed_names, feed_vals))
                base_key = step_prng_key(seed, step)
                st = ExecState(blocks, step, base_key, is_test=is_test,
                               axis_env=axis_env, amp_dtype=amp_dtype,
                               amp_keep=amp_keep, mesh=mesh)
                st.comm_log = []
                run_block(block, env, st)
                comm_cell["entries"] = tuple(st.comm_log)
                return ([env[n] for n in fetch_names],
                        [env[n] for n in state_out])
            return fn

        if getattr(program, "_pipeline_config", None):
            from .pipeline import compile_pipeline_step
            from .lowering import dispatch

            def run_ops(ops, env, st, blk):
                for op in ops:
                    dispatch(op, env, st, blk)

            devices = list(jax.devices(self._device.platform))
            fn, pp_mesh = compile_pipeline_step(
                program, feed_names, fetch_names, state_mut, state_ro,
                state_out, devices, run_ops, ExecState, seed, amp_dtype)
            if windowed:
                # the GPipe schedule composes inside the outer window
                # scan: the shard_map'd schedule traces once as the scan
                # body, so its collective species/counts are exactly the
                # K=1 step's
                fn = _make_window_fn(fn, state_mut, state_out, K)
            jit_kwargs = {"donate_argnums": (0,)}
            if getattr(program, "_mp_shardings", None):
                # 3D composition: Megatron-annotated weights (+ their
                # accumulators) enter the pipeline step pinned to their
                # 'mp' GSPMD sharding; the shard_map inside is manual
                # only over (dp, pp), so these shardings survive
                mp_specs = _mp_state_specs(program, pp_mesh)
                jit_kwargs["in_shardings"] = (
                    tuple(mp_specs.get(n) for n in state_mut),
                    tuple(mp_specs.get(n) for n in state_ro),
                    None, None)
                jit_kwargs["out_shardings"] = (
                    None, [mp_specs.get(n) for n in state_out])
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                jitted = jax.jit(fn, **jit_kwargs)
            cblock = _CompiledBlock(jitted, state_mut, state_ro, state_out,
                                    feed_names, fetch_names)
            cblock.steps_per_run = K
            cblock.is_window = windowed
            cblock._jitted = jitted
            cblock._comm_cell = comm_cell
            cblock.program_fingerprint = program.fingerprint
            return cblock.annotate_opt_state(program)

        if use_collective:
            cblock = self._compile_collective(program, make_fn, feed_names,
                                              fetch_names, state_mut,
                                              state_ro, state_out,
                                              steps_per_run=steps_per_run)
            cblock.steps_per_run = K
            cblock.is_window = windowed
            cblock._comm_cell = comm_cell
            cblock.program_fingerprint = program.fingerprint
            return cblock.annotate_opt_state(program)

        extra_axes = _model_parallel_axes(program)
        if in_shardings is None and extra_axes:
            # model-parallel program run through plain Executor.run: build
            # the (dp, mp/sp/ep...) mesh over all visible devices ourselves
            # (the transpilers set _mp/_sp/_ep degrees + annotations)
            from jax.sharding import NamedSharding, PartitionSpec as P
            from .mesh_utils import build_mesh
            devices = list(jax.devices(self._device.platform))
            model = int(np.prod([d for _, d in extra_axes]))
            if len(devices) % model:
                raise RuntimeError(
                    "model-parallel degrees %s do not divide the %d "
                    "visible %s devices" % (dict(extra_axes), len(devices),
                                            self._device.platform))
            mesh = build_mesh(
                ("dp",) + tuple(n for n, _ in extra_axes),
                (-1,) + tuple(d for _, d in extra_axes), devices=devices)
            in_shardings = ("state-sharded", NamedSharding(mesh, P()),
                            NamedSharding(mesh, P("dp")), frozenset())
        trace_mesh = in_shardings[1].mesh if in_shardings is not None \
            else None
        fn = make_fn(mesh=trace_mesh)
        jit_kwargs = {"donate_argnums": (0,)}
        if in_shardings is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            # (marker, replicated sharding, batch-dim sharding[, sharded
            # state names]) from CompiledProgram: feeds sharded on dim 0;
            # state replicated EXCEPT names in the ZeRO-1 set, which are
            # stored P('dp') between steps (out_shardings pins the updated
            # state to the same layout so GSPMD keeps storage sharded and
            # inserts the gathers around compute itself).
            _, repl, shard0, sharded_names = in_shardings
            # Megatron TP / expert parallel: weights annotated by the
            # transpilers (and their same-shaped optimizer accumulators)
            # are stored sharded over their mesh axis; GSPMD inserts the
            # collectives during partitioning.
            mp_specs = _mp_state_specs(program, repl.mesh) \
                if getattr(program, "_mp_shardings", None) else {}

            def spec_of(n):
                if n in mp_specs:
                    return mp_specs[n]
                return shard0 if n in sharded_names else repl

            # feeds shard on dim 0 only when the dp axis divides it —
            # partial last batches and rank-0 feeds stay replicated (GSPMD
            # shardings are layout hints, not semantics, so this is safe)
            first = shard0.spec[0] if len(shard0.spec) else None
            axes = (first,) if isinstance(first, str) else tuple(first or ())
            dp_size = int(np.prod([shard0.mesh.shape[a]
                                   for a in axes])) if axes else 1
            # sequence-parallel feeds additionally shard their sequence
            # dim over 'sp' (transpiler/sequence_parallel.py records which
            # feed carries the sequence on which dim)
            sp_feed_dims = getattr(program, "_sp_feed_dims", {}) or {}
            sp_size = dict(repl.mesh.shape).get("sp", 1)

            def feed_spec(name, shape):
                shape = shape or ()
                dp_ok = (len(shape) >= 1 and shape[0] and dp_size and
                         shape[0] % dp_size == 0)
                sdim = sp_feed_dims.get(name)
                sp_ok = (sdim is not None and sp_size > 1 and
                         len(shape) > sdim and shape[sdim] and
                         shape[sdim] % sp_size == 0)
                if sp_ok:
                    parts = [None] * len(shape)
                    if dp_ok:
                        parts[0] = "dp"
                    if sdim == 0 and dp_ok:
                        # a dim-0 sequence sharding COMPOSES with the
                        # batch axis (ADVICE r4: assigning 'sp' here must
                        # not silently replace the 'dp' feed sharding);
                        # both axes split dim 0 only when they divide it
                        # jointly, else dp wins
                        if shape[0] % (dp_size * sp_size) == 0:
                            parts[0] = ("dp", "sp")
                    else:
                        parts[sdim] = "sp"
                    return NamedSharding(repl.mesh, P(*parts))
                return shard0 if dp_ok else repl

            feed_shardings = tuple(feed_spec(n, s)
                                   for n, s in zip(feed_names, feed_shapes))
            if windowed:
                # stacked [K, ...] window feeds: the window dim rides
                # unsharded ahead of the per-step dp/sp placement
                feed_shardings = tuple(_window_feed_sharding(s)
                                       for s in feed_shardings)
            jit_kwargs["in_shardings"] = (
                tuple(spec_of(n) for n in state_mut),
                tuple(spec_of(n) for n in state_ro),
                feed_shardings,
                repl)
            if sharded_names or mp_specs:
                # fn returns ([fetches], [state]) — match list structure
                jit_kwargs["out_shardings"] = (
                    [None for _ in fetch_names],
                    [spec_of(n) for n in state_out])
        nan_policy = flags.nan_inf_policy()
        if nan_policy == "raise":
            # FLAGS_check_nan_inf (operator.cc:953 contract): the per-op
            # isfinite checks emitted by lowering.dispatch become checkify
            # user checks; throw host-side after the step with the op
            # name.  Shares the jit in/out shardings with the normal path
            # so the debug flag works on sharded/multi-process programs
            # too — checkify prepends an error slot to the output tree,
            # which rides unconstrained (None prefix).  For a K-step
            # window, checkify transforms THROUGH the scan, so the first
            # offending inner step's op still names itself.
            from jax.experimental import checkify
            target = _make_window_fn(fn, state_mut, state_out, K) \
                if windowed else fn
            checked = checkify.checkify(target, errors=checkify.user_checks)
            ck_kwargs = dict(jit_kwargs)
            if "out_shardings" in ck_kwargs:
                ck_kwargs["out_shardings"] = (None,
                                              ck_kwargs["out_shardings"])
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                jitted_c = jax.jit(checked, **ck_kwargs)

            def runner(mut_vals, ro_vals, feed_vals, step):
                err, out = jitted_c(mut_vals, ro_vals, feed_vals, step)
                err.throw()
                return out
            cblock = _CompiledBlock(runner, state_mut, state_ro, state_out,
                                    feed_names, fetch_names)
            # introspection lowers the checkified jit itself — ``runner``
            # is a plain closure with no .lower (ADVICE r5: compiled_hlo
            # crashed under FLAGS_check_nan_inf)
            cblock._jitted = jitted_c
        elif nan_policy == "skip":
            # FLAGS_check_nan_inf=skip: the production "one poisoned batch
            # must not kill a pod job" policy (_make_skip_fn).  Inside a
            # K-step window the guard runs per INNER step on that step's
            # carried state — one poisoned batch loses only its own step,
            # the other K-1 steps of the window still commit — and the
            # verdicts ride back as a [K] vector counted lazily.
            fn_skip = _make_skip_fn(fn, state_mut, state_out)
            target = _make_window_fn(fn_skip, state_mut, state_out, K,
                                     has_ok=True) if windowed else fn_skip
            sk_kwargs = dict(jit_kwargs)
            if "out_shardings" in sk_kwargs:
                f_sh, s_sh = sk_kwargs["out_shardings"]
                sk_kwargs["out_shardings"] = (f_sh, s_sh, None)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                jitted_s = jax.jit(target, **sk_kwargs)

            def runner(mut_vals, ro_vals, feed_vals, step):
                fetches, new_state, ok = jitted_s(mut_vals, ro_vals,
                                                  feed_vals, step)
                profiler.record_bad_step(ok)
                return fetches, new_state
            cblock = _CompiledBlock(runner, state_mut, state_ro, state_out,
                                    feed_names, fetch_names)
            cblock._jitted = jitted_s
            cblock._has_verdicts = True
        else:
            target = _make_window_fn(fn, state_mut, state_out, K) \
                if windowed else fn
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                jitted = jax.jit(target, **jit_kwargs)
            cblock = _CompiledBlock(jitted, state_mut, state_ro, state_out,
                                    feed_names, fetch_names)
            cblock._jitted = jitted
        cblock.steps_per_run = K
        cblock.is_window = windowed
        cblock._comm_cell = comm_cell
        cblock.program_fingerprint = program.fingerprint
        cblock.annotate_opt_state(program)
        if jit_kwargs.get("in_shardings") is not None:
            # multi-process runs must globalize numpy feeds that carry a
            # non-trivial sharding (run() consults this): jax refuses
            # plain numpy args there, every process holding the same
            # global value is exactly the make_array_from_callback case
            cblock.feed_shardings = jit_kwargs["in_shardings"][2]
            cblock.state_ro_shardings = jit_kwargs["in_shardings"][1]
        return cblock

    def _compile_collective(self, program, make_fn, feed_names, fetch_names,
                            state_mut, state_ro, state_out,
                            steps_per_run=None):
        """Explicit-collective execution: run the block under shard_map over
        a 'dp' mesh axis so the program's c_* ops become ICI/DCN
        collectives.  Returns the fully-annotated :class:`_CompiledBlock`.

        This is the TPU analogue of ParallelExecutor driving a graph with
        inserted AllReduceOpHandles (parallel_executor.cc:327): one XLA
        computation per device shard, communication expressed by the
        program's own collective ops.  Per-replica values fetched with a
        batch dim are concatenated across replicas, as the reference's fetch
        does; scope state takes replica 0's copy (reference ParallelExecutor
        keeps per-device copies and saves device 0's).

        The mesh spans the GLOBAL device list (``mesh_utils.
        ordered_devices`` under ``jax.distributed`` — the pod-scale
        runtime, docs/distributed.md), so under ``fluid.distributed.
        init`` the same program runs multi-process: each process feeds
        its LOCAL batch (``_CompiledBlock.globalize_feeds`` assembles
        the global array — part of the dispatch plan, not a bespoke
        per-call wrapper), batch-sharded fetches localize back to this
        host's rows, and replicated state rides as numpy / replicated
        global arrays.  ONE jitted executable per compile, cached like
        every other path — the PR 2 dispatch-plan hot path serves
        multi-host dispatches too.

        ``steps_per_run=K`` fuses K steps: the PER-SHARD step fn is
        wrapped in the shared ``_make_window_fn`` scan BEFORE shard_map,
        so the scan body traces once and the window's collective
        species/counts are exactly the K=1 step's — persistable state
        (incl. the int8 error-feedback residuals and the ZeRO-style
        sharded optimizer moments) carries through the scan like on the
        GSPMD path.  Feeds arrive stacked [K, ...]; their dp sharding
        shifts one dim right.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .mesh_utils import build_mesh, ordered_devices

        platform = self._device.platform
        # ordered_devices(platform) (not a filter over jax.devices()) so
        # a CPU mesh is reachable even when the default backend is a
        # 1-chip TPU — and under jax.distributed this is the GLOBAL
        # device list in (process_index, id) order, so every process
        # builds the identical mesh
        devices = ordered_devices(platform=platform)
        nranks = getattr(program, "_collective_nranks", None) or len(devices)
        if nranks > len(devices):
            # a program transpiled for N ranks silently running on fewer
            # devices would shard differently — fail loudly instead
            # (closes the c_comm_init nranks/mesh mismatch hole)
            raise RuntimeError(
                "program was transpiled for nranks=%d but only %d %s "
                "devices are visible across %d process(es) (launch more "
                "processes / check fluid.distributed.init)"
                % (nranks, len(devices), platform, jax.process_count()))
        devices = devices[:nranks]
        multi_host = len({d.process_index for d in devices}) > 1
        hier = getattr(program, "_collective_hierarchical", None)
        if hier and hier > 1:
            # two-level reduction (reference nccl_helper.h:246 hierarchical
            # allreduce; BuildStrategy.use_hierarchical_allreduce): outer
            # 'dcn' axis across nodes, inner 'ici' axis within a node.
            # A psum over ("dcn", "ici") lowers to XLA's two-phase
            # reduce — reduce-scatter on ici, allreduce on dcn, gather.
            if len(devices) % hier:
                raise RuntimeError(
                    "hierarchical allreduce: %d devices not divisible by "
                    "nnodes=%d" % (len(devices), hier))
            mesh = build_mesh(("dcn", "ici"), (hier, -1), devices=devices)
            rings = getattr(program, "_collective_rings", None) or {}
            rings = {r: ("dcn", "ici") for r in (rings or {0: None})}
            dp_spec = P(("dcn", "ici"))
        else:
            mesh = build_mesh(("dp",), devices=devices)
            rings = getattr(program, "_collective_rings", None) or {0: "dp"}
            dp_spec = P("dp")
        fn = make_fn(axis_env=rings)

        state = {"jitted": None, "out_fetch_specs": None}
        windowed = steps_per_run is not None
        K = int(steps_per_run) if windowed else 1
        # weight-update sharding (transpiler.collective._transpile_wus):
        # these persistable vars — optimizer-moment shards and the
        # AG-phase EF residuals — are STORED P('dp') between steps, each
        # device holding only its 1/N slice (the ZeRO-1 memory win);
        # everything else stays replicated as before.  Multi-host, the
        # slices span processes: each process addresses only its own.
        sharded = frozenset(getattr(program, "_dp_sharded_state", ())
                            or ())

        def state_spec(n):
            return dp_spec if n in sharded else P()

        def _spec_replicated(spec):
            return all(p is None for p in tuple(spec))

        def globalize_state(vals, names):
            """Multi-host: dp-sharded state handed in as host numpy (a
            checkpoint restore put the GATHERED global value back into
            the scope) re-shards onto the global mesh — each process
            materializes only its addressable slices.  Already-global
            jax.Arrays (the steady state: every dispatch returns them)
            pass through untouched; replicated numpy rides as-is (jit
            treats uncommitted arrays as replicated per-process
            copies)."""
            if not multi_host or not sharded:
                return vals
            out = list(vals)
            for i, (n, v) in enumerate(zip(names, vals)):
                if n not in sharded or (isinstance(v, jax.Array) and
                                        not v.is_fully_addressable):
                    continue
                arr = np.asarray(v)
                out[i] = jax.make_array_from_callback(
                    arr.shape, NamedSharding(mesh, state_spec(n)),
                    lambda idx, a=arr: a[idx])
            return tuple(out)

        def build(mut_vals, ro_vals, feed_vals, step):
            """Build (once) and return the shard_map'd jitted step —
            shared by the dispatch path and, via ``call.ensure_built``,
            by Executor._lowered_executable so the explicit-collective
            path is HLO-introspectable like every other path.
            ``feed_vals`` carry GLOBAL shapes (multi-host callers
            globalize first — _run_plan already does)."""
            if state["jitted"] is not None:
                return state["jitted"]
            # out_specs need output ranks: probe with eval_shape on the
            # unmapped fn (ranks are identical under the map); windowed
            # feeds probe their per-step [1:] slice.
            probe_feeds = tuple(v[0] for v in feed_vals) if windowed \
                else feed_vals
            fetches_s, outs_s = jax.eval_shape(make_fn(), mut_vals,
                                               ro_vals, probe_feeds, step)
            fetch_specs = [dp_spec if s.ndim >= 1 else P()
                           for s in fetches_s]
            out_state_specs = [state_spec(n) for n in state_out]
            target = fn
            feed_specs = tuple(dp_spec for _ in feed_vals)
            out_fetch_specs = fetch_specs
            if windowed:
                # K-step window: scan the PER-SHARD step, then map —
                # the scan body (and its collectives) trace once, so
                # species/counts match K=1; stacked [K, ...] feeds and
                # fetches shift their dp placement one dim right
                target = _make_window_fn(fn, state_mut, state_out, K)
                feed_specs = tuple(P(*((None,) + tuple(dp_spec)))
                                   for _ in feed_vals)
                out_fetch_specs = [P(*((None,) + tuple(s)))
                                   for s in fetch_specs]
            state["out_fetch_specs"] = out_fetch_specs
            from .mesh_utils import shard_map
            smapped = shard_map(
                target, mesh=mesh,
                in_specs=(tuple(state_spec(n) for n in state_mut),
                          tuple(state_spec(n) for n in state_ro),
                          feed_specs,
                          P()),
                out_specs=(out_fetch_specs, out_state_specs),
                check_vma=False)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                state["jitted"] = jax.jit(smapped, donate_argnums=(0,))
            return state["jitted"]

        def call(mut_vals, ro_vals, feed_vals, step):
            """ONE cached executable per compile (the dispatch-plan
            contract): feeds arrive already globalized (the plan's
            globalize step), state re-shards only after a restore, and
            the only per-call multi-host work is handing batch-sharded
            fetches back as this host's rows (local feed → local fetch,
            the launch.py contract)."""
            jitted = build(mut_vals, ro_vals, feed_vals, step)
            mut_vals = globalize_state(mut_vals, state_mut)
            ro_vals = globalize_state(ro_vals, state_ro)
            fetches, outs = jitted(mut_vals, ro_vals, feed_vals, step)
            if multi_host:
                from jax.experimental import multihost_utils
                fetches = [
                    f if _spec_replicated(spec) else
                    multihost_utils.global_array_to_host_local_array(
                        f, mesh, spec)
                    for f, spec in zip(fetches,
                                       state["out_fetch_specs"])]
            return fetches, outs

        call.ensure_built = build
        cblock = _CompiledBlock(call, state_mut, state_ro, state_out,
                                feed_names, fetch_names)
        cblock.collective_mesh = mesh
        # feed contract: each process's local batch is one shard of the
        # global batch along dp (shifted one dim right inside a stacked
        # [K, ...] window)
        per_feed = P(*((None,) + tuple(dp_spec))) if windowed \
            else dp_spec
        if multi_host:
            cblock.feed_local_specs = tuple(per_feed for _ in feed_names)
        else:
            # world of one (incl. the elastic survivor that shrank to a
            # single process): feeds the prefetch committed to ONE
            # device must land on the collective mesh instead — these
            # shardings drive the prefetch put and the dispatch-time
            # fix_feed_placements guard
            cblock.feed_placement_shardings = tuple(
                NamedSharding(mesh, per_feed) for _ in feed_names)
        return cblock
