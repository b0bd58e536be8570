"""Process-level flags, settable via FLAGS_* environment variables.

Reference pattern: gflags defined at C++ use sites + the ``__bootstrap__``
env allowlist (python/paddle/fluid/__init__.py:124 ``--tryfromenv``), so
``FLAGS_foo=x python train.py`` works identically here.

Notable TPU-specific flag: ``FLAGS_matmul_precision`` — XLA precision for
fp32 matmul/conv on the MXU.  ``default`` (single bf16 pass, fastest),
``float32``/``highest`` (multi-pass fp32 emulation: bit-accurate but an
order of magnitude slower to compile AND run on TPU — measured 62s vs 1.7s
compile for one conv).  AMP/bf16 training makes this moot; fp32 parity
checks on CPU are unaffected (CPU ignores precision).

Fault-tolerance flags (checkpoint.py, docs/checkpointing.md):

- ``FLAGS_checkpoint_async`` (default on) — ``CheckpointManager.save``
  returns right after the device→host snapshot; serialization + fsync +
  atomic commit run on a background thread (at most one in flight,
  errors re-raised on the next ``save()``/``wait()``).  Off forces fully
  synchronous, durable-on-return saves.
- ``FLAGS_check_nan_inf`` is a POLICY, not just a bool: ``off`` (default),
  ``raise`` (also ``1``/``true``: per-op isfinite checkify asserts that
  throw host-side naming the op — the reference operator.cc:953
  contract), or ``skip`` (detect a non-finite step, LEAVE persistable
  state untouched, bump ``profiler.bad_step_count()`` and continue — the
  production "one poisoned batch must not kill a pod job" path).
- ``FLAGS_bad_step_rollback=K`` / ``FLAGS_rollback_limit`` — the
  self-healing escalation of ``skip``: K consecutive bad steps restore
  the last checkpoint (``train_from_dataset(checkpoint_manager=...)``)
  instead of endlessly skipping, capped at ``rollback_limit`` attempts.
- ``FLAGS_storage_retries`` / ``FLAGS_storage_retry_backoff_s`` — the
  object-store checkpoint backend's bounded retry-with-backoff on
  transient I/O errors (storage.py; docs/checkpointing.md).
- ``FLAGS_checkpoint_commit_timeout_s`` — bound on the collective-free
  pod-save commit poll (docs/checkpointing.md "Async pod checkpoints"):
  how long the chief polls storage for sibling manifests (and workers
  for the chief's marker) before abandoning the prefix as debris.
- ``FLAGS_checkpoint_reap_min_age_s`` — minimum age before the storage
  debris reaper may delete an unmarked ``step-*`` prefix: younger
  prefixes are presumed to be an async pod save still uploading.
"""

import os

_DEFS = {
    "matmul_precision": "default",   # default | high | highest
    "check_nan_inf": "off",          # off | raise | skip — non-finite
                                     # policy (nan_inf_policy(); bools
                                     # accepted for back-compat)
    "benchmark": False,              # per-step device sync + wall timing
    "eager_delete_tensor_gb": 0.0,   # accepted for parity; XLA owns buffers
    "tpu_donate_buffers": True,
    "rpc_deadline": 180000.0,        # ms, PS rpc call deadline (reference)
    "rpc_retry_times": 3.0,          # call-level retries on broken conns
    "prng_impl": "rbg",              # rbg (HW RngBitGenerator) | threefry
                                     # | unsafe_rbg (rbg-keyed split too)
    "steps_per_run": 1,              # K>1 fuses K training steps into ONE
                                     # jitted dispatch (lax.scan window,
                                     # Executor.run_window) — host overhead
                                     # per step drops ~1/K (the TF
                                     # iterations_per_loop / MLPerf TPU
                                     # multi-step contract); 1 = one
                                     # dispatch a step
    "feed_ring_depth": 2,            # device-resident input pipeline: the
                                     # producer thread stages up to DEPTH
                                     # feed windows ahead (async sharded
                                     # device_put, host stacking off the
                                     # consumer's critical path — reader.
                                     # FeedRing); 0 = a one-batch look-
                                     # ahead on the calling thread, what a
                                     # program-bound loader's worker does
                                     # at any depth (same losses, bit for
                                     # bit)
    "checkpoint_async": True,        # CheckpointManager: serialize+commit
                                     # on a background thread (snapshot
                                     # stays synchronous)
    "metrics_jsonl": "",             # telemetry.py: append one JSON line
                                     # per executor step-event to this
                                     # path (off = the hot path does no
                                     # file I/O; docs/observability.md)
    "metrics_ring": 1024,            # telemetry.py: step-event ring
                                     # buffer capacity (bounded host
                                     # memory for week-long jobs)
    "trace_spans": False,            # telemetry.span(): record timed
                                     # span events (dispatch, barrier/
                                     # consensus entry, feed staging,
                                     # checkpoint phases) into the
                                     # step-event ring/JSONL for
                                     # tools/pod_trace.py merging; off
                                     # (default) = bit-exact zero-sync
                                     # hot path (docs/observability.md
                                     # "Pod-level tracing"); a span's
                                     # jax.profiler annotation needs no
                                     # flag
    "bad_step_rollback": 0,          # K>0: under FLAGS_check_nan_inf=
                                     # skip, K CONSECUTIVE bad-step
                                     # verdicts make train_from_dataset
                                     # restore the last checkpoint
                                     # (requires checkpoint_manager=)
                                     # and resume; 0 = off
    "rollback_limit": 3,             # hard cap on automatic rollbacks
                                     # per train_from_dataset call
                                     # before raising (a job stuck in a
                                     # rollback loop must fail loudly)
    "storage_retries": 3,            # object-store checkpoint backend:
                                     # transient-I/O retries per
                                     # operation (storage.py)
    "storage_retry_backoff_s": 0.05,  # base retry backoff, doubling
                                      # per attempt
    "checkpoint_commit_timeout_s": 120.0,  # collective-free pod commit
                                     # (checkpoint.py async pod saves):
                                     # how long the chief polls storage
                                     # for sibling manifests — and
                                     # workers for the chief's marker —
                                     # before abandoning the prefix
                                     # (checkpoint_commit_abandoned_
                                     # total); never a collective wait
    "checkpoint_reap_min_age_s": 600.0,  # storage debris reaper guard:
                                     # an unmarked step-* prefix younger
                                     # than this (by its chief-claim
                                     # lease, else dir mtime) is
                                     # presumed an in-flight async pod
                                     # save and is never reaped
    "serving_buckets": "",           # serving.py bucket ladder: comma/
                                     # space-separated batch sizes every
                                     # request batch is padded up to
                                     # (each bucket = ONE compiled
                                     # executable); "" = powers of two
                                     # up to ServingExecutor(max_batch=)
    "serving_max_wait_ms": 5.0,      # serving latency budget: how long
                                     # the scheduler holds an under-full
                                     # batch open for more requests
                                     # before dispatching
    "serving_max_queue": 256,        # serving backpressure: queued-not-
                                     # yet-dispatched request cap; submit
                                     # beyond it rejects (counted) rather
                                     # than growing an unbounded queue
    "watchdog_timeout_s": 0.0,       # hang detection (fluid/watchdog.py):
                                     # >0 arms the in-process watchdog —
                                     # no progress stamp for this many
                                     # seconds dumps all-thread stacks
                                     # and hard-aborts with exit code
                                     # watchdog.EXIT_HANG so the launcher
                                     # relaunches; 0 (default) = off,
                                     # bit-exact zero-overhead hot path
    "watchdog_abort": True,          # off: the watchdog still detects,
                                     # stack-dumps, records kind="hang"
                                     # and STOPS touching its heartbeat
                                     # file (launcher-side liveness takes
                                     # over) but never os._exit()s —
                                     # observe-only mode
    "watchdog_checkpoint_grace_s": 300.0,  # deadline extension while a
                                     # checkpoint save/upload is in
                                     # flight (slow object stores are
                                     # progress, not a hang)
    "watchdog_compile_grace_s": 600.0,  # deadline extension around a
                                     # fresh executable's first call
                                     # (trace + XLA compile legitimately
                                     # takes minutes on real models)
    "cost_ledger": True,             # device-cost ledger (costmodel.py):
                                     # stamp a kind="compile" record +
                                     # hlo_* gauges per fresh executable
                                     # and allow full-HLO captures via
                                     # Executor.cost_record(); 0 = fully
                                     # off, bit-exact, zero host syncs
                                     # (docs/observability.md)
    "device_profile": 0,             # N>0: capture a jax.profiler.trace
                                     # artifact covering the next N
                                     # dispatched steps, written under
                                     # FLAGS_device_profile_dir — the
                                     # measured half of the roofline
                                     # model's measured-vs-estimated
                                     # comparison; 0 = off
    "device_profile_dir": "",        # output dir for FLAGS_device_profile
                                     # traces ("" = ./device_profile)
    "roofline_peak_flops": 197e12,   # roofline model peak FLOP/s used for
                                     # estimated_step_s (default: v5e
                                     # bf16 peak, costmodel.DEVICE_PEAKS)
    "roofline_peak_bytes_per_s": 819e9,  # roofline model peak memory
                                     # bandwidth (default: v5e HBM ~819
                                     # GB/s); estimated_step_s =
                                     # max(flops/peak, bytes/bw)
}
# dropped vs the reference: FLAGS_cpu_deterministic — XLA fixes reduction
# and scatter orders at compile time, so CPU runs are already bit-stable;
# there is no nondeterministic fast path to switch off.

_cache = {}


def get_flag(name):
    if name in _cache:
        return _cache[name]
    default = _DEFS[name]
    raw = os.environ.get("FLAGS_" + name)
    if raw is None:
        val = default
    elif isinstance(default, bool):
        val = raw.lower() in ("1", "true", "yes")
    elif isinstance(default, float):
        val = float(raw)
    elif isinstance(default, int):
        try:
            val = int(raw)
        except ValueError:
            raise ValueError(
                "FLAGS_%s must be an integer, got %r" % (name, raw))
    else:
        val = raw
    _cache[name] = val
    return val


def set_flag(name, value):
    if name not in _DEFS:
        raise KeyError("Unknown flag %r" % name)
    _cache[name] = value
    if name == "prng_impl":
        apply_prng_impl()


def apply_prng_impl():
    """Install FLAGS_prng_impl as jax's default PRNG implementation.

    ``rbg`` (default) drives random ops (dropout masks, uniform/gaussian
    fills) through the TPU's hardware RngBitGenerator — the analogue of the
    reference's curand-backed dropout (operators/dropout_op.cu) and, like
    curand, stable only per (backend, compiler) rather than across them.
    Dropout masks then cost one hardware instruction per tile, where
    threefry spends vector-unit integer work per element (the speed
    difference is not measured on the current code).
    ``FLAGS_prng_impl=threefry`` restores jax's cross-backend-reproducible
    counter-based PRNG.
    """
    import jax

    impl = get_flag("prng_impl")
    impl = {"threefry": "threefry2x32"}.get(impl, impl)
    if impl not in ("rbg", "threefry2x32", "unsafe_rbg"):
        raise ValueError(
            "FLAGS_prng_impl must be rbg|threefry|unsafe_rbg, got %r"
            % (impl,))
    jax.config.update("jax_default_prng_impl", impl)


def nan_inf_policy():
    """Normalize FLAGS_check_nan_inf to one of ``off``/``raise``/``skip``.

    Back-compat: the flag was a plain bool (``set_flag(.., True)``,
    ``FLAGS_check_nan_inf=1``), which maps to ``raise`` — semantics
    identical to the old hard checkify assert."""
    v = get_flag("check_nan_inf")
    if isinstance(v, str):
        v = v.strip().lower()
    if v in (False, None, "", "0", "false", "no", "off"):
        return "off"
    if v in (True, "1", "true", "yes", "on", "raise"):
        return "raise"
    if v == "skip":
        return "skip"
    raise ValueError(
        "FLAGS_check_nan_inf must be off|raise|skip (or a bool), got %r"
        % (v,))


def steps_per_run_value(override=None):
    """Validated window size K of the multi-step fused training loop.

    ``override`` (an explicit ``steps_per_run=`` argument) wins over
    ``FLAGS_steps_per_run``.  K must be a positive integer — a fused
    window is a ``lax.scan`` of statically-known length, so fractional or
    non-positive values can never mean anything.  Raises ValueError
    naming the flag."""
    import numpy as np

    v = get_flag("steps_per_run") if override is None else override
    if isinstance(v, bool) or not isinstance(v, (int, np.integer)):
        raise ValueError(
            "FLAGS_steps_per_run (steps_per_run=) must be a positive "
            "int, got %r of type %s" % (v, type(v).__name__))
    v = int(v)
    if v < 1:
        raise ValueError(
            "FLAGS_steps_per_run (steps_per_run=) must be a positive "
            "int, got %d" % v)
    return v


def trace_time_key():
    """Tuple of every flag that affects tracing/lowering — part of each
    compiled-executable cache key so toggling a flag between runs
    recompiles instead of silently reusing a stale executable."""
    return (get_flag("matmul_precision"), nan_inf_policy(),
            get_flag("prng_impl"))


def matmul_precision():
    """Returns a jax.lax.Precision or None (backend default)."""
    from jax import lax
    p = get_flag("matmul_precision")
    return {"default": None, "high": lax.Precision.HIGH,
            "float32": lax.Precision.HIGHEST,
            "highest": lax.Precision.HIGHEST}.get(p)
