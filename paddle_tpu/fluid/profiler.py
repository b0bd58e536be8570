"""Profiler: host spans + device trace (reference: platform/profiler.{h,cc},
python/paddle/fluid/profiler.py, tools/timeline.py chrome-trace export).

Host-side RAII spans mirror ``RecordEvent`` (profiler.h:81); device-side
tracing delegates to the XLA/JAX profiler (the CUPTI analogue,
platform/device_tracer.h).  ``stop_profiler`` can emit a Chrome trace JSON
like tools/timeline.py.
"""

import contextlib
import json
import os
import re
import threading
import time

from . import telemetry

_lock = threading.Lock()        # the bad-step verdict pool's
_jax_trace_dir = [None]
# a profiler session: RecordEvent spans are kept (in the telemetry ring)
# while ``on``; stop_profiler exports those that began after ``since``
_session = {"on": False, "since": 0}


class RecordEvent:
    """RAII span (platform/profiler.h:81): ``telemetry.span("user",
    name=...)`` — in the jax.profiler trace always, in the ring (and so
    in ``stop_profiler``'s Chrome trace and table) during a
    ``start_profiler`` session."""

    def __init__(self, name):
        self.name = name
        self._span = None

    def __enter__(self):
        self._span = telemetry.span("user", name=self.name)
        self._span.record = _session["on"]
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        return self._span.__exit__(*exc)


record_event = RecordEvent


def _user_spans():
    """This session's RecordEvent spans, oldest first."""
    return [ev for ev in telemetry.step_events()
            if ev.get("kind") == "span" and ev.get("span") == "user"
            and ev["ts_ns"] >= _session["since"]]


def start_profiler(state="All", trace_dir=None):
    _session.update(on=True, since=time.perf_counter_ns())
    if trace_dir is not None:
        import jax
        jax.profiler.start_trace(trace_dir)
        _jax_trace_dir[0] = trace_dir


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    _session["on"] = False
    if _jax_trace_dir[0] is not None:
        import jax
        jax.profiler.stop_trace()
        _jax_trace_dir[0] = None
    # chrome trace export (tools/timeline.py analogue)
    trace = {"traceEvents": []}
    user = _user_spans()
    for ev in user:
        trace["traceEvents"].append({
            "name": ev["name"], "ph": "X", "ts": ev["ts_ns"] / 1000.0,
            "dur": ev["dur_ns"] / 1000.0, "pid": os.getpid(),
            "tid": ev["tid"], "cat": "host"})
    # executor step-events interleave on their own track: same
    # perf_counter_ns clock as the host spans, so "why was step N slow"
    # lines up a dispatch against the host work around it
    for ev in telemetry.step_events():
        ts = ev.get("ts_ns")
        if ts is None or ev.get("span") == "user":
            continue
        if ev.get("kind") == "span":     # timed region (FLAGS_trace_spans)
            name = "span:%s" % ev.get("span", "?")
        elif ev.get("kind"):             # preemption/rollback lifecycle
            name = str(ev["kind"])
        elif ev.get("window"):
            name = "window[k=%d]" % ev.get("k", 1)
        else:
            name = "step"
        trace["traceEvents"].append({
            "name": name, "ph": "X", "ts": ts / 1000.0,
            "dur": ev.get("dur_ns", 0) / 1000.0, "pid": os.getpid(),
            "tid": "step-events", "cat": "step",
            "args": {k: v for k, v in ev.items()
                     if k not in ("ts_ns", "dur_ns")}})
    if profile_path:
        os.makedirs(os.path.dirname(profile_path) or ".", exist_ok=True)
        with open(profile_path + ".chrome_trace.json", "w") as f:
            # step-event args may carry numpy scalars (producers pass
            # arbitrary fields) — degrade like the JSONL exporter rather
            # than losing the whole trace at session end
            json.dump(trace, f, default=telemetry._json_default)
    # aggregated table, like the reference's PrintProfiler
    agg = {}
    for ev in user:
        tot, cnt = agg.get(ev["name"], (0.0, 0))
        agg[ev["name"]] = (tot + ev["dur_ns"] / 1e6, cnt + 1)
    if agg:
        rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
        print("%-40s %10s %8s" % ("Event", "total_ms", "calls"))
        for name, (tot, cnt) in rows[:50]:
            print("%-40s %10.3f %8d" % (name[:40], tot, cnt))
    return trace


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile"):
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*args, **kwargs):  # name kept for API parity
    yield


# -- FLAGS_device_profile: N-step jax.profiler trace capture -----------------
# The measured half of the device-cost ledger's roofline comparison
# (docs/observability.md "Device-cost ledger"): FLAGS_device_profile=N
# brackets the next N dispatched steps in one jax.profiler.start_trace /
# stop_trace window, written under FLAGS_device_profile_dir, so the
# measured-vs-estimated step-time comparison lights up the moment real
# hardware is attached.  The executor calls the begin/end hooks at each
# dispatch boundary; with the flag at 0 each hook is one cached-int read.

_device_profile = {"remaining": None, "active": False, "dir": None}


def device_profile_begin():
    """Start the FLAGS_device_profile trace before the first profiled
    dispatch.  No-op (one dict read) when the flag is 0 or the budget is
    spent.  A trace that cannot start raises: a requested capture that
    silently does not exist would be read as "nothing ran"."""
    st = _device_profile
    rem = st["remaining"]
    if rem is None:
        from . import flags
        n = int(flags.get_flag("device_profile") or 0)
        st["remaining"] = rem = max(0, n)
    if rem <= 0 or st["active"]:
        return
    from . import flags
    out = flags.get_flag("device_profile_dir") or \
        os.path.join(os.getcwd(), "device_profile")
    import jax
    os.makedirs(out, exist_ok=True)
    jax.profiler.start_trace(out)
    st["active"] = True
    st["dir"] = out


def device_profile_end(k=1):
    """Account ``k`` inner steps against the FLAGS_device_profile budget
    and stop the trace once it is spent (a K-window counts as K)."""
    st = _device_profile
    if not st["active"]:
        return
    st["remaining"] -= max(1, int(k))
    if st["remaining"] <= 0:
        st["remaining"] = 0
        st["active"] = False
        import jax
        jax.profiler.stop_trace()


def device_profile_reset():
    """Forget cached FLAGS_device_profile state (tests toggling the flag
    via set_flag); stops a live trace first."""
    st = _device_profile
    if st["active"]:
        device_profile_end(st["remaining"] or 1)
    st.update(remaining=None, active=False, dir=None)


def device_profile_dir():
    """Directory the current/last FLAGS_device_profile trace wrote to
    (None if no capture started)."""
    return _device_profile["dir"]


# -- names of the compiled step, for readers of a device trace ---------------
# A device trace's ``XLA Ops`` events carry an instruction's HLO text and no
# metadata; the scopes the lowering puts around every Fluid op (``role_fwd``
# / ``role_bwd`` / ``role_opt`` outside ``fluid_<op type>``, lowering.py;
# the Pallas kernels' names, ops/pallas_ops.py) live in the compiled HLO's
# ``metadata={op_name=...}``.  The executor notes the executable its
# introspection (``compiled_hlo`` / ``_cost`` / ``_memory``) handed out
# last; nothing is read from it until ``step_scopes()`` is asked.

_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?(\S+) = .*metadata=\{[^}]*op_name="([^"]*)"')
_step_executable = {"executable": None, "scopes": None}


def note_step_executable(executable):
    if executable is not _step_executable["executable"]:
        _step_executable.update(executable=executable, scopes=None)


def step_scopes():
    """``{instruction name: op_name}`` of the executable the executor's
    introspection handed out last, parsed from its optimized HLO text on
    first asking; ``{}`` before any introspection call."""
    st = _step_executable
    if st["scopes"] is None and st["executable"] is not None:
        scopes = {}
        for line in st["executable"].as_text().splitlines():
            m = _HLO_OP_NAME.match(line)
            if m:
                scopes[m.group(1)] = m.group(2)
        st["scopes"] = scopes
    return st["scopes"] or {}


# -- host-sync accounting ----------------------------------------------------
# Every point where the executor's step loop forces a host<->device sync
# (a numpy fetch, a print_period loss pull, the end-of-pass drain) reports
# here.  Tests assert the async dispatch contract against this counter
# (train_from_dataset must not sync between batches); bench.py --hot-path
# reads it to prove the cached-hit run() path stays sync-free.
#
# Since the telemetry PR the storage is the metrics registry
# (telemetry.py); these functions are thin views kept for API stability.

_m_host_syncs = telemetry.counter(
    "host_syncs_total", "host<->device sync points, labeled by tag")


def record_host_sync(tag="fetch"):
    _m_host_syncs.inc(tag=tag)


def host_sync_count(tag=None):
    if tag is None:
        return int(_m_host_syncs.value())
    return int(_m_host_syncs.value(tag=tag))


def reset_host_sync_count():
    _m_host_syncs.reset()


# -- multi-step window accounting (Executor.run_window) ----------------------
# One fused K-step dispatch counts as ONE window of K inner steps: host
# overhead, print_period pulls, and benchmark syncs are per-WINDOW, while
# step-keyed accounting (steps_since_checkpoint, scope.step_counter)
# advances by K.  bench.py --hot-path --steps-per-run reads these to
# prove the ~1/K host-overhead scaling.

_m_windows = telemetry.counter(
    "window_dispatches_total", "fused multi-step window dispatches")
_m_inner_steps = telemetry.counter(
    "window_inner_steps_total", "inner steps run by fused windows")
_m_last_k = telemetry.gauge(
    "window_last_k", "K of the most recent fused window")


def record_window(k):
    _m_windows.inc()
    _m_inner_steps.inc(int(k))
    _m_last_k.set(int(k))


def window_stats():
    """{'windows': fused dispatches, 'inner_steps': total steps they ran,
    'last_k': K of the most recent window}."""
    return {"windows": int(_m_windows.value()),
            "inner_steps": int(_m_inner_steps.value()),
            "last_k": int(_m_last_k.value() or 0)}


def reset_window_stats():
    _m_windows.reset()
    _m_inner_steps.reset()
    _m_last_k.reset()


# -- checkpoint accounting (checkpoint.py CheckpointManager) ----------------
# Save duration / bytes / last-checkpointed-step counters: ops dashboards
# read these to alarm on "steps since last durable checkpoint" — the
# recovery-point-objective metric at pod scale.

_m_ckpt_saves = telemetry.counter(
    "checkpoint_saves_total", "committed checkpoint saves")
_m_ckpt_seconds = telemetry.counter(
    "checkpoint_save_seconds_total", "serialize+fsync+commit seconds")
_m_ckpt_bytes = telemetry.counter(
    "checkpoint_bytes_total", "serialized checkpoint bytes written")
_m_ckpt_last_s = telemetry.gauge(
    "checkpoint_last_save_seconds", "duration of the most recent save")
_m_ckpt_last_bytes = telemetry.gauge(
    "checkpoint_last_bytes", "bytes of the most recent save")
_m_ckpt_last_step = telemetry.gauge(
    "checkpoint_last_step", "step of the most recent durable save (RPO)")


def record_checkpoint_save(seconds, nbytes, step):
    _m_ckpt_saves.inc()
    _m_ckpt_seconds.inc(seconds)
    _m_ckpt_bytes.inc(nbytes)
    _m_ckpt_last_s.set(seconds)
    _m_ckpt_last_bytes.set(nbytes)
    _m_ckpt_last_step.set(step)


def checkpoint_stats():
    last_s = _m_ckpt_last_s.value()
    last_b = _m_ckpt_last_bytes.value()
    return {"saves": int(_m_ckpt_saves.value()),
            "total_save_s": float(_m_ckpt_seconds.value()),
            "last_save_s": float(last_s) if last_s is not None else 0.0,
            "total_bytes": int(_m_ckpt_bytes.value()),
            "last_bytes": int(last_b) if last_b is not None else 0,
            "last_step": _m_ckpt_last_step.value()}


def steps_since_checkpoint(current_step):
    """Steps of work at risk if the job died now (None: never saved)."""
    last = _m_ckpt_last_step.value()
    return None if last is None else int(current_step) - int(last)


def reset_checkpoint_stats():
    for m in (_m_ckpt_saves, _m_ckpt_seconds, _m_ckpt_bytes,
              _m_ckpt_last_s, _m_ckpt_last_bytes, _m_ckpt_last_step):
        m.reset()


# -- bad-step accounting (FLAGS_check_nan_inf=skip policy) ------------------
# The executor's skip-policy runner hands over the step's device-side
# finiteness verdict WITHOUT materializing it — forcing it would put a
# host sync on the training hot path.  Verdicts pool here and are counted
# lazily when bad_step_count() is read (by then the arrays are long
# ready); the pool self-drains past a bound so it cannot grow unbounded.
#
# The COUNT lives in the metrics registry; the PENDING pool of
# device-resident verdicts stays here — this is the lazy/device-resident
# pattern the registry itself follows: only host scalars ever reach a
# metric, and only when something reads them.

_m_bad_steps = telemetry.counter(
    "bad_steps_total", "non-finite steps skipped (check_nan_inf=skip)")
# streak: TRAILING consecutive bad steps across drains — the rollback
# trigger (FLAGS_bad_step_rollback reads it per boundary via
# bad_step_streak()).  Verdict ordering is single-consumer: the one
# training loop both records and drains, so append order IS step order.
_bad_steps = {"pending": [], "streak": 0}


def record_bad_step(ok):
    """``ok``: (possibly device-resident) bool verdict(s) — a scalar for
    a single step, or a [K] vector of per-inner-step verdicts from a
    fused steps_per_run window.  True means that step was finite and its
    state was committed."""
    with _lock:
        _bad_steps["pending"].append(ok)
        drain = (_bad_steps["pending"]
                 if len(_bad_steps["pending"]) >= 1024 else None)
        if drain is not None:
            _bad_steps["pending"] = []
    if drain is not None:
        _apply_verdicts(drain)


def _apply_verdicts(verdicts):
    """Materialize drained verdicts (np.asarray — the caller accepts the
    device sync) and fold them into the total counter and the trailing
    consecutive-bad streak, in step order."""
    import numpy as np
    bad = 0
    with _lock:
        streak = _bad_steps["streak"]
    for x in verdicts:
        for ok in np.asarray(x).ravel():
            if bool(ok):
                streak = 0
            else:
                streak += 1
                bad += 1
    with _lock:
        _bad_steps["streak"] = streak
    if bad:
        _m_bad_steps.inc(bad)


def _drain_pending():
    with _lock:
        drain = _bad_steps["pending"]
        _bad_steps["pending"] = []
    if drain:
        _apply_verdicts(drain)


def pending_bad_step_verdicts():
    """Count of verdicts pooled but not yet materialized (telemetry
    step-events report this instead of forcing the device arrays)."""
    with _lock:
        return len(_bad_steps["pending"])


def bad_step_count():
    _drain_pending()
    return int(_m_bad_steps.value())


def bad_step_streak():
    """Trailing count of CONSECUTIVE bad steps (resets to 0 at every
    finite step).  Drains the pending verdict pool first, so reading it
    forces the device arrays — one host sync the rollback policy
    (FLAGS_bad_step_rollback) accepts per boundary check."""
    _drain_pending()
    with _lock:
        return _bad_steps["streak"]


def reset_bad_step_streak():
    """Restart the consecutive-bad run (a rollback restored known-good
    state, so the streak that triggered it is history)."""
    with _lock:
        _bad_steps["streak"] = 0


def reset_bad_step_count():
    _m_bad_steps.reset()
    with _lock:
        _bad_steps["pending"] = []
        _bad_steps["streak"] = 0


# -- FLAGS_benchmark step timing (reference executor FLAGS_benchmark) -------
# Window-aware: a fused K-step dispatch records ONE wall-time entry that
# covers K inner steps, so the per-step mean attributes window_s / K to
# each inner step — benchmark_stats()["mean_s"] stays comparable across
# steps_per_run values (the ROADMAP PR-4 follow-on).

_m_bench_steps = telemetry.counter(
    "benchmark_inner_steps_total", "inner steps timed under FLAGS_benchmark")
_m_bench_seconds = telemetry.counter(
    "benchmark_seconds_total", "synced wall seconds under FLAGS_benchmark")
_m_bench_last_k = telemetry.gauge(
    "benchmark_last_k", "steps_per_run of the most recent timed dispatch")


def record_benchmark_step(seconds, steps=1):
    """``seconds`` of synced wall time covering ``steps`` inner steps
    (1 for a plain run(), K for a fused run_window dispatch)."""
    _m_bench_steps.inc(int(steps))
    _m_bench_seconds.inc(seconds)
    _m_bench_last_k.set(int(steps))


def benchmark_stats():
    """{'steps': inner steps timed, 'total_s': T, 'mean_s': T/steps,
    'last_k': steps_per_run of the latest dispatch} for FLAGS_benchmark
    runs.  mean_s is PER INNER STEP, so K=1 and K=16 runs of the same
    program are directly comparable."""
    n = int(_m_bench_steps.value())
    tot = float(_m_bench_seconds.value())
    return {"steps": n, "total_s": tot,
            "mean_s": tot / n if n else 0.0,
            "last_k": int(_m_bench_last_k.value() or 0)}


def reset_benchmark_stats():
    _m_bench_steps.reset()
    _m_bench_seconds.reset()
    _m_bench_last_k.reset()


def reset_profiler():
    """Drop collected span data (reference profiler.py reset_profiler)."""
    _session["since"] = time.perf_counter_ns()
    reset_benchmark_stats()


# -- FLAGS_pe_profile_fname: whole-process host profile --------------------
# Reference: gperftools ProfilerStart around ParallelExecutor
# (parallel_executor.cc:38).  Here the host-side equivalent is cProfile
# over the whole process, dumped at exit to the named file (readable with
# pstats / snakeviz); device-side profiling is the XLA trace
# (start_profiler).

_pe_profiler = None


def maybe_start_pe_profile():
    """Idempotently start the process profiler when
    FLAGS_pe_profile_fname is set; called from Executor.__init__ (the
    reference hooks ParallelExecutor construction the same way)."""
    global _pe_profiler
    import os
    fname = os.environ.get("FLAGS_pe_profile_fname")
    if not fname or _pe_profiler is not None:
        return
    import atexit
    import cProfile
    _pe_profiler = cProfile.Profile()
    _pe_profiler.enable()

    def _dump():
        _pe_profiler.disable()
        _pe_profiler.dump_stats(fname)
    atexit.register(_dump)
