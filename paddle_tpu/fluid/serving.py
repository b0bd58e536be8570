"""Continuous-batching serving executor over AOT-warmable shape buckets.

The training side got five perf PRs; this module is the inference
serving story the ROADMAP names, built entirely on substrate that
already exists:

- **Shape buckets** — XLA's fixed-shape contract means every novel feed
  shape is a multi-second recompile ON THE LATENCY PATH ("Fine-Tuning
  and Serving Gemma on Cloud TPU", PAPERS.md, makes the economic case).
  So variable request batch sizes are padded UP a configurable ladder
  (``FLAGS_serving_buckets``; default powers of two up to
  ``max_batch``), each bucket compiles exactly once (the PR 2 dispatch-
  plan cache makes the steady-state dispatch one dict lookup), all
  buckets are eagerly compiled by :meth:`ServingExecutor.warmup`, and
  on a TPU the compiled artifacts persist across processes in JAX's
  compilation cache (``executor.maybe_enable_compile_cache``).  ``serving_recompiles_total`` pins the
  contract: after warmup it must stay 0 forever.
- **Continuous batching** — a scheduler thread (the FeedRing
  producer/consumer pattern from reader.py, generalized to a request
  queue) packs queued requests into the smallest bucket that fits,
  holding an under-full batch open for at most ``max_wait_ms`` (the
  latency budget).  Dispatch is asynchronous (``return_numpy=False``):
  the scheduler starts packing batch N+1 the moment batch N is enqueued
  on the device, while a completion thread materializes batch N's
  outputs and slices per-request responses out of the padded rows — no
  head-of-line blocking behind a full "static" batch, and padding rows
  never leak into real rows (property-tested across the ladder).
- **Production edges** — SIGTERM (fluid.preemption) stops admission and
  drains: every accepted request is answered, metrics are flushed, the
  process exits 0.  Backpressure rejects (counted) beyond
  ``max_queue`` queued requests.  Per-request latency splits queue-wait
  from compute in two histograms, with ``serving_queue_depth`` and
  ``serving_batch_occupancy_frac`` gauges — all through the one
  telemetry registry, scrapeable via tools/metrics_server.py.

Usage::

    sv = fluid.serving.ServingExecutor(
        infer_program, feed_specs={"img": ((3, 224, 224), "float32")},
        fetch_list=[prob], scope=scope, max_batch=32)
    sv.warmup()                       # compile the whole ladder up front
    fut = sv.submit({"img": batch})   # -> concurrent.futures.Future
    probs, = fut.result()
    sv.close()                        # drain + join threads

or from a saved model (positional requests follow the saved manifest's
feed order — io.py's feed-order contract)::

    sv = fluid.serving.ServingExecutor.from_inference_model("model_dir")
    out, = sv.infer([img_batch])

See docs/serving.md for bucket-ladder tuning, the latency budget, and
the scrape endpoint; ``bench.py --serving`` measures the win over
one-request-per-dispatch on any host.
"""

import concurrent.futures
import itertools
import queue
import threading
import time

import numpy as np

from . import flags
from . import preemption
from . import telemetry
from .aot import normalize_feed_specs
from .reader import QUEUE_DRAINED, stop_aware_get

__all__ = ["ServingExecutor", "ServingError", "ServingRejectedError",
           "ServingClosedError", "bucket_ladder"]

# -- telemetry (docs/observability.md "Serving") ----------------------------
_m_requests = telemetry.counter(
    "serving_requests_total", "requests accepted into the serving queue")
_m_responses = telemetry.counter(
    "serving_responses_total", "requests answered (future completed)")
_m_rejects = telemetry.counter(
    "serving_rejects_total",
    "requests rejected before admission, by reason "
    "(queue_full | too_large | closed)")
_m_recompiles = telemetry.counter(
    "serving_recompiles_total",
    "executables compiled by a QUEUED serving dispatch — 0 forever "
    "after warmup() is the shape-discipline contract")
_m_batches = telemetry.counter(
    "serving_batches_total", "padded batches dispatched, by bucket")
_m_padded_rows = telemetry.counter(
    "serving_padded_rows_total",
    "padding rows dispatched (bucket minus real rows)")
_m_errors = telemetry.counter(
    "serving_errors_total", "batches whose dispatch/completion raised "
    "(every affected request future carries the exception)")
_m_cancelled = telemetry.counter(
    "serving_cancelled_total",
    "accepted requests dropped at dispatch because the client "
    "cancelled the future while it was queued")
_m_depth = telemetry.gauge(
    "serving_queue_depth", "requests accepted but not yet dispatched")
_m_occupancy = telemetry.gauge(
    "serving_batch_occupancy_frac",
    "real rows / bucket rows of the most recent dispatch (1.0 = no "
    "padding wasted)")
# request latency split: time spent WAITING for a batch to form vs time
# from dispatch to materialized outputs — the two knobs they tune
# (max_wait_ms vs bucket ladder) are told apart by which histogram moved
_LAT_BUCKETS = (1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5, 1.0, 10.0)
_m_queue_wait = telemetry.histogram(
    "serving_queue_wait_seconds",
    "submit-to-dispatch wait per request", buckets=_LAT_BUCKETS)
_m_compute = telemetry.histogram(
    "serving_compute_seconds",
    "dispatch-to-materialized-output wall per batch", buckets=_LAT_BUCKETS)


# per-process executor ids: serving step-events carry sid so report
# tooling can aggregate per-INSTANCE cumulative samples (rejects_total)
# correctly when several executors share one JSONL stream
_sid_counter = itertools.count(1)


class ServingError(RuntimeError):
    """Serving-layer failure (bad request spec, non-batched fetch, dead
    scheduler)."""


class ServingRejectedError(ServingError):
    """Request refused before admission — backpressure (queue_full), an
    over-sized batch (too_large), or a closed/draining executor.  The
    request was NOT accepted: no future exists and nothing will answer
    it, so the client should shed or retry elsewhere."""


class ServingClosedError(ServingRejectedError):
    """The executor is draining (close() or a preemption stop) — new
    admissions are refused while accepted requests are answered."""


def _resolve(future, exc, result=None):
    """Resolve a client future, tolerating a concurrent client-side
    ``Future.cancel()``: ``set_result``/``set_exception`` on a cancelled
    future raises ``InvalidStateError``, and an unhandled one would kill
    the serving thread and park every later ``fut.result()`` forever.
    Returns True when the future actually carried the answer."""
    try:
        if exc is not None:
            future.set_exception(exc)
        else:
            future.set_result(result)
        return True
    except concurrent.futures.InvalidStateError:
        return False


def bucket_ladder(max_batch, buckets=None):
    """Resolve the bucket ladder: explicit ``buckets`` >
    ``FLAGS_serving_buckets`` > powers of two up to ``max_batch``
    (inclusive — a non-power-of-two cap becomes the top bucket).
    Returns a sorted, de-duplicated list of positive ints."""
    if buckets is None:
        raw = flags.get_flag("serving_buckets")
        if raw:
            buckets = [int(t) for t in
                       str(raw).replace(",", " ").split()]
    if buckets is not None:
        ladder = sorted(set(int(b) for b in buckets))
        if not ladder or ladder[0] < 1:
            raise ValueError(
                "serving buckets must be positive batch sizes, got %r"
                % (buckets,))
        return ladder
    max_batch = int(max_batch)
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1, got %d" % max_batch)
    ladder, b = [], 1
    while b < max_batch:
        ladder.append(b)
        b *= 2
    ladder.append(max_batch)
    return sorted(set(ladder))


class _Request:
    __slots__ = ("feeds", "rows", "future", "t_submit", "t_dispatch")

    def __init__(self, feeds, rows, future):
        self.feeds = feeds
        self.rows = rows
        self.future = future
        self.t_submit = time.perf_counter()
        self.t_dispatch = None


class _Dispatched:
    """One in-flight padded batch: the scheduler hands it to the
    completion thread right after the (async) dispatch is enqueued."""

    __slots__ = ("batch", "rows", "bucket", "fetches", "t0_ns", "compiled")

    def __init__(self, batch, rows, bucket, fetches, t0_ns, compiled):
        self.batch = batch
        self.rows = rows
        self.bucket = bucket
        self.fetches = fetches
        self.t0_ns = t0_ns       # same clock as every other ring record
        self.compiled = compiled


class ServingExecutor:
    """Serve an inference ``Program`` through a bucketed-shape,
    continuously-batched request loop.

    feed_specs: ``{name: (per-SAMPLE shape, dtype)}`` (no batch dim) or
        example per-sample ndarrays; insertion order is the positional-
        request order (``submit([a, b])``).  Derived from the program's
        data vars by :meth:`from_inference_model`.
    fetch_list: output Variables/names; every fetch must carry the batch
        dim first (validated at warmup — per-request slicing needs it).
    scope: parameter scope (default: the global scope; the startup
        program must have run there).
    max_batch / buckets / max_wait_ms / max_queue: see
        :func:`bucket_ladder`, ``FLAGS_serving_max_wait_ms``,
        ``FLAGS_serving_max_queue``.

    Threads (both started lazily on the first ``submit`` so ``warmup()``
    keeps the executor single-threaded): ``serving-scheduler`` packs the
    queue into padded buckets and dispatches; ``serving-completion``
    materializes outputs and fulfills request futures.  Both poll the
    preemption stop flag on every idle wait (reader.stop_aware_get), so
    shutdown can never park on an empty queue.
    """

    def __init__(self, program, feed_specs=None, fetch_list=None,
                 scope=None, place=None, max_batch=64, buckets=None,
                 max_wait_ms=None, max_queue=None, executor=None):
        from .executor import Executor, global_scope

        if not feed_specs:
            raise ServingError(
                "ServingExecutor needs feed_specs ({name: (per-sample "
                "shape, dtype)}) — a program with no feeds has no "
                "request rows to batch")
        self._program = program
        self._specs = {n: (tuple(s), np.dtype(d)) for n, (s, d) in
                       normalize_feed_specs(feed_specs).items()}
        self.feed_names = list(self._specs)
        if fetch_list is None or not list(fetch_list):
            raise ServingError("ServingExecutor needs a fetch_list")
        self._fetch_list = list(fetch_list)
        self._scope = scope if scope is not None else global_scope()
        self._exe = executor if executor is not None else \
            Executor(place)
        self.buckets = bucket_ladder(max_batch, buckets)
        self._max_wait_s = (flags.get_flag("serving_max_wait_ms")
                            if max_wait_ms is None else
                            float(max_wait_ms)) / 1e3
        self._max_queue = int(flags.get_flag("serving_max_queue")
                              if max_queue is None else max_queue)
        self._queue = queue.Queue()
        self._done = queue.Queue()
        self._lock = threading.Lock()
        self._pending = 0            # accepted, not yet dispatched
        self._closed = threading.Event()
        self._admission_closed = False   # set by the scheduler's final
        #                                  sweep, under _lock — closes the
        #                                  submit-vs-shutdown race so an
        #                                  accepted request is ALWAYS
        #                                  answered
        self._scheduler_thread = None
        self._completion_thread = None
        self._failure = None
        self._warmed = False
        self._sid = next(_sid_counter)
        # per-instance stats (the global counters aggregate across
        # executors; tests and bench isolate one instance through these)
        self._n_requests = 0
        self._n_responses = 0
        self._n_rejects = 0
        self._n_cancelled = 0
        self._n_recompiles = 0
        self._n_batches = 0
        self._n_rows = 0
        self._n_padded = 0
        self._occ_sum = 0.0

    # -- construction helpers ----------------------------------------------
    @classmethod
    def from_inference_model(cls, dirname, place=None, model_filename=None,
                             params_filename=None, **kwargs):
        """Build a ServingExecutor from a ``save_inference_model``
        artifact: the program and parameters load into a private scope,
        feed specs derive from the program's data vars (leading dim must
        be the batch dim), and ``feed_names`` follows the saved
        manifest's feed order — the positional-request contract."""
        from . import io as fluid_io
        from .executor import Executor, Scope, scope_guard

        exe = Executor(place)
        scope = Scope()
        with scope_guard(scope):
            program, feed_names, fetch_vars = \
                fluid_io.load_inference_model(
                    dirname, exe, model_filename=model_filename,
                    params_filename=params_filename)
        block = program.global_block()
        specs = {}
        for n in feed_names:
            v = block.var(n)
            shape = tuple(v.shape or ())
            if not shape or shape[0] not in (-1, None):
                raise ServingError(
                    "feed %r has shape %s — serving needs a variable "
                    "leading batch dim (shape[0] == -1); pass "
                    "feed_specs= explicitly to override" % (n, shape))
            sample = tuple(int(d) for d in shape[1:])
            if any(d < 0 for d in sample):
                raise ServingError(
                    "feed %r has non-leading dynamic dims %s — the "
                    "bucket ladder only pads the batch dim; pass "
                    "feed_specs= with concrete trailing dims"
                    % (n, shape))
            specs[n] = (sample, v.dtype)
        return cls(program, feed_specs=specs, fetch_list=fetch_vars,
                   scope=scope, executor=exe, **kwargs)

    # -- admission ---------------------------------------------------------
    def _draining(self):
        return self._closed.is_set() or preemption.stop_requested()

    def submit(self, feed):
        """Admit one request; returns a ``concurrent.futures.Future``
        resolving to the list of per-fetch numpy arrays (this request's
        rows only — padding and co-batched requests sliced away).

        ``feed`` is a dict ``{name: [rows, *sample_shape] array}`` or a
        positional sequence following ``self.feed_names`` (the saved
        manifest order for loaded models).  All feeds must agree on the
        leading row count; 1 <= rows <= the largest bucket.  Raises
        :class:`ServingRejectedError` on backpressure / over-size /
        draining — the request was not accepted.

        The future supports client-side ``cancel()`` while the request
        is still queued: a cancelled request is dropped at dispatch
        time (counted in ``serving_cancelled_total``) instead of
        computed; once dispatch claims it, ``cancel()`` returns False
        and the result arrives normally."""
        if self._failure is not None:
            raise ServingError(
                "serving executor failed: %s" % (self._failure,)) \
                from self._failure
        feeds, rows = self._validate(feed)
        if rows > self.buckets[-1]:
            self._reject("too_large")
            raise ServingRejectedError(
                "request rows %d exceed the largest bucket %d — raise "
                "max_batch/FLAGS_serving_buckets or split the request"
                % (rows, self.buckets[-1]))
        fut = concurrent.futures.Future()
        req = _Request(feeds, rows, fut)
        with self._lock:
            if self._admission_closed or self._draining():
                self._reject("closed")
                raise ServingClosedError(
                    "serving executor is draining (%s) — admission is "
                    "closed" % ("close()" if self._closed.is_set()
                                else "preemption stop"))
            if self._pending >= self._max_queue:
                self._reject("queue_full")
                raise ServingRejectedError(
                    "serving queue full (%d queued >= max_queue=%d) — "
                    "backpressure; shed or retry"
                    % (self._pending, self._max_queue))
            self._pending += 1
            self._n_requests += 1
            # put under the lock: the scheduler's final sweep takes the
            # same lock before closing admission, so a request that
            # passed the checks above is visible to the sweep
            self._queue.put(req)
        _m_requests.inc()
        _m_depth.set(self._pending)
        self._ensure_threads()
        return fut

    def infer(self, feed, timeout=None):
        """Synchronous convenience: ``submit(feed).result(timeout)``."""
        return self.submit(feed).result(timeout)

    def _reject(self, reason):
        self._n_rejects += 1
        _m_rejects.inc(reason=reason)

    def _validate(self, feed):
        if not isinstance(feed, dict):
            vals = list(feed)
            if len(vals) != len(self.feed_names):
                raise ServingError(
                    "positional request has %d arrays, program feeds "
                    "are %s (the saved manifest order)"
                    % (len(vals), self.feed_names))
            feed = dict(zip(self.feed_names, vals))
        feeds, rows = {}, None
        for n, (sample, dtype) in self._specs.items():
            if n not in feed:
                raise ServingError(
                    "request is missing feed %r (program feeds: %s)"
                    % (n, self.feed_names))
            arr = np.asarray(feed[n])
            if arr.dtype != dtype:
                arr = arr.astype(dtype)
            if arr.ndim != len(sample) + 1 or \
                    tuple(arr.shape[1:]) != sample:
                raise ServingError(
                    "feed %r must be [rows%s] of %s, got shape %s"
                    % (n, "".join(", %d" % d for d in sample), dtype,
                       arr.shape))
            if rows is None:
                rows = int(arr.shape[0])
            elif int(arr.shape[0]) != rows:
                raise ServingError(
                    "request feeds disagree on the row count: %r has "
                    "%d rows, %r has %d" % (self.feed_names[0], rows,
                                            n, arr.shape[0]))
            feeds[n] = arr
        if not rows:
            raise ServingError("request must carry at least one row")
        return feeds, rows

    # -- warmup ------------------------------------------------------------
    def warmup(self, ledger=False):
        """Eagerly compile every bucket (zero-filled feeds, outputs
        discarded) so steady-state traffic never pays a compile on the
        latency path.  On a TPU, later processes warm from JAX's persistent
        compilation cache instead of recompiling.
        Returns ``{bucket: seconds}`` (first-process entries ARE the
        XLA compile times).  Call before serving traffic — warmup
        dispatches on the caller's thread and does not count toward
        ``serving_recompiles_total``.

        ``ledger=True`` additionally captures a full device-cost ledger
        record per bucket (``Executor.cost_record``, tagged
        ``serving:b<bucket>``) so the per-bucket FLOPs/memory ladder is
        in the JSONL/gauges.  Opt-in: the capture pays one extra
        ahead-of-time compile per bucket, which warmup alone never does.
        No-op when ``FLAGS_cost_ledger=0``."""
        if self._scheduler_thread is not None:
            raise ServingError(
                "warmup() must run before serving traffic — the "
                "scheduler thread is already dispatching")
        times = {}
        for b in self.buckets:
            feeds = {n: np.zeros((b,) + sample, dtype)
                     for n, (sample, dtype) in self._specs.items()}
            t0 = time.perf_counter()
            fetches = self._exe.run(self._program, feed=feeds,
                                    fetch_list=self._fetch_list,
                                    scope=self._scope,
                                    return_numpy=False)
            self._check_fetch_dims(fetches, b)
            times[b] = time.perf_counter() - t0
            if ledger:
                self._exe.cost_record(
                    self._program, feed=feeds,
                    fetch_list=self._fetch_list, scope=self._scope,
                    tag="serving:b%d" % b)
        self._warmed = True
        return times

    def _check_fetch_dims(self, fetches, bucket):
        for i, f in enumerate(fetches):
            shape = tuple(np.shape(f))
            if not shape or shape[0] != bucket:
                name = self._fetch_list[i]
                name = getattr(name, "name", name)
                raise ServingError(
                    "fetch %r has shape %s for bucket %d — serving "
                    "fetches must be per-row ([batch, ...]) so each "
                    "request's rows can be sliced out; fetch the "
                    "per-row tensor, not a batch reduction"
                    % (name, shape, bucket))

    # -- scheduler / completion threads ------------------------------------
    def _ensure_threads(self):
        if self._scheduler_thread is not None:
            return
        with self._lock:
            if self._scheduler_thread is not None:
                return
            self._scheduler_thread = threading.Thread(
                target=self._scheduler, name="serving-scheduler",
                daemon=True)
            self._completion_thread = threading.Thread(
                target=self._completer, name="serving-completion",
                daemon=True)
            self._scheduler_thread.start()
            self._completion_thread.start()

    def _bucket_for(self, rows):
        for b in self.buckets:
            if b >= rows:
                return b
        return self.buckets[-1]

    def _scheduler(self):
        """Pack the request queue into padded buckets, continuously:
        block (stop-aware) for the first request, hold the batch open
        for up to ``max_wait_ms`` while more arrive, dispatch the
        moment it fills the largest bucket — then immediately start
        forming the next batch while the device computes this one."""
        carry, batch, leftovers = None, [], []
        try:
            while True:
                if carry is not None:
                    req, carry = carry, None
                else:
                    req = stop_aware_get(self._queue, poll_s=0.05,
                                         stopping=self._idle_poll)
                    if req is QUEUE_DRAINED:
                        break
                batch, rows = [req], req.rows
                top = self.buckets[-1]
                deadline = time.perf_counter() + self._max_wait_s
                while rows < top:
                    if self._draining():
                        # drain mode: no latency budget — pack whatever
                        # is already queued and go
                        try:
                            nxt = self._queue.get_nowait()
                        except queue.Empty:
                            break
                    else:
                        left = deadline - time.perf_counter()
                        if left <= 0:
                            break
                        try:
                            nxt = self._queue.get(
                                timeout=min(left, 0.05))
                        except queue.Empty:
                            continue    # re-check deadline / drain flip
                    if rows + nxt.rows > top:
                        carry = nxt     # head of the NEXT batch
                        break
                    batch.append(nxt)
                    rows += nxt.rows
                self._dispatch_batch(batch)
                batch = []    # dispatched (or answered) — the crash
                #               handler must not re-resolve in-flight
                #               futures and race the completion thread
            # final sweep: close admission under the lock (no submit can
            # slip past it — see submit()), then answer everything that
            # landed before the door shut
            with self._lock:
                self._admission_closed = True
            if carry is not None:
                leftovers.append(carry)
                carry = None    # owned by leftovers now — the crash
                #                 handler must not account it twice
            while True:
                try:
                    leftovers.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            while leftovers:
                batch, rows = [], 0
                while leftovers and \
                        rows + leftovers[0].rows <= self.buckets[-1]:
                    req = leftovers.pop(0)
                    batch.append(req)
                    rows += req.rows
                self._dispatch_batch(batch)
                batch = []
        except BaseException as e:
            self._failure = e
            # close admission FIRST (same lock protocol as the clean
            # sweep) so no submit can land an unanswerable request after
            # the drain below, then answer every popped-but-undispatched
            # request (the batch being packed, the sweep's leftovers,
            # the carry) and everything still queued — a scheduler crash
            # must never leave a client parked on fut.result()
            with self._lock:
                self._admission_closed = True
            stranded = batch + leftovers
            if carry is not None:
                stranded.append(carry)
            for r in stranded:
                self._fail_request(r, e)
            if stranded:
                with self._lock:
                    self._pending -= len(stranded)
                _m_depth.set(self._pending)
            self._fail_queued(e)
        finally:
            self._done.put(None)     # completion thread's end sentinel

    def _idle_poll(self):
        """The scheduler's empty-queue poll (stop_aware_get consults
        this each timeout).  An idle server waiting for traffic is
        ALIVE, not hung — stamp watchdog progress so an armed watchdog
        (or the /healthz staleness probe) never kills a healthy server
        over a traffic lull.  (A dispatch wedged on the device is still
        caught while requests keep the scheduler busy; once it goes
        idle, per-request deadlines — not process liveness — are the
        tool for stuck in-flight batches.)"""
        telemetry.record_progress("serving_idle")
        return self._closed.is_set()

    def _dispatch_batch(self, batch):
        """Pad to the smallest fitting bucket and dispatch ONE async
        executor call for the whole batch; hand the live fetches to the
        completion thread.  Never raises and never orphans: every
        request leaves answered, dropped-as-cancelled, or in flight,
        with its ``_pending`` slot released exactly once."""
        if not batch:
            return
        admitted = len(batch)
        released = False    # the batch's _pending slots, freed ONCE
        try:
            # the cancellation fence: claim every future before
            # computing.  set_running_or_notify_cancel() returns False
            # for a future the client cancelled while queued — drop
            # that request (it wants no answer) — and True pins the
            # future RUNNING so a later cancel() can never race the
            # completion thread's set_result.  Inside the guard: the
            # cancel notification runs client done-callbacks, which
            # may raise.
            live = [r for r in batch
                    if r.future.set_running_or_notify_cancel()]
            dropped = admitted - len(live)
            if dropped:
                self._n_cancelled += dropped
                _m_cancelled.inc(dropped)
            batch = live    # the except path must not re-handle
            #                 futures the completed fence dropped
            if not batch:
                with self._lock:
                    self._pending -= admitted
                released = True
                _m_depth.set(self._pending)
                return
            rows = sum(r.rows for r in batch)
            bucket = self._bucket_for(rows)
            pad = bucket - rows
            # batch ASSEMBLY is inside the guard too: a concat/alloc
            # failure must answer these futures, not orphan them into
            # the scheduler's crash path
            feeds = {}
            for n, (sample, dtype) in self._specs.items():
                parts = [r.feeds[n] for r in batch]
                if pad:
                    parts.append(np.zeros((pad,) + sample, dtype))
                feeds[n] = parts[0] if len(parts) == 1 else \
                    np.concatenate(parts, axis=0)
            t0_ns = time.perf_counter_ns()
            c0 = self._exe.compile_count()
            fetches = self._exe.run(self._program, feed=feeds,
                                    fetch_list=self._fetch_list,
                                    scope=self._scope,
                                    return_numpy=False)
            compiled = self._exe.compile_count() - c0
            if compiled and self._warmed:
                # the pinned contract: stays 0 forever after warmup()
                self._n_recompiles += compiled
                _m_recompiles.inc(compiled)
            for r in batch:
                r.t_dispatch = t0_ns / 1e9   # perf_counter's float view
            with self._lock:
                self._pending -= admitted
            released = True
            _m_depth.set(self._pending)
            occ = rows / float(bucket)
            self._n_batches += 1
            self._n_rows += rows
            self._n_padded += pad
            self._occ_sum += occ
            _m_batches.inc(bucket=bucket)
            _m_padded_rows.inc(pad)
            _m_occupancy.set(round(occ, 4))
            self._done.put(_Dispatched(batch, rows, bucket, fetches,
                                       t0_ns, compiled))
        except BaseException as e:
            _m_errors.inc()
            # the batch has NOT reached the completion thread —
            # _done.put is the try's last statement — so claimed and
            # still-pending futures take the exception here; futures
            # the client cancelled fold into the cancelled count
            for r in batch:
                self._fail_request(r, e)
            if not released:
                with self._lock:
                    self._pending -= admitted
            _m_depth.set(self._pending)

    def _completer(self):
        """Materialize dispatched batches (the only blocking host reads
        in the pipeline — off the scheduler's path, so packing batch
        N+1 overlaps batch N's device compute) and fulfill per-request
        futures with padding-free slices."""
        while True:
            item = self._done.get()   # scheduler ALWAYS puts the None
            if item is None:          # sentinel before exiting
                break
            try:
                arrays = [np.asarray(f) for f in item.fetches]
            except BaseException as e:
                _m_errors.inc()
                for r in item.batch:
                    _resolve(r.future, e)
                continue
            dur_ns = time.perf_counter_ns() - item.t0_ns
            compute_s = dur_ns / 1e9
            _m_compute.observe(compute_s)
            qwaits_us = []
            off = 0
            for r in item.batch:
                outs = [a[off:off + r.rows].copy() for a in arrays]
                off += r.rows
                wait = r.t_dispatch - r.t_submit
                qwaits_us.append(round(wait * 1e6, 1))
                _m_queue_wait.observe(wait)
                if _resolve(r.future, None, outs):
                    self._n_responses += 1
                    _m_responses.inc()
            # one step-event per batch (kind="serving"): the JSONL/ring
            # substrate tools/metrics_report.py's serving section reads
            telemetry.record_step_event(
                kind="serving", ts_ns=item.t0_ns,
                dur_ns=dur_ns, k=0,
                bucket=item.bucket, rows=item.rows,
                occupancy=round(item.rows / float(item.bucket), 4),
                qwaits_us=qwaits_us, recompiled=item.compiled,
                rejects_total=self._n_rejects, sid=self._sid)

    def _fail_request(self, req, exc):
        """Answer one request with ``exc``; a request the client
        cancelled first folds into the cancelled count instead — still
        that counter's meaning ('cancelled while queued'), even when
        the answer would have been an exception."""
        if not _resolve(req.future, exc) and req.future.cancelled():
            self._n_cancelled += 1
            _m_cancelled.inc()

    def _fail_queued(self, exc):
        drained = 0
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            drained += 1
            self._fail_request(req, exc)
        if drained:
            with self._lock:
                self._pending -= drained
            _m_depth.set(self._pending)

    # -- shutdown ----------------------------------------------------------
    def close(self, timeout=60.0):
        """Graceful drain: stop admission, answer every accepted
        request, join both threads, flush metrics.  Idempotent; also
        the preemption path — a SIGTERM through ``preemption.install()``
        flips the scheduler into drain mode on its own, and ``close()``
        then just joins and accounts the drain.

        Raises :class:`ServingError` if the drain does not finish
        within ``timeout`` — a wedged thread must NOT be reported as a
        clean drain (no depth reset, no drain record, JSONL left open
        for a later retry)."""
        t0 = time.perf_counter()
        was_stop = preemption.stop_requested()
        self._closed.set()
        sched = self._scheduler_thread
        if sched is not None:
            # one budget across BOTH joins, so close(timeout=T) blocks
            # at most ~T — not 2T — before reporting the wedge
            deadline = t0 + timeout
            sched.join(timeout=timeout)
            self._completion_thread.join(
                timeout=max(0.0, deadline - time.perf_counter()))
            stuck = [t.name for t in (sched, self._completion_thread)
                     if t.is_alive()]
            if stuck:
                raise ServingError(
                    "drain did not finish within %.1fs (%s still "
                    "alive, %d requests pending) — not recording a "
                    "completed drain; call close() again to retry"
                    % (timeout, ", ".join(stuck), self._pending))
        _m_depth.set(0)
        if was_stop:
            # serving analogue of the training drain record: requests
            # answered instead of steps, nothing to checkpoint
            preemption.record_drain(
                step=self._n_responses,
                dur_ns=int((time.perf_counter() - t0) * 1e9),
                saved=False, source="serving")
        telemetry.close_jsonl()       # flushed + durable for scrapers
        if self._failure is not None:
            raise ServingError(
                "serving executor failed during drain: %s"
                % (self._failure,)) from self._failure

    def drained(self):
        """True once the scheduler exited with everything answered."""
        t = self._scheduler_thread
        return t is None or not t.is_alive()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- introspection -----------------------------------------------------
    def stats(self):
        """Per-instance counters (the registry aggregates globally):
        requests/responses/rejects/cancelled, batches/rows/padded_rows, mean
        occupancy, recompiles-after-warmup, live queue depth, and the
        resolved bucket ladder."""
        n = self._n_batches
        return {
            "requests": self._n_requests,
            "responses": self._n_responses,
            "rejects": self._n_rejects,
            "cancelled": self._n_cancelled,
            "recompiles": self._n_recompiles,
            "batches": n,
            "rows": self._n_rows,
            "padded_rows": self._n_padded,
            "occupancy_mean": round(self._occ_sum / n, 4) if n else None,
            "queue_depth": self._pending,
            "buckets": list(self.buckets),
            "warmed": self._warmed,
        }
