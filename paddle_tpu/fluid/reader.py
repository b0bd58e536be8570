"""PyReader / DataLoader: host input pipeline with device prefetch.

Reference contract: ``python/paddle/fluid/reader.py`` (PyReader over the C++
``LoDTensorBlockingQueue``, ``operators/reader/buffered_reader.cc`` double
buffering).  Here the blocking queue is a Python queue of ready feed dicts
and double buffering is ``jax.device_put`` issued from the producer thread —
the transfer overlaps the current step's device compute, which is exactly
the buffered_reader trick in XLA terms.

Two modes, as in the reference:
- iterable=True: ``for data in loader(): exe.run(feed=data)``.
- iterable=False: ``loader.start(); exe.run()`` — the executor pulls
  batches from the bound program queue and raises ``fluid.core.EOFException``
  when the pass ends (executor.py integration).
"""

import queue
import threading
import time
import warnings

import numpy as np
import jax

from . import framework
from . import preemption
from . import telemetry
from .data_feeder import DataFeeder
from .executor import _device_for_place, device_nbytes, feed_nbytes, TPUPlace
from .core_shim import EOFException

# input-pipeline telemetry (docs/observability.md): batches produced by
# the loader tier, plus the STARVATION gauge — how long the consumer
# (Executor.run pulling next_feed) blocked waiting for the producer.  A
# rising wait is the "input-bound, not compute-bound" signal the MLPerf
# TPU-pod writeups profile first.
_m_loader_batches = telemetry.counter(
    "loader_batches_total", "feed dicts produced by DataLoader/PyReader")
# what the input layer holds of the device's memory: the batches a
# program-bound loader's worker has put on the device and no step has
# taken yet (the capacity queue, the one-batch lookahead, the batch the
# worker is handing to a full queue); a batch a step holds is the step's
_m_staged = telemetry.gauge(
    "feed_staged_bytes",
    "bytes on one device of the feeds program-bound DataLoaders hold "
    "staged and not yet handed to a step (stat=now|peak)")
_m_wait_s = telemetry.counter(
    "data_wait_seconds_total",
    "seconds the consumer blocked on the DataLoader queue")
_m_wait_last = telemetry.gauge(
    "data_wait_last_seconds", "most recent consumer wait (starvation)")
# wait DISTRIBUTION (not just the last sample): p50 vs p99 starvation
# separates "every step waits a little" (raise ring depth / reader
# threads) from "rare stalls" (shard skew, GC); tools/metrics_report.py
# reports both per K from the step-events' data_wait_s field
_m_wait_hist = telemetry.histogram(
    "data_wait_seconds",
    "consumer wait for the next ready feed (starvation distribution)",
    buckets=(1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0))
# feed-ring telemetry: occupancy says how far ahead the producer runs
# (pinned at ~0 = feed-bound, pinned at depth = compute-bound); the
# overlap fraction is the headline "H2D rides under compute" number
_m_ring_occ = telemetry.gauge(
    "feed_ring_occupancy",
    "device-resident feed windows ready in the ring (0..depth)")
_m_overlap = telemetry.gauge(
    "h2d_overlap_frac",
    "fraction of feed staging wall time (host fill + device_put) hidden "
    "under consumer compute; 1.0 = fully overlapped")
_m_ring_windows = telemetry.counter(
    "feed_ring_windows_total",
    "feed windows staged device-side by feed-ring producer threads")


def _record_wait(wait, pending=True):
    """One consumer starvation sample: counter + last-gauge + histogram,
    plus (when ``pending``) the per-dispatch pool the executor drains
    into the next step-event's ``data_wait_s``.  End-of-stream waits —
    blocking to learn the pass ended — pass ``pending=False``: no
    dispatch consumes them, and stamping them onto the NEXT unrelated
    dispatch would corrupt its starvation attribution."""
    _m_wait_s.inc(wait)
    _m_wait_last.set(wait)
    _m_wait_hist.observe(wait)
    if pending:
        telemetry.record_data_wait(wait)


class DataLoaderWorkerError(RuntimeError):
    """A DataLoader producer thread died: re-raised to the consumer with
    batch-index and generator attribution (a mid-epoch data error names
    its batch instead of surfacing as a bare queue-thread traceback)."""


class _EndSentinel:
    """End-of-pass marker; carries the producer's exception, if any,
    plus the count of batches delivered before it died."""

    __slots__ = ("err", "batch_index")

    def __init__(self, err=None, batch_index=None):
        self.err = err
        self.batch_index = batch_index


def _reader_name(reader):
    return getattr(reader, "__qualname__", None) or \
        getattr(reader, "__name__", None) or repr(reader)


# sentinel: the queue drained under a stop request — distinct from any
# item a producer could legally enqueue (incl. None)
QUEUE_DRAINED = object()


def stop_aware_get(q, stopping=None, poll_s=0.1):
    """Pull one item from ``q`` without ever parking on a queue nobody
    will fill: poll with a bounded timeout, and give up once a stop is
    requested (``fluid.preemption`` or the extra ``stopping()``
    predicate) with the queue still empty.  One final non-blocking pull
    closes the timed-out-while-the-item-landed race, so an item enqueued
    strictly before the stop request is never dropped.

    Returns the item, or :data:`QUEUE_DRAINED` when the wait ended on a
    stop with nothing queued.  This is the PR 7 "consumers drain too"
    contract (GeneratorLoader.next_feed, FeedRing) factored out so every
    consumer-side queue wait — including the serving scheduler
    (serving.py) — shares one proven loop instead of growing its own."""
    while True:
        try:
            return q.get(timeout=poll_s)
        except queue.Empty:
            if preemption.stop_requested() or \
                    (stopping is not None and stopping()):
                try:
                    return q.get_nowait()
                except queue.Empty:
                    return QUEUE_DRAINED


class FeedRingError(RuntimeError):
    """Batch-index context for a feed-ring producer failure.  The
    consumer re-raises the producer's ORIGINAL exception (existing
    ``except IOError``-style handlers keep working exactly as on the
    synchronous path) with this attached as its ``__cause__``, so the
    traceback still names the batch the pipeline died at."""


class FeedRing:
    """Device-resident input ring: ``depth`` feed windows staged ahead
    of the consumer by a producer thread (the ``FLAGS_feed_ring_depth``
    pipeline; docs/performance.md lever #8).

    The producer iterates ``batches`` (host feed dicts — per-step, or
    stacked ``[K, ...]`` windows from ``dataset.stack_batch_windows``)
    and applies ``put`` — typically a sharded ``jax.device_put`` — so
    both the host-side window fill AND the H2D transfer run off the
    consumer's critical path, overlapping device compute (the
    buffered_reader.cc / tf.data prefetch-buffer design, XLA terms).
    The consumer iterates ready device-resident windows, blocking only
    when the ring is empty (counted in the starvation gauge/histogram).

    Lifecycle contract:

    - a slot returns to the producer only when the consumer asks for
      the NEXT window — by then the dispatch consuming the previous one
      has been enqueued, so staging-buffer reuse can never race a live
      feed (and donation of scope state is unaffected: feeds are never
      donated);
    - a preemption stop request (``fluid.preemption``), an external
      ``stop_when`` predicate, or ``close()`` drains the producer — it
      can never stay parked on a full ring nobody will drain;
    - a producer exception surfaces on the consumer as
      :class:`FeedRingError` naming the batch index;
    - ``close()`` (also driven by generator ``.close()`` chains and the
      train loops' ``finally``) closes the source iterator and joins
      the producer thread.
    """

    def __init__(self, put, batches, depth, stop_when=None):
        self._put = put
        self._batches = batches
        self._depth = max(1, int(depth))
        self._stop_when = stop_when
        self._ready = queue.Queue()   # (device, host) pairs + end sentinel
        self._slots = threading.Semaphore(self._depth)
        self._closed = threading.Event()
        self._out = None              # window handed out, freed on next pull
        self._pulled = 0              # windows handed out so far
        self._staged_ready = 0        # real windows in _ready (gauge src)
        self._occ_lock = threading.Lock()   # += / -= cross two threads
        self._stage_s = 0.0           # producer staging wall (fill + put)
        self._wait_s = 0.0            # consumer starvation wall
        self._thread = threading.Thread(
            target=self._producer, name="feed-ring-producer", daemon=True)
        self._thread.start()

    # -- producer ----------------------------------------------------------
    def _stopping(self):
        return (self._closed.is_set() or preemption.stop_requested() or
                (self._stop_when is not None and self._stop_when()))

    def _producer(self):
        err = None
        staged = 0
        it = iter(self._batches)
        try:
            while True:
                # the source advance IS staging work too — for stacked
                # windows it runs the K-sample fill, the dominant host
                # cost at large K (the overlap gauge's denominator must
                # include it); waiting for a free slot is not
                t0 = time.perf_counter()
                try:
                    host = next(it)
                except StopIteration:
                    break
                self._stage_s += time.perf_counter() - t0
                acquired = False
                while not self._stopping():
                    if self._slots.acquire(timeout=0.1):
                        acquired = True
                        break
                if not acquired:
                    return
                t0 = time.perf_counter()
                # fluid.feed_stage: the device_put staging work, on the
                # producer thread's own line of a trace (no phase arg —
                # the progress stamp below stays AFTER the put: a stamp
                # means COMPLETED staging work); ``batch`` is the one the
                # consumer's fluid.feed_wait carries for this window
                with telemetry.span("feed_stage", batch=staged,
                                    bytes=feed_nbytes(host)):
                    dev = self._put(host)
                self._stage_s += time.perf_counter() - t0
                # hang-detection stamp: each window staged is forward
                # progress of the input pipeline — a wedged producer
                # stops stamping and the watchdog names the stall
                # (fluid/watchdog.py; no-op when disarmed)
                telemetry.record_progress("feed_ring")
                with self._occ_lock:
                    self._staged_ready += 1
                    occ = self._staged_ready
                self._ready.put((dev, host))
                _m_ring_windows.inc()
                _m_ring_occ.set(occ)
                staged += 1
        except BaseException as e:   # surfaced to the consumer
            err = e
        finally:
            close = getattr(self._batches, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass
            # the ready queue is unbounded (the semaphore is the bound),
            # so the sentinel can always land even mid-drain
            self._ready.put(_EndSentinel(err, batch_index=staged))

    # -- consumer ----------------------------------------------------------
    def __iter__(self):
        return self

    def _recycle(self):
        """Free the previously handed-out window's slot (the dispatch
        consuming it has been enqueued by the time the consumer comes
        back) and offer its staging buffers back to the pool."""
        out, self._out = self._out, None
        if out is None:
            return
        dev, host = out
        self._slots.release()
        release = getattr(host, "release", None)
        if release is not None:
            try:
                release(dev if isinstance(dev, dict) else None)
            except Exception:
                pass

    def __next__(self):
        self._recycle()
        t0 = time.perf_counter()
        # fluid.feed_wait: the consumer's starvation window, the region
        # _record_wait accounts
        with telemetry.span("feed_wait", batch=self._pulled):
            while True:
                if self._closed.is_set():
                    raise StopIteration
                try:
                    item = self._ready.get(timeout=0.1)
                    break
                except queue.Empty:
                    if self._stopping():
                        # preemption/external stop drained the producer
                        # — never park on a queue nothing will fill
                        raise StopIteration
        wait = time.perf_counter() - t0
        self._wait_s += wait
        _record_wait(wait, pending=not isinstance(item, _EndSentinel))
        if isinstance(item, _EndSentinel):
            # exhausted: further __next__ calls must keep raising
            # StopIteration (iterator protocol — a second epoch loop
            # over the same object is empty, never a hang)
            self._closed.set()
            _m_ring_occ.set(0)
            self._thread.join(timeout=5.0)
            if item.err is not None:
                # surface the ORIGINAL exception type (consumers catch
                # what they always caught); the staging-position context
                # rides as its __cause__.  "item" = whatever the source
                # yields — a per-step batch, or one stacked [K, ...]
                # window (multiply by K for the sample position there)
                raise item.err from FeedRingError(
                    "feed ring producer failed staging item %d (%d "
                    "item(s) staged; one item = one batch, or one "
                    "stacked [K, ...] window on windowed streams)"
                    % (item.batch_index, item.batch_index))
            raise StopIteration
        # occupancy counts STAGED windows only (the end sentinel shares
        # the queue but is not one) — "pinned at 0" must stay readable
        # as the feed-bound signature
        with self._occ_lock:
            self._staged_ready -= 1
            occ = self._staged_ready
        _m_ring_occ.set(occ)
        if self._stage_s > 0.0:
            _m_overlap.set(max(0.0, min(
                1.0, 1.0 - self._wait_s / self._stage_s)))
        self._out = item
        self._pulled += 1
        return item[0]

    def close(self):
        """Stop the producer, drop staged windows, join the thread.
        Idempotent; also reached through generator ``.close()`` chains
        (`GeneratorLoader`, `train_from_dataset`'s ``finally``)."""
        self._closed.set()
        self._out = None           # dropped un-recycled: buffers may be live
        try:
            while True:
                self._ready.get_nowait()
        except queue.Empty:
            pass
        # a mid-stream close must not leave a stale occupancy reported
        # forever (the gauge is read as an absolute diagnosis signal)
        with self._occ_lock:
            self._staged_ready = 0
        _m_ring_occ.set(0)
        thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(timeout=5.0)

    def __del__(self):
        try:
            if not self._closed.is_set():
                self.close()
        except Exception:
            pass


def _staged_nbytes(feed):
    """Bytes on one device of a staged feed's arrays.  A value still on
    the host (no consumer has bound a device yet) holds none."""
    return sum(device_nbytes(v) for v in feed.values()
               if isinstance(v, jax.Array))


def _note_staged(delta):
    now = _m_staged.inc(delta, stat="now")
    if delta > 0:
        _m_staged.raise_to(now, stat="peak")


class GeneratorLoader:
    def __init__(self, feed_list, capacity=8, use_double_buffer=True,
                 iterable=True, return_list=False, steps_per_run=None):
        from . import flags
        # K>1 (explicit opt-in): stage K batches ahead as ONE stacked
        # [K, ...] array per slot (dataset.stack_batch_windows) and
        # device_put the whole window with the same one-window lookahead
        # — feeds arrive ready for Executor.run_window's fused
        # multi-step dispatch (program-bound loaders route there
        # automatically)
        self._steps_per_run = 1 if steps_per_run is None else \
            flags.steps_per_run_value(steps_per_run)
        self._feed_list = feed_list
        self._names = [v.name if isinstance(v, framework.Variable) else v
                       for v in feed_list]
        self._capacity = capacity
        self._use_double_buffer = use_double_buffer
        self._iterable = iterable
        self._return_list = return_list
        self._gen = None
        self._src_name = None
        self._places = None
        self._queue = None
        self._thread = None
        self._stop_event = None
        self._pulled = 0          # batches next_feed handed over since start
        # feed_staged_bytes, this loader's part: bytes the worker staged
        # (its thread alone adds) and bytes next_feed handed over (the
        # consumer's alone); the difference is dropped with the queue
        self._staged_in = self._staged_out = 0
        # set by Executor.run on the first program-bound pull: when no
        # explicit places were given, the producer thread device_puts
        # subsequent batches to the CONSUMING executor's device, so the
        # H2D transfer still overlaps the step instead of riding the
        # jitted call (single-process only — multi-process feeds must
        # stay numpy, the global-value contract)
        self._consumer_device = None
        # set by Executor._bind_loader_shardings after a loader-fed
        # dispatch: {feed name: NamedSharding} from the compiled plan.
        # When the bound executor compiled under GSPMD, the producer
        # device_puts each feed with ITS sharding, so batches land
        # already sharded instead of replicated-then-resharded (zero
        # reshard transfers at dispatch; tests/test_hlo_properties.py)
        self._consumer_shardings = None
        if not iterable:
            # non-iterable: bind to the current program so Executor.run can
            # pull batches (reference py_reader-in-program contract)
            framework.default_main_program()._loader = self

    # -- wiring ------------------------------------------------------------
    def set_sample_generator(self, reader, batch_size, drop_last=True,
                             places=None):
        def batcher():
            buf = []
            for sample in reader():
                if not isinstance(sample, (list, tuple)):
                    sample = (sample,)
                buf.append(sample)
                if len(buf) == batch_size:
                    yield buf
                    buf = []
            if buf and not drop_last:
                yield buf
        self.set_sample_list_generator(batcher, places)
        self._src_name = _reader_name(reader)   # the USER's generator,
        return self                             # not the batcher wrapper

    def set_sample_list_generator(self, reader, places=None):
        feeder = DataFeeder(self._feed_list)

        def to_feed():
            for samples in reader():
                yield feeder.feed(samples)
        self._gen = to_feed
        self._src_name = _reader_name(reader)
        self._places = places
        return self

    def set_batch_generator(self, reader, places=None):
        def to_feed():
            for batch in reader():
                if isinstance(batch, dict):
                    yield batch
                else:
                    yield dict(zip(self._names, batch))
        self._gen = to_feed
        self._src_name = _reader_name(reader)
        self._places = places
        return self

    # -- device prefetch ---------------------------------------------------
    def _device(self):
        places = self._places
        if places:
            place = places[0] if isinstance(places, (list, tuple)) else places
            return _device_for_place(place)
        return None

    def _prefetched(self, stop_when=None, depth=None):
        """Iterator of feed dicts, device_put'ed ahead of consumption
        (executor.prefetch_ahead — the FLAGS_feed_ring_depth async ring,
        or the one-batch lookahead at ``depth=0``; either way H2D rides
        under the consumer's compute)."""
        from .executor import prefetch_ahead, sharded_put

        explicit = self._device() if self._use_double_buffer else None
        multi = jax.process_count() > 1

        def put(d):
            # _consumer_device/_consumer_shardings are read fresh each
            # batch: the executor binds them on/after its first pull,
            # when the producer thread is already running
            dev = explicit
            shardings = None
            if self._use_double_buffer and not multi:
                if dev is None:
                    dev = self._consumer_device
                shardings = self._consumer_shardings
            if dev is None and not shardings:
                return d
            staged = sharded_put(d, shardings, dev)
            if stop_when is not None and not stop_when():
                # a program-bound loader's worker, inside its
                # fluid.feed_stage span: the batch is the loader's until
                # next_feed hands it over
                nbytes = _staged_nbytes(staged)
                self._staged_in += nbytes
                _note_staged(nbytes)
            return staged

        src = self._gen()
        if self._steps_per_run > 1:
            from .dataset import stack_batch_windows
            src = stack_batch_windows(src, self._steps_per_run)

        def counted(it):
            try:
                for d in it:
                    _m_loader_batches.inc()
                    yield d
            finally:
                # generator .close() must reach the ring so its
                # producer thread is joined, not leaked
                if hasattr(it, "close"):
                    it.close()

        return counted(prefetch_ahead(put, src, depth=depth,
                                      stop_when=stop_when))

    # -- iterable protocol -------------------------------------------------
    def __call__(self):
        assert self._iterable, "non-iterable loader: use start()/reset()"
        assert self._gen is not None, "no generator set"
        if self._return_list:
            return ([d[n] for n in self._names] for d in self._prefetched())
        return self._prefetched()

    __iter__ = __call__

    # -- non-iterable (program-bound) protocol -----------------------------
    def start(self):
        assert not self._iterable
        self._stop_worker()   # a restart must not leak the previous producer
        q = queue.Queue(maxsize=self._capacity)
        stop = threading.Event()

        def worker(q=q, stop=stop):
            # a process-wide preemption stop request drains this producer
            # too: the consumer may never pull again, so a worker parked
            # on a full queue would otherwise outlive the graceful
            # shutdown (the clean-drain contract, preemption.py)
            def stopping():
                return stop.is_set() or preemption.stop_requested()

            err = None
            delivered = 0   # batches handed to the consumer queue so far;
            # depth=0: this worker thread IS the async staging producer
            # (stacking + device_put run here, off the consumer, with
            # the capacity queue as the buffer) — nesting a FeedRing
            # inside it would stack a second device-window tier on top
            # of `capacity` and double-count the same stall as both
            # ring wait and next_feed wait
            src = self._prefetched(stop_when=stopping, depth=0)
            try:            # an error is attributed to the NEXT batch
                for d in src:
                    while not stopping():
                        try:
                            q.put(d, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stopping():
                        return
                    delivered += 1
            except BaseException as e:  # surfaced to the consumer
                err = e
            finally:
                if hasattr(src, "close"):
                    src.close()
            # under preemption the consumer may already be gone — give
            # up on the sentinel too (next_feed polls the stop flag, so
            # a consumer that IS still pulling raises EOF on its own)
            while not stopping():
                try:
                    q.put(_EndSentinel(err, batch_index=delivered),
                          timeout=0.1)
                    break
                except queue.Full:
                    continue

        self._queue = q
        self._stop_event = stop
        self._pulled = 0
        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()

    def _stop_worker(self):
        thread = self._thread
        if thread is not None:
            self._stop_event.set()
            try:  # unblock a producer stuck in put()
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            thread.join(timeout=5.0)
            if thread.is_alive():
                # slow (not stuck) generators can outlive the join; the
                # stop_event makes the old worker exit without touching the
                # new queue, so restarting is safe — but warn, since a
                # stateful generator source would now see two consumers
                warnings.warn(
                    "DataLoader worker still running after 5s; it will "
                    "exit after its current read. If the data source is "
                    "stateful (shared file handle), records may be lost.")
        self._thread = None
        self._queue = None
        self._stop_event = None
        self._drop_staged()

    def _drop_staged(self):
        """The staged batches no step will take (the queue's, the
        lookahead's) leave the books with the queue."""
        if self._staged_in != self._staged_out:
            _note_staged(self._staged_out - self._staged_in)
        self._staged_in = self._staged_out = 0

    def reset(self):
        self._stop_worker()

    def next_feed(self, step=None):
        """Called by Executor.run when no explicit feed is given (with
        the ``step`` the feed is for, which the ``fluid.feed_wait`` span
        carries beside the index of the batch handed over)."""
        if self._queue is None:
            raise RuntimeError(
                "DataLoader not started: call loader.start() before "
                "exe.run() (reference PyReader contract)")
        t0 = time.perf_counter()
        # a preemption stop request drains the PRODUCER without a
        # sentinel (the consumer may be gone); a consumer that is still
        # here must not block forever on the dead queue — end the pass
        labels = {} if step is None else {"step": int(step)}
        with telemetry.span("feed_wait", batch=self._pulled, **labels):
            item = stop_aware_get(self._queue)
        if item is QUEUE_DRAINED:
            self._queue = None
            self._thread = None
            self._stop_event = None
            self._drop_staged()
            raise EOFException(
                "preemption stop requested: DataLoader drained")
        wait = time.perf_counter() - t0
        _record_wait(wait, pending=not isinstance(item, _EndSentinel))
        if isinstance(item, _EndSentinel):
            self._queue = None
            self._thread = None
            self._stop_event = None
            self._drop_staged()
            if item.err is not None:
                # batch attribution: with the device prefetch (ring or
                # one-batch lookahead) the generator is ahead of
                # delivery, so the failure is at (or just past) batch
                # `item.batch_index`.  The ring already re-raises the
                # generator's ORIGINAL exception, so __cause__ here is
                # the original error (the pinned DataLoaderWorkerError
                # contract)
                raise DataLoaderWorkerError(
                    "DataLoader worker failed around batch %s (%d "
                    "batch(es) delivered; feed vars %s; generator %s): "
                    "%s: %s" % (item.batch_index, item.batch_index or 0,
                                self._names,
                                self._src_name or "<unset>",
                                type(item.err).__name__, item.err)
                ) from item.err
            raise EOFException(
                "pass end: there is no data in the DataLoader queue")
        self._pulled += 1
        nbytes = _staged_nbytes(item)
        self._staged_out += nbytes
        _note_staged(-nbytes)
        return item


class DataLoader:
    """``fluid.io.DataLoader.from_generator`` facade (reference reader.py)."""

    @staticmethod
    def from_generator(feed_list=None, capacity=8, use_double_buffer=True,
                       iterable=True, return_list=False, steps_per_run=None):
        return GeneratorLoader(feed_list, capacity=capacity,
                               use_double_buffer=use_double_buffer,
                               iterable=iterable, return_list=return_list,
                               steps_per_run=steps_per_run)


class PyReader(GeneratorLoader):
    """Reference fluid.io.PyReader — thin alias over GeneratorLoader with
    the decorate_* method names."""

    def __init__(self, feed_list=None, capacity=8, use_double_buffer=True,
                 iterable=True, return_list=False):
        super().__init__(feed_list, capacity=capacity,
                         use_double_buffer=use_double_buffer,
                         iterable=iterable, return_list=return_list)

    def decorate_sample_generator(self, sample_generator, batch_size,
                                  drop_last=True, places=None):
        return self.set_sample_generator(sample_generator, batch_size,
                                         drop_last=drop_last, places=places)

    def decorate_sample_list_generator(self, reader, places=None):
        return self.set_sample_list_generator(reader, places=places)

    def decorate_batch_generator(self, reader, places=None):
        return self.set_batch_generator(reader, places=places)
