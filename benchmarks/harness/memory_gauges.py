"""What the program's gauges say of the step's memory, for the five readers
under ``hbm_peak_gb`` (``layer_metrics/step_temp_gb.py``, ``step_state_gb``,
``feed_staged_gb``, ``hbm_in_use_peak_gb``, ``hbm_reserved_peak_gb``).

``hbm_peak_gb`` is the fullest chip's ``peak_bytes_in_use`` +
``peak_bytes_reserved`` (``loop.device_peak_bytes``).  The program accounts
for both where it compiles and holds them:

- ``step_memory_bytes{sig, kind}`` / ``step_resident_bytes{sig, kind}``: the
  record ``Executor._step_executable`` stamps for every executable an
  introspection call produces.  The traced run asks for the step's HLO
  (``loop.trace_stretch``), so the step's record is there at no compile the
  run did not make already.
- ``feed_staged_bytes{stat}``: what the program-bound loader holds staged.
- ``device_memory_bytes{device, stat}``: the runtime's two books, set by
  ``telemetry.sample_device_memory()`` when somebody asks.

A program from before these gauges has none of them, and every function
here then returns None.
"""

from . import program_spans

GB = 1e9
STATE_KINDS = ("parameter", "optimizer_state", "other_state")


def log(msg):
    print(msg, flush=True)


def _labelsets(name):
    from paddle_tpu.fluid import telemetry

    metric = telemetry.registry().get(name)
    return None if metric is None else metric.labelsets()


def _step_memory():
    """``{signature: {kind: bytes}}`` of every executable stamped."""
    records = {}
    for ls in _labelsets("step_memory_bytes") or ():
        records.setdefault(ls["sig"], {})[ls["kind"]] = \
            program_spans.compile_counter("step_memory_bytes", **ls)
    return records


def step_signature(records=None):
    """The signature of the training step among the executables stamped:
    the one with the most temporaries (a cell's check may have introspected
    a second, smaller program)."""
    records = _step_memory() if records is None else records
    return max(records, key=lambda sig: records[sig].get("temp", 0),
               default=None)


def step_temp_gb():
    records = _step_memory()
    sig = step_signature(records)
    if sig is None:
        return None
    for name, kinds in sorted(records.items()):
        log("step_memory_bytes of %s%s: %s" % (
            name, " (the step)" if name == sig else "", "  ".join(
                "%s %d" % (k, v) for k, v in sorted(kinds.items()))))
    return records[sig]["temp"] / GB


def step_state_gb():
    """The persistables the step takes, on one device: parameters, the
    optimizer's state and the rest."""
    sig = step_signature()
    if sig is None:
        return None
    parts = [program_spans.compile_counter("step_resident_bytes", sig=sig,
                                           kind=kind)
             for kind in STATE_KINDS]
    if None in parts:
        return None
    feed = program_spans.compile_counter("step_resident_bytes", sig=sig,
                                         kind="feed")
    log("step_resident_bytes of %s: %s  (feed %d)" % (
        sig, "  ".join("%s %d" % (k, v) for k, v in zip(STATE_KINDS, parts)),
        feed or 0))
    return sum(parts) / GB


def feed_staged_gb():
    peak = program_spans.compile_counter("feed_staged_bytes", stat="peak")
    if peak is None:
        return None
    now = program_spans.compile_counter("feed_staged_bytes", stat="now")
    log("feed_staged_bytes: peak %d, now %d" % (peak, now or 0))
    return peak / GB


def device_peaks():
    """``(peak_bytes_in_use, peak_bytes_reserved)`` in GB of ONE device, the
    one whose sum of the two is largest (``loop.run_cell``'s choice of chip
    for ``hbm_peak_gb``), sampled now."""
    from paddle_tpu.fluid import telemetry

    sample = getattr(telemetry, "sample_device_memory", None)
    if sample is None:
        return None
    sample()
    books = {}
    for ls in _labelsets("device_memory_bytes") or ():
        books.setdefault(ls["device"], {})[ls["stat"]] = \
            program_spans.compile_counter("device_memory_bytes", **ls)
    peaks = {d: (b.get("peak_bytes_in_use", 0), b.get("peak_bytes_reserved", 0))
             for d, b in books.items()}
    if not peaks:
        return None
    device = max(peaks, key=lambda d: sum(peaks[d]))
    log("device_memory_bytes, peak_bytes_in_use + peak_bytes_reserved: %s; "
        "device %s is the fullest; its books: %s" % (
            ", ".join("%s: %d + %d" % (d, u, r)
                      for d, (u, r) in sorted(peaks.items())), device,
            "  ".join("%s %d" % (k, v)
                      for k, v in sorted(books[device].items()))))
    in_use, reserved = peaks[device]
    return in_use / GB, reserved / GB
