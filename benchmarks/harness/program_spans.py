"""What the program says of itself in a traced stretch, for the readers in
``layer_metrics/`` that look inside the layers the older readers time from
outside.

Three sources, all the program's own (it is never told it is measured):

- **spans**: ``paddle_tpu.fluid.telemetry.span`` enters a
  ``jax.profiler.TraceAnnotation("fluid.<kind>", **labels)``, so the
  stretch's xplane holds ``fluid.step`` / ``fluid.feed_wait`` /
  ``fluid.dispatch`` / ``fluid.enqueue`` events on the consumer thread's
  line and ``fluid.feed_stage`` on the loader worker's, on the clock of the
  device planes' ``XLA Ops``.  ``ctx`` names no path: the stretch's trace is
  the newest ``.xplane.pb`` under ``loop.TRACE_DIR``.
- **scopes**: ``paddle_tpu.fluid.profiler.step_scopes()`` maps each
  instruction of the compiled step to its ``op_name``, in which the lowering
  has put ``role_fwd`` / ``role_bwd`` / ``role_opt`` around every Fluid op.
- **kernel names**: XLA:TPU names a Mosaic custom call's instruction after
  the kernel (``%flash_dq.3``), so a device event names its kernel itself.

A program from before these existed has none of them, and every function
here then returns None; a program that has them but whose compiled step shows
none (a stale executable, a scope lost in a refactor) is an error.
"""

import glob
import os
import re

from . import loop
from . import trace as trace_mod

SPAN_PREFIX = "fluid."
CALLER_SPAN = "bench.exe_run"     # loop.Stepper's span around each exe.run
ROLE = re.compile(r"role_(fwd|bwd|opt)")
ROLES = ("fwd", "bwd", "opt")
MOSAIC = "tpu_custom_call"
KERNEL_PREFIX = "flash_"


def log(msg):
    print(msg, flush=True)


# -- arithmetic on intervals --------------------------------------------------

def overlap_seconds(a, b):
    """Seconds that lie both in ``a`` and in ``b``, two lists of ``(start,
    end)`` pairs (merged here, so neither has to be disjoint)."""
    a, b = trace_mod.merge(a), trace_mod.merge(b)
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_shares(gaps, window_s, steps, feed_waits):
    """``(share inside fluid.feed_wait, share inside fluid.step and outside
    fluid.feed_wait)`` of a device's window, in %, from its idle ``gaps``
    and the two spans' intervals (a ``fluid.feed_wait`` lies inside its
    ``fluid.step``)."""
    in_wait = overlap_seconds(gaps, feed_waits)
    in_step = overlap_seconds(gaps, steps)
    return 100.0 * in_wait / window_s, \
        100.0 * max(0.0, in_step - in_wait) / window_s


# -- the program's spans ------------------------------------------------------

class Spans:
    """The ``fluid.*`` events of one trace: ``(line, start s, end s,
    labels)`` by name; a line of a host plane is a thread.  ``caller_s``:
    the seconds of the benchmark's own span around each ``exe.run``, for
    the inside to be held against the outside of the same stretch."""

    def __init__(self, path):
        from jax.profiler import ProfileData

        self.path = path
        self.by_name = {}
        self.caller_s = []
        line_id = 0
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                line_id += 1
                for e in line.events:
                    if e.name == CALLER_SPAN:
                        self.caller_s.append(e.duration_ns * 1e-9)
                    elif e.name.startswith(SPAN_PREFIX):
                        self.by_name.setdefault(e.name, []).append(
                            (line_id, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9,
                             dict(e.stats)))
        for events in self.by_name.values():
            events.sort(key=lambda e: e[1])

    def named(self, kind):
        return self.by_name.get(SPAN_PREFIX + kind, [])

    def intervals(self, kind):
        return [(start, end) for _, start, end, _ in self.named(kind)]

    def seconds(self, kind):
        return sum(end - start for start, end in self.intervals(kind))

    def mean_ms(self, kind):
        spans = self.named(kind)
        return 1e3 * self.seconds(kind) / len(spans) if spans else None


def newest_xplane():
    found = glob.glob(os.path.join(loop.TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def spans(ctx):
    """The traced stretch's ``Spans`` (its xplane is read once a process);
    None in an untraced run and where the program records no
    ``fluid.step``."""
    if ctx["trace"] is None:
        return None
    path = newest_xplane()
    found = path and _once("spans", ctx["trace"], lambda: Spans(path))
    return found if found and found.named("step") else None


def idlest_device(reduced):
    """The chip ``device_idle_share`` reads: the one that idles most."""
    return max(reduced.devices.values(),
               key=lambda d: 1.0 - d["busy_s"] / d["window_s"])


def idle_by_span(ctx):
    """``(idle_feed_wait_share, idle_dispatch_share)`` of the stretch, and
    on an earlier line the idle seconds inside each ``fluid.*`` span."""
    found = spans(ctx)
    if found is None:
        return None
    dev = idlest_device(ctx["trace"])
    busy = trace_mod.merge((o[2], o[3]) for o in dev["ops"])
    gaps = trace_mod.gaps(busy, dev["begin"], dev["end"])
    return _once("idle", ctx["trace"], lambda: _idle_by_span(
        found, gaps, dev["window_s"]))


def _idle_by_span(found, gaps, window_s):
    inside = sorted(((overlap_seconds(gaps, found.intervals(kind)), kind)
                     for kind in (n[len(SPAN_PREFIX):]
                                  for n in found.by_name)), reverse=True)
    idle_s = sum(b - a for a, b in gaps)
    entries = [start for start, _ in found.intervals("step")]
    paced = sorted(b - a for a, b in zip(entries, entries[1:]))
    if paced:
        # against the untraced window's p50 interval: what tracing costs
        log("traced stretch: %d fluid.step spans, median %.3f ms from one "
            "entry to the next" % (len(entries),
                                   1e3 * paced[len(paced) // 2]))
    if found.caller_s:
        log("inside against outside, same stretch: fluid.step %.3f ms a "
            "step (feed_wait %.3f + enqueue %.3f + the executor's own "
            "%.3f) inside the benchmark's %s of %.3f ms" % (
                found.mean_ms("step"), found.mean_ms("feed_wait") or 0.0,
                found.mean_ms("enqueue") or 0.0,
                1e3 * (found.seconds("step") - found.seconds("feed_wait") -
                       found.seconds("enqueue")) / len(entries),
                CALLER_SPAN,
                1e3 * sum(found.caller_s) / len(found.caller_s)))
    log("idle by program span: %.6f s idle of %.6f s on the chip that idles "
        "most; inside (a child counts in its parents too): %s; outside "
        "fluid.step %.6f s" % (
            idle_s, window_s,
            ", ".join("%s%s %.6f s" % (SPAN_PREFIX, kind, s)
                      for s, kind in inside),
            idle_s - overlap_seconds(gaps, found.intervals("step"))))
    return idle_shares(gaps, window_s, found.intervals("step"),
                       found.intervals("feed_wait"))


# -- the compiled step's names ------------------------------------------------

def step_scopes():
    """``{instruction: op_name}`` of the compiled step as the program gives
    it, None from a program that does not."""
    from paddle_tpu.fluid import profiler

    scopes_of = getattr(profiler, "step_scopes", None)
    return None if scopes_of is None else scopes_of()


def role_seconds(ctx):
    """Seconds of the first chip's operations under each op role, ``{"fwd",
    "bwd", "opt", "none"}`` (``none``: instructions no Fluid op's scope
    covers — copies, what the partitioner adds), with the split logged on an
    earlier line.  Raises where the step has Fluid ops and no role scope."""
    reduced = ctx["trace"]
    if reduced is None or not reduced.steps:
        return None
    scopes = step_scopes()
    if scopes is None:
        return None
    return _once("roles", reduced, lambda: _role_seconds(reduced, scopes))


def _role_seconds(reduced, scopes):
    out = dict.fromkeys(ROLES + ("none",), 0.0)
    fluid_ops = 0
    for label, name, start, end, _ in reduced.ops():
        role = ROLE.search(scopes.get(name, ""))
        out[role.group(1) if role else "none"] += end - start
        fluid_ops += label.startswith("fluid_")
    if fluid_ops and not any(out[r] for r in ROLES):
        raise RuntimeError(
            "the traced step has %d operations under fluid_<op> scopes and "
            "none under a role_fwd / role_bwd / role_opt scope (%d "
            "instructions named by the program): the executable was "
            "compiled without them — a stale compilation cache?" % (
                fluid_ops, len(scopes)))
    step_ms = 1e3 * reduced.devices[min(reduced.devices)]["busy_s"] / \
        reduced.steps
    ms = {k: 1e3 * v / reduced.steps for k, v in out.items()}
    log("step by op role, ms a step on the first chip: step_device_ms %.3f "
        "= backward %.3f + optimizer %.3f + under no role scope %.3f + "
        "forward remainder %.3f (operations under role_fwd sum to %.3f)" % (
            step_ms, ms["bwd"], ms["opt"], ms["none"],
            step_ms - ms["bwd"] - ms["opt"] - ms["none"], ms["fwd"]))
    return out


def kernel_ms_per_step(ctx, kernel):
    """Device milliseconds a step spends in the Mosaic kernel ``kernel`` on
    the first chip.  Raises where the step runs Mosaic calls and none
    carries a kernel's name."""
    reduced = ctx["trace"]
    if reduced is None or not reduced.steps or step_scopes() is None:
        return None
    by_kernel = _once("kernels", reduced, lambda: _kernel_seconds(reduced))
    return 1e3 * by_kernel.get(kernel, 0.0) / reduced.steps


def _kernel_seconds(reduced):
    out = {}
    for _, name, start, end, target in reduced.ops():
        if target == MOSAIC:
            kernel = name.split(".")[0]
            out[kernel] = out.get(kernel, 0.0) + end - start
    if out and not any(k.startswith(KERNEL_PREFIX) for k in out):
        raise RuntimeError(
            "the traced step runs Mosaic calls and none is named %s*: %s — "
            "a stale compilation cache?" % (KERNEL_PREFIX, sorted(out)[:6]))
    return out


def compile_counter(name, **labels):
    """The program's counter ``name`` summed over ``labels``; None from a
    program without it."""
    from paddle_tpu.fluid import telemetry

    metric = telemetry.registry().get(name)
    return None if metric is None else metric.value(**labels)


_memo = {}


def _once(key, reduced, compute):
    """``compute()`` once per reduced trace (its readers share one pass and
    one logged line)."""
    hit = _memo.get(key)
    if hit is None or hit[0] is not reduced:
        hit = _memo[key] = (reduced, compute())
    return hit[1]
