"""From a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing else.  On a TPU the trace
holds one plane per chip, ``/device:TPU:<id>``, whose line ``XLA Ops`` has
one event per executed HLO operation and whose line ``XLA Modules`` one per
executed program; the host plane ``/host:CPU`` has one line per thread, and
the benchmark's own ``bench.*`` spans (``jax.profiler.TraceAnnotation`` in
``loop.Stepper``) are events of the main thread's line, on the same clock.

Everything below the reading is plain arithmetic on ``(start, end)`` pairs
in seconds, tested on synthetic lists in ``benchmarks/tests``.
"""

import glob
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
NO_SPAN = "no_benchmark_span"
# operations that only contain others: their time is their children's
CONTAINERS = ("while", "conditional", "call")
FLUID_SCOPE = re.compile(r"fluid_[A-Za-z0-9_]+")
# an event of the XLA Ops line is named by its HLO text:
# "%fusion.83 = bf16[...] fusion(...), kind=kOutput, calls=..."
HLO_NAME = re.compile(r"^%?([^\s=]+)")
HLO_KIND = re.compile(r"kind=(k\w+)")
HLO_TARGET = re.compile(r'custom_call_target="([^"]+)"')
HLO_META = re.compile(
    r'^\s*(?:ROOT )?%?(\S+) = .*metadata=\{[^}]*op_name="([^"]*)"')


# -- arithmetic on intervals --------------------------------------------------

def merge(intervals):
    """Union of ``(start, end)`` pairs as a sorted list of disjoint pairs."""
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def union_seconds(intervals):
    return sum(end - start for start, end in merge(intervals))


def gaps(busy, begin, end):
    """The idle stretches of ``[begin, end]`` that ``busy`` (merged pairs)
    leaves open."""
    out, at = [], begin
    for start, stop in busy:
        if start > at:
            out.append((at, min(start, end)))
        at = max(at, stop)
        if at >= end:
            break
    if at < end:
        out.append((at, end))
    return [(a, b) for a, b in out if b > a]


def attribute(gap, spans):
    """The name of the host span that covers most of ``gap``; ``spans`` are
    ``(name, start, end)``.  NO_SPAN where none overlaps it."""
    best, best_s = NO_SPAN, 0.0
    for name, start, end in spans:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > best_s:
            best, best_s = name, overlap
    return best


def top(pairs, n=10):
    """Sum ``(name, seconds)`` pairs by name; the ``n`` largest, as lists."""
    totals = {}
    for name, seconds in pairs:
        totals[name] = totals.get(name, 0.0) + seconds
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[name, seconds] for name, seconds in ranked[:n]]


# -- reading ------------------------------------------------------------------

def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def scope_map(hlo_text):
    """Instruction name -> ``fluid_<op>`` scope, from the compiled step's
    HLO text: ``lowering.dispatch`` lowers every Fluid op inside a named
    scope, which XLA keeps in each instruction's ``metadata={op_name=...}``
    (a fusion carries its root's).  The trace's events carry no metadata,
    only the instruction's name."""
    out = {}
    for line in hlo_text.splitlines():
        m = HLO_META.match(line)
        if m:
            scope = FLUID_SCOPE.search(m.group(2))
            if scope:
                out[m.group(1)] = scope.group(0)
    return out


def parse_op(text, scopes):
    """``(label, instruction name, custom-call target)`` of an XLA Ops
    event.  The label groups: the ``fluid_<op>`` scope where the compiled
    HLO names one for the instruction, else the instruction's name without
    its numbers, with the fusion kind where it has one."""
    name = HLO_NAME.match(text).group(1)
    target = HLO_TARGET.search(text)
    label = scopes.get(name)
    if label is None:
        kind = HLO_KIND.search(text)
        label = re.sub(r"\.\d+", "", name) or name
        if kind:
            label += "/" + kind.group(1)
    return label, name, target.group(1) if target else ""


class Reduced:
    """What the readers in ``layer_metrics/`` see of a trace."""

    def __init__(self, steps):
        self.steps = steps
        self.devices = {}       # device id -> {"busy_s", "window_s", "ops",
        #                          "async_ops", "begin", "end"}
        self.host_spans = []    # (name, start, end), the benchmark's own

    def _device(self, device=None):
        return self.devices[min(self.devices) if device is None else device]

    # ops of one device: (label, instruction, start, end, custom-call target)
    def ops(self, device=None):
        return self._device(device)["ops"]

    @property
    def busy_s(self):
        """Seconds an operation ran, averaged over the chips used."""
        return sum(d["busy_s"] for d in self.devices.values()) / \
            len(self.devices)

    @property
    def window_s(self):
        return max(d["window_s"] for d in self.devices.values())

    def idle_share(self):
        """On the device that idles most."""
        return max(1.0 - d["busy_s"] / d["window_s"]
                   for d in self.devices.values())

    def op_seconds(self, match, device=None, line="ops"):
        """Summed durations of the operations ``match(label, name,
        target)`` picks, on one device (``line="async_ops"``: of the
        asynchronous operations, start to done)."""
        return sum(end - start
                   for label, name, start, end, target
                   in self._device(device)[line]
                   if match(label, name, target))

    def custom_call_seconds(self, target, device=None):
        """Summed durations of the custom calls to ``target`` (Mosaic
        kernels are ``tpu_custom_call``), on one device."""
        return self.op_seconds(lambda label, name, t: t == target, device)

    def idle_gaps(self, device=None):
        d = self._device(device)
        busy = merge((s, e) for _, _, s, e, _ in d["ops"])
        return [(attribute(g, self.host_spans), g[1] - g[0])
                for g in gaps(busy, d["begin"], d["end"])]

    def breakdown(self):
        return {
            "device_ops": top((label, end - start)
                              for label, _, start, end, _ in self.ops()),
            "idle_gaps": top(self.idle_gaps()),
        }

    def summary(self):
        return "%d step(s); per device busy/window s %s; idle share %.4f" % (
            self.steps,
            {k: (round(d["busy_s"], 6), round(d["window_s"], 6))
             for k, d in self.devices.items()}, self.idle_share())


def _read_ops(line, scopes):
    ops = []
    for text, start, end in _events(line):
        label, name, target = parse_op(text, scopes)
        if name.split(".")[0] in CONTAINERS or end <= start:
            continue
        ops.append((label, name, start, end, target))
    return ops


def reduce_trace(path, device_ids, steps, scopes=None):
    """Reduce the trace at ``path`` over the chips ``device_ids``.
    ``steps``: steps dispatched in the stretch, used where the trace has no
    ``XLA Modules`` line to count them.  ``scopes``: ``scope_map`` of the
    compiled step.  The window of a device runs from its first operation's
    start to its last one's end: the pipeline's fill before and drain after
    the stretch are the harness's, not the program's."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {p.name: p for p in data.planes}
    out = Reduced(steps)
    for dev in device_ids:
        plane = planes.get("/device:TPU:%d" % dev)
        line = plane and _line(plane, OPS_LINE)
        ops = _read_ops(line, scopes or {}) if line else []
        if not ops:
            continue
        begin = min(o[2] for o in ops)
        end = max(o[3] for o in ops)
        async_line = _line(plane, ASYNC_LINE)
        out.devices[dev] = {
            "ops": ops, "begin": begin, "end": end, "window_s": end - begin,
            "busy_s": union_seconds((o[2], o[3]) for o in ops),
            "async_ops": _read_ops(async_line, scopes or {})
            if async_line else []}
        modules = _line(plane, MODULES_LINE)
        if modules:
            # the step is the program that ran most often in the stretch
            runs = {}
            for name, _, _ in _events(modules):
                runs[name] = runs.get(name, 0) + 1
            out.steps = max(runs.values())
    if not out.devices:
        raise RuntimeError("no device operation in the trace %s (planes: %s)"
                           % (path, sorted(planes)))
    host = planes.get(HOST_PLANE)
    for line in (host.lines if host else ()):
        out.host_spans += [(n, s, e) for n, s, e in _events(line)
                           if n.startswith(SPAN_PREFIX)]
    return out


def describe(path, limit=12):
    """A trace by hand: planes, lines, event counts and the first events
    with their stats.  ``python -m`` style helper for whoever writes a new
    reader: look before you code."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        lines.append("PLANE %s" % plane.name)
        for line in plane.lines:
            events = list(line.events)
            lines.append("  LINE %s: %d events" % (line.name, len(events)))
            for e in events[:limit]:
                lines.append("    %s start=%.0f dur=%.0f %s" % (
                    e.name, e.start_ns, e.duration_ns, dict(e.stats)))
    return "\n".join(lines)
