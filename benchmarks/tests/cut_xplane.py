#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to a fixture small enough to commit.

    python3 benchmarks/tests/cut_xplane.py <in.xplane.pb> <out.xplane.pb>

Keeps, of every ``/device:TPU:<n>`` plane, the first STEPS steps of the
lines the reduction reads, and of each step the first HEAD and last TAIL
operations plus some of its Mosaic custom calls and all-reduces (the hole
in the middle is an idle gap for the tests to attribute); of the host plane
the benchmark's own ``bench.*`` spans.  Needs the XSpace protobuf that
TensorFlow ships (a tool for whoever records a new fixture, not a test).
"""

import sys

STEPS, HEAD, TAIL, SPECIAL = 2, 60, 40, 12
LINES = ("Steps", "XLA Modules", "XLA Ops", "Async XLA Ops")


def cut(space):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    out = xplane_pb2.XSpace()
    t_end = 0
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        steps = [ln for ln in plane.lines if ln.name == "Steps"][0]
        spans = [(ln_ts(steps, e), ln_ts(steps, e) + e.duration_ps)
                 for e in list(steps.events)[:STEPS]]
        t_end = max(t_end, spans[-1][1])
        for line in plane.lines:
            if line.name not in LINES:
                continue
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            for lo, hi in spans:
                inside = [e for e in line.events
                          if lo <= ln_ts(line, e) < hi]
                keep, special = [], 0
                for i, e in enumerate(inside):
                    text = plane.event_metadata[e.metadata_id].name
                    wanted = "tpu_custom_call" in text or \
                        text.startswith("%all-reduce")
                    if i < HEAD or i >= len(inside) - TAIL or \
                            line.name in ("Steps", "XLA Modules"):
                        keep.append(e)
                    elif wanted and special < SPECIAL:
                        keep.append(e)
                        special += 1
                for e in keep:
                    ne = nl.events.add()
                    ne.CopyFrom(e)
                    del ne.stats[:]
                    new.event_metadata[e.metadata_id].CopyFrom(
                        plane.event_metadata[e.metadata_id])
    for plane in space.planes:
        if plane.name != "/host:CPU":
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            events = [e for e in line.events
                      if plane.event_metadata[e.metadata_id].name
                      .startswith("bench.") and ln_ts(line, e) < t_end]
            if not events:
                continue
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            for e in events:
                ne = nl.events.add()
                ne.CopyFrom(e)
                del ne.stats[:]
                new.event_metadata[e.metadata_id].CopyFrom(
                    plane.event_metadata[e.metadata_id])
    return out


def ln_ts(line, event):
    """Event start in picoseconds on the trace's clock."""
    return line.timestamp_ns * 1000 + event.offset_ps


def main(argv):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(argv[1], "rb") as f:
        space.ParseFromString(f.read())
    with open(argv[2], "wb") as f:
        f.write(cut(space).SerializeToString())


if __name__ == "__main__":
    main(sys.argv)
