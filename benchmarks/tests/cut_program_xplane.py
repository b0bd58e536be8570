#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to a fixture that keeps the program's
own spans.

    python3 benchmarks/tests/cut_program_xplane.py <in.xplane.pb> \
        <out.xplane.pb> [<step_scopes.json> <out_scopes.json>]

``cut_xplane.cut`` keeps the device lines of the first steps and the
benchmark's ``bench.*`` spans, all without stats; here with enough of a step's
Mosaic calls kept that the backward's kernels are among them (a step runs its
forward's first).  This adds, of every line of the host planes, the program's
``fluid.*`` events that begin before the kept device steps end, WITH their
stats: a span's labels (``step``, ``batch``, ``bytes``, ...) are what its
readers match spans by.  The optional pair cuts a dump of the same run's
``fluid.profiler.step_scopes()`` to the instructions the fixture holds.  Needs
the XSpace protobuf that TensorFlow ships (a tool for whoever records a new
fixture, not a test).
"""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cut_xplane    # noqa: E402

PREFIX = "fluid."
MOSAIC_CALLS_KEPT = 36      # a step of BERT-base runs 48: 24 + 12 + 12


def cut(space):
    cut_xplane.SPECIAL = MOSAIC_CALLS_KEPT
    out = cut_xplane.cut(space)
    t_end = max(cut_xplane.ln_ts(line, e) + e.duration_ps
                for plane in out.planes for line in plane.lines
                if line.name == "Steps" for e in line.events)
    kept = {plane.name: plane for plane in out.planes}
    for plane in space.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            events = [e for e in line.events
                      if plane.event_metadata[e.metadata_id].name
                      .startswith(PREFIX)
                      and cut_xplane.ln_ts(line, e) < t_end]
            if not events:
                continue
            new = kept.get(plane.name)
            if new is None:
                new = kept[plane.name] = out.planes.add(id=plane.id,
                                                        name=plane.name)
            lines = [ln for ln in new.lines if ln.id == line.id]
            nl = lines[0] if lines else new.lines.add(
                id=line.id, name=line.name, timestamp_ns=line.timestamp_ns)
            for e in events:
                nl.events.add().CopyFrom(e)
                new.event_metadata[e.metadata_id].CopyFrom(
                    plane.event_metadata[e.metadata_id])
                for stat in e.stats:
                    ids = [stat.metadata_id]
                    if stat.WhichOneof("value") == "ref_value":
                        ids.append(stat.ref_value)
                    for i in ids:
                        new.stat_metadata[i].CopyFrom(plane.stat_metadata[i])
    return out


def cut_scopes(scopes, space):
    """``scopes`` of the instructions that name an event of a device line."""
    held = {re.match(r"%?([^\s=]+)", plane.event_metadata[e.metadata_id].name)
            .group(1)
            for plane in space.planes if plane.name.startswith("/device:")
            for line in plane.lines for e in line.events}
    return {k: v for k, v in scopes.items() if k in held}


def main(argv):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(argv[1], "rb") as f:
        space.ParseFromString(f.read())
    out = cut(space)
    with open(argv[2], "wb") as f:
        f.write(out.SerializeToString())
    if len(argv) > 4:
        with open(argv[3]) as f:
            scopes = cut_scopes(json.load(f), out)
        with open(argv[4], "w") as f:
            json.dump(scopes, f, indent=0, sort_keys=True)


if __name__ == "__main__":
    main(sys.argv)
