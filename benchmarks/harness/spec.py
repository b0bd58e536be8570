"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A cell is one entry of ``workloads``: a configuration (``configs/<config>
.json``, which names its builder module beside it), a traffic mix
(``traffic/<traffic>.json``, which names its ``wrap``: ``wraps/<wrap>.py``)
and the chips it needs.  A per-layer metric is ``layer_metrics/<name>.py``.
Nothing here knows a name: a later PR adds files and entries.
"""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import one file by path (a name with a dot or a dash is not
    a Python identifier, and nothing is put on ``sys.path``)."""
    name = "bench_" + "".join(
        c if c.isalnum() else "_"
        for c in os.path.relpath(os.path.splitext(path)[0], BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One workload, resolved: what to build, feed and read."""

    def __init__(self, name, bench, bench_dir=BENCH_DIR):
        entries = [w for w in bench["workloads"] if w["name"] == name]
        if len(entries) != 1:
            raise SystemExit("no workload %r in BENCHMARK.json (has: %s)" % (
                name, ", ".join(w["name"] for w in bench["workloads"])))
        entry = entries[0]
        self.name = name
        self.chips = entry["chips"]
        files = {c["name"]: c["file"] for c in bench["configs"]}
        self.config = load_json(os.path.join(os.path.dirname(bench_dir),
                                             files[entry["config"]]))
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", entry["traffic"] + ".json"))
        self.builder = load_module(os.path.join(
            bench_dir, "configs", self.config["builder"]))
        self.wrap = load_module(os.path.join(
            bench_dir, "wraps", self.traffic["wrap"] + ".py"))
        self.end_to_end = [m["name"] for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [
            (m["name"], os.path.join(bench_dir, "layer_metrics",
                                     m["name"] + ".py"))
            for m in bench["per_layer"]
            if name in m.get("workloads", [name])]
        self.units = {m["name"]: m["unit"]
                      for m in bench["end_to_end"] + bench["per_layer"]}

    def params(self, tiny=False):
        """The configuration's sizes with the traffic's on top; ``tiny``
        (CPU tests only) puts the configuration's tiny set on top of
        both."""
        params = {**self.config, **self.traffic}
        if tiny:
            params.update(self.config["tiny"])
        return params


def load_cell(name, bench_path=None):
    bench_path = bench_path or os.path.join(REPO_DIR, "BENCHMARK.json")
    return Cell(name, load_json(bench_path),
                os.path.join(os.path.dirname(os.path.abspath(bench_path)),
                             "benchmarks"))
