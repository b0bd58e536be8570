#!/usr/bin/env python
"""Prometheus scrape endpoint over the fluid telemetry registry.

The registry (``fluid/telemetry.py``) already renders the Prometheus
text exposition format (``prometheus_text()`` / ``dump_prometheus()``);
this is the missing last inch the ROADMAP names — an actual HTTP
endpoint a Prometheus server can scrape, so serving/training metrics
(``serving_queue_depth``, ``serving_recompiles_total``, dispatch
histograms, ...) reach dashboards without file-shipping.

Embedded (a serving process typically wants this)::

    from tools.metrics_server import start_metrics_server
    srv = start_metrics_server(port=9184)     # port=0 = ephemeral
    print(srv.url)                            # http://127.0.0.1:9184/metrics
    ...
    srv.close()                               # graceful: finishes in-flight
                                              # scrapes, joins the thread

Standalone (scrape whatever the importing process registered)::

    python tools/metrics_server.py --port 9184

Routes: ``/metrics`` (text format, correct Content-Type),
``/aggregate`` (the pod/fleet view: this process's registry merged with
every sibling snapshot ``*.prom`` in ``--aggregate-dir`` — siblings
export via ``telemetry.dump_prometheus(dir + "/metrics.p<idx>.prom")``
and ONE process serves the whole pack to the scraper), ``/healthz``
(liveness).  ``/healthz`` is a REAL liveness probe: with the training
watchdog armed (``fluid/watchdog.py``), a stale last-progress stamp —
no dispatch/feed/checkpoint progress past the deadline — answers 503
``unhealthy`` naming the age and last phase, so the scrape endpoint
doubles as the k8s/LB probe for serving and training alike.  Unarmed
(or healthy) it stays the historical 200 ``ok``.  The server runs on a
daemon thread; ``close()`` is idempotent and bounded — it can never
park shutdown on a live scrape.
"""

import argparse
import glob
import os
import signal
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from paddle_tpu.fluid import telemetry, watchdog  # noqa: E402

_m_scrapes = telemetry.counter(
    "metrics_scrapes_total", "HTTP scrapes served, by route")


def _inject_process_label(line, label):
    """Stamp ``process="<label>"`` into one exposition sample line that
    does not already carry a process label (merged sources must never
    collide on identical label sets)."""
    if 'process="' in line:
        return line
    brace = line.find("{")
    space = line.find(" ")
    if space < 0:
        return line
    if 0 <= brace < space:
        return '%sprocess="%s",%s' % (line[:brace + 1], label,
                                      line[brace + 1:])
    return '%s{process="%s"}%s' % (line[:space], label, line[space:])


def aggregate_prometheus_texts(sources):
    """Merge several Prometheus text expositions (``[(label, text)]``)
    into one: ``# HELP``/``# TYPE`` lines deduped (first occurrence
    wins — every process registers the same instruments), every sample
    line stamped with a ``process`` label (the source's, when the
    sample doesn't already carry one).  Samples keep per-source order;
    the shared metadata dedup is what keeps scrapers from rejecting
    duplicate TYPE declarations."""
    meta_seen = set()
    out = []
    for label, text in sources:
        for line in text.splitlines():
            if not line.strip():
                continue
            if line.startswith("#"):
                if line not in meta_seen:
                    meta_seen.add(line)
                    out.append(line)
                continue
            out.append(_inject_process_label(line, label))
    return "\n".join(out) + "\n"


def _prom_file_label(path):
    """Process label of a sibling snapshot file: the ``<idx>`` of a
    ``*.p<idx>.prom`` name, else the basename sans extension."""
    base = os.path.basename(path)
    stem = base[:-5] if base.endswith(".prom") else base
    head, dot, tail = stem.rpartition(".p")
    if dot and tail.isdigit():
        return tail
    return stem


def aggregate_body(aggregate_dir):
    """The ``/aggregate`` exposition: this process's live registry plus
    every sibling ``*.prom`` snapshot under ``aggregate_dir`` (written
    atomically by ``telemetry.dump_prometheus`` — a torn read is
    impossible).  Unreadable siblings are skipped: the aggregate must
    answer even while a sibling is mid-restart."""
    own = telemetry.process_label()
    sources = [("self" if own is None else str(own),
                telemetry.prometheus_text())]
    if aggregate_dir:
        for path in sorted(glob.glob(os.path.join(aggregate_dir,
                                                  "*.prom"))):
            try:
                with open(path, "r", encoding="utf-8") as f:
                    text = f.read()
            except OSError:
                continue
            sources.append((_prom_file_label(path), text))
    return aggregate_prometheus_texts(sources)


def healthz_body():
    """(status_code, body) of the liveness probe: 200 ``ok`` while the
    watchdog is unarmed or fed; 503 naming the staleness once the
    last-progress stamp blows the (timeout + extension) deadline."""
    h = watchdog.health()
    if h["healthy"]:
        return 200, "ok\n"
    return 503, ("unhealthy: no progress for %.1fs (deadline %.1fs, "
                 "last phase %s)\n"
                 % (h["age_s"] if h["age_s"] is not None else -1.0,
                    h["budget_s"] if h["budget_s"] is not None else -1.0,
                    h["phase"] or "unknown"))


class _Handler(BaseHTTPRequestHandler):
    # scrapers poll every few seconds; stderr access logs would drown
    # the training/serving process's real output
    def log_message(self, fmt, *args):
        pass

    def _send(self, code, body, content_type="text/plain; charset=utf-8"):
        data = body.encode("utf-8") if isinstance(body, str) else body
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        if path in ("/", "/metrics"):
            _m_scrapes.inc(route="metrics")
            self._send(200, telemetry.prometheus_text(),
                       telemetry.PROMETHEUS_CONTENT_TYPE)
        elif path == "/aggregate":
            _m_scrapes.inc(route="aggregate")
            self._send(200, aggregate_body(
                getattr(self.server, "aggregate_dir", None)),
                telemetry.PROMETHEUS_CONTENT_TYPE)
        elif path == "/healthz":
            _m_scrapes.inc(route="healthz")
            self._send(*healthz_body())
        else:
            self._send(404, "not found: %s (routes: /metrics, "
                       "/aggregate, /healthz)\n" % path)


class MetricsServer:
    """A running scrape endpoint: ``.host``/``.port``/``.url`` plus a
    graceful, idempotent ``close()``."""

    def __init__(self, host="127.0.0.1", port=0, aggregate_dir=None):
        # ThreadingHTTPServer: a slow scraper can never block /healthz;
        # daemon_threads so a straggling connection can't wedge exit
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        # sibling-snapshot directory served by /aggregate (the handler
        # reads it off self.server — per-server state, not class state)
        self._httpd.aggregate_dir = aggregate_dir
        self._httpd.daemon_threads = True
        self.host, self.port = self._httpd.server_address[:2]
        self.url = "http://%s:%d/metrics" % (self.host, self.port)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="metrics-server",
            kwargs={"poll_interval": 0.1}, daemon=True)
        self._thread.start()
        self._closed = False

    def close(self, timeout=5.0):
        """Graceful shutdown: stop accepting, finish in-flight scrapes,
        join the serve thread, release the port.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._httpd.shutdown()
        self._thread.join(timeout=timeout)
        self._httpd.server_close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def start_metrics_server(port=0, host="127.0.0.1", aggregate_dir=None):
    """Start the scrape endpoint on a daemon thread; ``port=0`` binds an
    ephemeral port (read it back from ``.port`` — the port-0 test
    contract).  ``aggregate_dir`` enables the ``/aggregate`` merge of
    sibling ``*.prom`` snapshots.  Returns a :class:`MetricsServer`."""
    return MetricsServer(host=host, port=port,
                         aggregate_dir=aggregate_dir)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Prometheus scrape endpoint over fluid telemetry")
    ap.add_argument("--port", type=int, default=9184)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--aggregate-dir", default=None,
                    help="serve /aggregate over sibling *.prom "
                         "snapshots in this directory")
    args = ap.parse_args(argv)
    srv = start_metrics_server(port=args.port, host=args.host,
                               aggregate_dir=args.aggregate_dir)
    # the handlers stand before the line that tells a parent it may signal
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    print("serving metrics on %s (SIGTERM/SIGINT to stop)" % srv.url,
          flush=True)
    stop.wait()
    srv.close()
    print("metrics server stopped", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
