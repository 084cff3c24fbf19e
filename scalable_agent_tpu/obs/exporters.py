"""Exporters: Prometheus text exposition + the scalar JSONL/TensorBoard sink.

Two consumers, one registry (obs/registry.py):

- ``render_prometheus`` / ``PrometheusExporter`` — the standard text
  exposition format, written as a snapshot file a node-exporter-style
  textfile collector (or a human) can scrape.  Histograms render as
  summaries (quantile-labelled series + ``_sum``/``_count``).
- ``MetricsWriter`` — the training-metrics sink (TensorBoard if
  tensorboardX is importable, JSONL always), kept API-compatible with
  the 53-line original (reference metric names — ``episode_return``,
  ``dmlab30/*`` — pass through unchanged) and rebuilt on the registry:
  ``write_registry`` appends the registry snapshot to the same streams,
  so queue gauges and stage latencies land next to the losses.
"""

import json
import logging
import os
import queue
import re
import threading
import time
from typing import Dict, Optional

from scalable_agent_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from scalable_agent_tpu.obs.trace import (
    get_tracer,
    thread_usage,
    usage_since,
)

log = logging.getLogger(__name__)

__all__ = ["MetricsHTTPServer", "MetricsWriter", "PrometheusExporter",
           "render_prometheus"]

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")
_PREFIX = "impala_"


def _prom_name(name: str) -> str:
    """Registry names (slash-namespaced, reference-compatible) -> valid
    Prometheus metric names, uniformly prefixed."""
    sanitized = _NAME_RE.sub("_", name)
    if sanitized and sanitized[0].isdigit():
        sanitized = "_" + sanitized
    return _PREFIX + sanitized


def _fmt(value: float) -> str:
    if value != value:  # NaN
        return "NaN"
    if value in (float("inf"), float("-inf")):
        return "+Inf" if value > 0 else "-Inf"
    return repr(float(value))


def render_prometheus(registry: MetricsRegistry) -> str:
    """Registry -> Prometheus text exposition format (version 0.0.4)."""
    lines = []
    for instrument in registry.instruments():
        name = _prom_name(instrument.name)
        if instrument.help:
            lines.append(f"# HELP {name} {instrument.help}")
        if isinstance(instrument, Counter):
            lines.append(f"# TYPE {name} counter")
            lines.append(f"{name} {_fmt(instrument.value)}")
        elif isinstance(instrument, Gauge):
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {_fmt(instrument.value)}")
        elif isinstance(instrument, Histogram):
            lines.append(f"# TYPE {name} summary")
            for q, value in instrument.quantiles().items():
                lines.append(
                    f'{name}{{quantile="{q:g}"}} {_fmt(value)}')
            lines.append(f"{name}_sum {_fmt(instrument.sum)}")
            lines.append(f"{name}_count {instrument.count}")
    return "\n".join(lines) + "\n"


class PrometheusExporter:
    """Snapshot dumper: ``dump()`` atomically rewrites ``path`` with the
    current exposition text (rename, so a scraper never reads a torn
    file)."""

    def __init__(self, registry: MetricsRegistry, path: str):
        self._registry = registry
        self.path = path
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)

    def dump(self) -> str:
        text = render_prometheus(self._registry)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, self.path)
        return text


class MetricsHTTPServer:
    """A stdlib Prometheus scrape endpoint (``--metrics_http_port``).

    Serves the registry's CURRENT exposition text on ``/metrics`` (and
    ``/``) so scrapers don't have to poll ``<logdir>/metrics.prom`` off
    disk.  With a ``logdir``, two run-health routes ride the same
    already-open port so a remote rig needs no extra listener:
    ``/anomalies`` (the tail of ``anomalies.jsonl``, NDJSON — empty
    200 when the run has none) and ``/health`` (the ``obs.watch
    --once --json`` payload; 503 until the first prom snapshot lands).
    ``http.server.ThreadingHTTPServer`` on a daemon thread — rendering
    happens per request, never on the training hot path.  ``port=0``
    binds an ephemeral port (tests); read ``.port`` for the bound
    value.
    """

    ANOMALIES_TAIL_LINES = 64

    def __init__(self, registry: MetricsRegistry, port: int,
                 host: str = "0.0.0.0", logdir: str = ""):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        outer = self

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                route = self.path.split("?")[0]
                if route == "/anomalies" and outer._logdir:
                    outer_body = outer._anomalies_body()
                    self._send(outer_body, "application/x-ndjson")
                    return
                if route == "/health" and outer._logdir:
                    try:
                        payload = outer._health_payload()
                    except FileNotFoundError as exc:
                        # Detail goes in the body: the status line is
                        # latin-1 only and the diagnosis may not be.
                        self.send_error(503, "no metrics snapshot yet",
                                        str(exc))
                        return
                    except Exception as exc:
                        self.send_error(500, "health payload failed",
                                        str(exc))
                        return
                    self._send(json.dumps(payload).encode() + b"\n",
                               "application/json")
                    return
                if route not in ("/", "/metrics"):
                    self.send_error(404)
                    return
                try:
                    body = render_prometheus(outer._registry).encode()
                except Exception as exc:  # a dying gauge must 500, not die
                    self.send_error(500, str(exc))
                    return
                self._send(
                    body, "text/plain; version=0.0.4; charset=utf-8")

            def _send(self, body: bytes, content_type: str):
                self.send_response(200)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # no per-scrape stdout spam
                pass

        self._registry = registry
        self._logdir = logdir
        self._server = ThreadingHTTPServer((host, port), _Handler)
        self._server.daemon_threads = True
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name="metrics-http")
        self._thread.start()

    def _anomalies_body(self) -> bytes:
        """The anomalies.jsonl tail as NDJSON; an absent file is an
        empty (valid) stream, not an error — the run has no anomalies
        yet."""
        from scalable_agent_tpu.obs.health import ANOMALIES_JSONL

        path = os.path.join(self._logdir, ANOMALIES_JSONL)
        try:
            lines = open(path).read().splitlines()
        except OSError:
            return b""
        tail = lines[-self.ANOMALIES_TAIL_LINES:]
        return ("\n".join(tail) + "\n").encode() if tail else b""

    def _health_payload(self) -> dict:
        # Lazy import: watch pulls report/rounds parsing, none of which
        # belongs on the exporter's import path for plain scrapes.
        from scalable_agent_tpu.obs.watch import build_payload

        return build_payload(self._logdir)

    def close(self):
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc_info):
        self.close()
        return False


class MetricsWriter:
    """Scalar metrics writer: TensorBoard (if available) + JSONL.

    Reference metric names are kept for comparison runs (reference:
    experiment.py:423-425 learning_rate/total_loss summaries; :643-664
    per-level episode_return/episode_frames and DMLab-30 human-normalized
    scores; SF's tensorboardX usage, algorithms/utils/agent.py:195-238).

    A context manager (``with MetricsWriter(logdir) as writer:``) so the
    JSONL handle can't leak when the training loop raises.

    ``write`` hands the row to a writer thread and returns: the files
    are the thread's.  The caller is the training loop at its log
    publish, which has just waited for the device's queue to empty (the
    fetch of the newest update's metrics), so whatever time a write
    takes there the device idles through; and a write is not always
    120 ms: 2.5 s once a run on a TPU host, behind the page cache's
    write-back of the compile cache an in-process compile had left
    (``log/write``, PERF.md section 6, PR 32).  ``flush`` and ``close``
    wait for the rows handed over so far.
    """

    def __init__(self, logdir: str, flush_every_s: float = 5.0,
                 registry: Optional[MetricsRegistry] = None):
        os.makedirs(logdir, exist_ok=True)
        self._jsonl = open(os.path.join(logdir, "metrics.jsonl"), "a")
        self._flush_every_s = flush_every_s
        self._last_flush = 0.0
        self._registry = registry
        try:
            from tensorboardX import SummaryWriter

            self._tb = SummaryWriter(os.path.join(logdir, "summaries"))
        except ImportError:
            self._tb = None
        self._rows: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._drain, name="metrics-writer", daemon=True)
        self._thread.start()

    def write(self, step: int, scalars: Dict[str, float],
              wall_time: Optional[float] = None):
        # `is None`, not truthiness: an explicit wall_time=0.0 (epoch
        # zero in replayed/simulated-clock runs) must be preserved.
        if wall_time is None:
            wall_time = time.time()
        # The values become floats HERE: a device scalar is waited for
        # by the caller, as it always was, not by the thread.
        record = {"step": int(step), "time": wall_time}
        for key, value in scalars.items():
            record[key] = float(value)
        self._rows.put(record)

    def _drain(self):
        """The writer thread: every batch of rows that has come in is
        written under one span, ``writer/rows`` — this is the one thread
        that does file I/O and long pure-Python work beside the loop, so
        its spans say when it had the GIL."""
        while True:
            batch = [self._rows.get()]
            try:
                while True:
                    batch.append(self._rows.get_nowait())
            except queue.Empty:
                pass
            closing = None in batch
            rows = batch[:batch.index(None)] if closing else batch
            if rows:
                self._write_batch(rows)
            for _ in batch:
                self._rows.task_done()
            if closing:
                return

    def _write_batch(self, rows):
        tracer = get_tracer()
        args = before = None
        if tracer.enabled:
            args, before = {"rows": len(rows)}, thread_usage()
        with tracer.span("writer/rows", cat="log", args=args):
            for record in rows:
                try:
                    self._write_row(record)
                except Exception:  # noqa: BLE001  (a full disk ends no run)
                    log.exception("metrics writer: a row was not written")
            if before is not None:
                # what the batch cost THIS thread (obs/trace.py)
                args.update(usage_since(before))

    def _write_row(self, record: Dict[str, float]):
        if self._tb is not None:
            for key, value in record.items():
                if key not in ("step", "time"):
                    self._tb.add_scalar(key, value,
                                        global_step=record["step"],
                                        walltime=record["time"])
        self._jsonl.write(json.dumps(record) + "\n")
        now = time.monotonic()
        if now - self._last_flush > self._flush_every_s:
            with get_tracer().span("writer/flush", cat="log"):
                self._flush_files()
            self._last_flush = now

    def write_registry(self, step: int,
                       wall_time: Optional[float] = None,
                       prefix: str = "obs/"):
        """Append the registry snapshot (queue gauges, stage latencies,
        stall verdicts) as one row, namespaced so registry names can
        never collide with training metric names."""
        if self._registry is None:
            return
        self.write(step,
                   {prefix + k: v
                    for k, v in self._registry.snapshot().items()},
                   wall_time=wall_time)

    def _flush_files(self):
        self._jsonl.flush()
        if self._tb is not None:
            self._tb.flush()

    def flush(self):
        self._rows.join()
        self._flush_files()

    def close(self):
        if self._thread.is_alive():
            self._rows.put(None)
            self._thread.join()
        self._flush_files()
        self._jsonl.close()
        if self._tb is not None:
            self._tb.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc_info):
        self.close()
        return False
