"""Registry exporters — Prometheus text exposition + periodic JSONL sink.

``PrometheusExporter`` serves ``GET /metrics`` off a stdlib
``ThreadingHTTPServer`` daemon thread (enable via ``FLAGS_metrics_port``;
``-1`` binds an ephemeral port and ``.port`` reveals it — the CI smoke
uses that).  ``JsonlSink`` appends one timestamped registry snapshot per
interval to a per-``process_index`` file — the offline/multihost lane —
and :func:`merge_jsonl` collates the per-process files on the head node.
"""
from __future__ import annotations

import glob
import http.server
import json
import math
import os
import threading
import time
from typing import List, Optional, Sequence

from ..framework.locking import OrderedLock
from .metrics import MetricRegistry, default_registry

__all__ = [
    "render_prometheus", "PrometheusExporter", "JsonlSink",
    "process_jsonl_path", "merge_jsonl",
]


def _fmt_value(v: float) -> str:
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _fmt_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(
        '{}="{}"'.format(k, str(v).replace("\\", r"\\").replace(
            '"', r"\"").replace("\n", r"\n"))
        for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def render_prometheus(registry: Optional[MetricRegistry] = None) -> str:
    """The registry in Prometheus text exposition format 0.0.4
    (``# HELP`` / ``# TYPE`` headers, cumulative ``_bucket{le=...}`` /
    ``_sum`` / ``_count`` for histograms)."""
    reg = registry or default_registry()
    lines: List[str] = []
    for m in sorted(reg.collect(), key=lambda m: m.name):
        if m.help:
            lines.append(f"# HELP {m.name} " +
                         m.help.replace("\\", r"\\").replace("\n", r"\n"))
        lines.append(f"# TYPE {m.name} {m.type}")
        for name, labels, value in m.expose():
            lines.append(f"{name}{_fmt_labels(labels)} {_fmt_value(value)}")
    return "\n".join(lines) + ("\n" if lines else "")


class _Handler(http.server.BaseHTTPRequestHandler):
    def do_GET(self):
        if self.path.split("?")[0] not in ("/", "/metrics"):
            self.send_error(404)
            return
        body = render_prometheus(self.server._registry).encode()
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # scrapes must not spam stderr
        pass


class PrometheusExporter:
    """Text exposition on ``http://{addr}:{port}/metrics``.

    ``port <= 0`` binds an ephemeral port; read the bound one back from
    ``.port``.  The server runs on a daemon thread and every request gets
    its own handler thread, so a slow scraper never blocks training."""

    def __init__(self, registry: Optional[MetricRegistry] = None,
                 port: int = 0, addr: str = "127.0.0.1"):
        self._registry = registry or default_registry()
        self._server = http.server.ThreadingHTTPServer(
            (addr, max(int(port), 0)), _Handler)
        self._server._registry = self._registry
        self._server.daemon_threads = True
        self.addr = addr
        self.port = int(self._server.server_address[1])
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name=f"metrics-exporter:{self.port}", daemon=True)
        self._thread.start()

    @property
    def url(self) -> str:
        return f"http://{self.addr}:{self.port}/metrics"

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _process_index() -> int:
    # gang-aware: under the file gang transport (CPU multi-process pods)
    # jax itself only sees the local host, so the launch env carries the
    # rank — distributed.env.process_index resolves both cases
    try:
        from ..distributed.env import process_index

        return int(process_index())
    except Exception:
        try:
            import jax

            return int(jax.process_index())
        except Exception:
            return 0


def process_jsonl_path(base: str, process_index: Optional[int] = None) -> str:
    """Per-process sink path: ``metrics.jsonl`` →
    ``metrics.p<idx>.jsonl`` — multihost runs write one file each and
    :func:`merge_jsonl` collates them on the head."""
    idx = _process_index() if process_index is None else int(process_index)
    root, ext = os.path.splitext(base)
    return f"{root}.p{idx}{ext or '.jsonl'}"


class JsonlSink:
    """Append one ``{"ts":..., "process_index":..., "metrics": {...}}``
    snapshot line per ``interval_s`` to the per-process file.  ``close()``
    writes one final snapshot so short runs still leave a record."""

    def __init__(self, path: str, registry: Optional[MetricRegistry] = None,
                 interval_s: float = 10.0,
                 process_index: Optional[int] = None):
        self._registry = registry or default_registry()
        self._interval = max(float(interval_s), 0.05)
        self._pidx = (_process_index() if process_index is None
                      else int(process_index))
        self.path = process_jsonl_path(path, self._pidx)
        parent = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(parent, exist_ok=True)
        self._stop = threading.Event()
        self._lock = OrderedLock("JsonlSink._lock")
        self._thread = threading.Thread(
            target=self._run, name="metrics-jsonl", daemon=True)
        self._thread.start()

    def write_now(self) -> None:
        record = {"ts": time.time(), "process_index": self._pidx,
                  "metrics": self._registry.snapshot()}
        line = json.dumps(record) + "\n"
        with self._lock, open(self.path, "a") as f:
            f.write(line)

    def _run(self):
        while not self._stop.wait(self._interval):
            try:
                self.write_now()
            except Exception:
                pass  # a full disk must not take down the training loop

    def close(self) -> None:
        if self._stop.is_set():
            return
        self._stop.set()
        self._thread.join(timeout=5.0)
        try:
            self.write_now()
        except Exception:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def merge_jsonl(base_or_paths, out_path: Optional[str] = None) -> List[dict]:
    """Collate per-process sink files (head-node helper).

    ``base_or_paths`` — the base path given to :class:`JsonlSink` (globs
    ``<root>.p*<ext>``) or an explicit list of files.  Crash-tolerant: a
    process killed mid-write leaves a truncated (unparseable) trailing
    line, which is skipped rather than poisoning the whole merge.
    Returns records in a deterministic order — sorted by timestamp with
    process index (then input position) as tie-breaker; writes them back
    out as JSONL when ``out_path`` is given."""
    if isinstance(base_or_paths, (list, tuple)):
        paths: Sequence[str] = base_or_paths
    else:
        root, ext = os.path.splitext(base_or_paths)
        paths = sorted(glob.glob(f"{root}.p*{ext or '.jsonl'}"))
    records: List[dict] = []
    for p in paths:
        try:
            with open(p) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        records.append(json.loads(line))
                    except ValueError:
                        continue  # truncated/corrupt line: skip, keep rest
        except OSError:
            continue
    records.sort(key=lambda r: (
        r.get("ts", 0.0) if isinstance(r, dict) else 0.0,
        r.get("process_index", r.get("pid", 0)) if isinstance(r, dict)
        else 0))
    if out_path:
        with open(out_path, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
    return records
