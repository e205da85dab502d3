"""paddle_tpu.observability — unified metrics registry + exporters.

One call turns the framework's four telemetry islands (trace_events bus,
profiler host table, ServingMetrics snapshots, monitor stat counters)
into a scrapable surface::

    import paddle_tpu
    paddle_tpu.observability.enable(port=9400, jsonl="/tmp/metrics.jsonl")
    # ... train / serve ...
    # curl http://127.0.0.1:9400/metrics

or set ``FLAGS_metrics_port`` / ``FLAGS_metrics_jsonl`` and let the first
``Executor`` construction enable it (``maybe_enable_from_flags``).

``enable`` installs: the trace_events → registry bridge (every
``executor_cache`` / ``serving`` / ``resilience`` / ``autotune`` /
``steptrace`` snapshot becomes labeled gauges), the monitor/profiler
pull collectors, per-step training telemetry (``steptrace``), and —
when configured — the Prometheus HTTP endpoint and the periodic JSONL
sink.  ``disable()`` tears all of it down; with nothing enabled every
hot-path hook is a single falsy check.
"""
from __future__ import annotations

import threading
from typing import Optional

from . import exporters, metrics, slo, steptrace, tracing  # noqa: F401
from .exporters import (  # noqa: F401
    JsonlSink,
    PrometheusExporter,
    merge_jsonl,
    render_prometheus,
)
from .metrics import (  # noqa: F401
    DEFAULT_MS_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    default_registry,
    install_bridge,
    uninstall_bridge,
)
from .slo import Objective, ScaleSignal, SloEngine  # noqa: F401
from .tracing import TraceContext, Tracer  # noqa: F401

__all__ = [
    "MetricRegistry", "Counter", "Gauge", "Histogram",
    "DEFAULT_MS_BUCKETS", "default_registry", "render_prometheus",
    "PrometheusExporter", "JsonlSink", "merge_jsonl",
    "install_bridge", "uninstall_bridge",
    "enable", "disable", "enabled", "status", "maybe_enable_from_flags",
    "Objective", "ScaleSignal", "SloEngine", "TraceContext", "Tracer",
    "metrics", "exporters", "slo", "steptrace", "tracing",
]

_lock = threading.RLock()
_exporter: Optional[PrometheusExporter] = None
_sink: Optional[JsonlSink] = None
_enabled = False
_section_registered = False


def _register_summary_section():
    """Hook the "Training telemetry" block into profiler.summary() —
    once per process; the renderer returns "" while telemetry is off, so
    registering is free for profiler-only users."""
    global _section_registered
    if _section_registered:
        return
    from .. import profiler

    profiler.register_summary_section(steptrace.render_summary_section)
    _section_registered = True


def enable(port: Optional[int] = None, jsonl: Optional[str] = None,
           registry: Optional[MetricRegistry] = None,
           jsonl_interval_s: Optional[float] = None,
           trace: bool = False) -> MetricRegistry:
    """Turn observability on (idempotent; later calls can add an exporter
    or sink a first call didn't configure).

    ``port`` — Prometheus endpoint: ``None``/``0`` = no endpoint, ``-1``
    = bind an ephemeral port (read it back from ``status()``), else the
    TCP port.  ``jsonl`` — base path of the periodic JSONL sink (written
    as ``<base>.p<process_index>.jsonl``); ``None``/empty = no sink.
    ``trace`` — also enable end-to-end request tracing
    (``tracing.enable()`` works standalone too).
    """
    global _exporter, _sink, _enabled
    from ..framework.flags import flag

    with _lock:
        reg = registry or default_registry()
        metrics.install_bridge(reg)
        metrics.install_standard_collectors(reg)
        steptrace.install(reg)
        _register_summary_section()
        _enabled = True
        if trace:
            tracing.enable()
        if port and _exporter is None:
            _exporter = PrometheusExporter(reg, port=max(int(port), 0))
        if jsonl and _sink is None:
            interval = (float(flag("metrics_jsonl_interval_s"))
                        if jsonl_interval_s is None
                        else float(jsonl_interval_s))
            _sink = JsonlSink(jsonl, reg, interval_s=interval)
        return reg


def disable() -> None:
    """Tear down the bridge, telemetry, tracing, endpoint and sink (the
    default registry keeps its accumulated values; pass a fresh registry
    to the next ``enable`` for a clean slate)."""
    global _exporter, _sink, _enabled
    with _lock:
        uninstall_bridge()
        steptrace.uninstall()
        tracing.disable()
        if _exporter is not None:
            _exporter.close()
            _exporter = None
        if _sink is not None:
            _sink.close()
            _sink = None
        _enabled = False


def enabled() -> bool:
    return _enabled


def status() -> dict:
    with _lock:
        tr = tracing.active()
        return {
            "enabled": _enabled,
            "bridge": metrics.bridge_installed(),
            "steptrace": steptrace.active() is not None,
            "tracing": tr.stats() if tr is not None else None,
            "port": _exporter.port if _exporter is not None else None,
            "url": _exporter.url if _exporter is not None else None,
            "jsonl": _sink.path if _sink is not None else None,
        }


def maybe_enable_from_flags() -> bool:
    """Flag-driven auto-enable, called from ``Executor.__init__`` (the
    same pattern as the persistent compilation cache): when
    ``FLAGS_metrics_port`` is nonzero, ``FLAGS_metrics_jsonl`` is
    non-empty, or ``FLAGS_trace_requests`` is set, enable with those
    settings.  Cheap no-op otherwise."""
    from ..framework.flags import flag

    port = int(flag("metrics_port"))
    jsonl = flag("metrics_jsonl")
    trace = bool(flag("trace_requests"))
    if not port and not jsonl and not trace:
        return False
    with _lock:
        if trace and not port and not jsonl:
            tracing.enable()  # tracing alone: no registry machinery
        else:
            enable(port=port or None, jsonl=jsonl or None, trace=trace)
    return True
