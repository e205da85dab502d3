"""Typed, labeled metric registry — the single observability sink.

The repo grew four disjoint telemetry islands: the ``trace_events`` bus
(latest-value snapshots per family), the profiler's host event table,
``ServingMetrics`` snapshots, and ``framework.monitor`` stat counters.
This module unifies them behind one Prometheus-shaped registry —
Counter / Gauge / Histogram with fixed buckets, each optionally labeled —
WITHOUT rewriting any producer:

* :func:`install_bridge` subscribes one observer to ``trace_events`` and
  re-publishes every numeric field of the ``executor_cache`` / ``serving``
  / ``resilience`` / ``autotune`` / ``steptrace`` snapshot families as
  labeled gauges;
* pull-time collectors re-read ``monitor.all_stats()``, the profiler's
  dropped-span count, and the bus's dropped-notification count on every
  :meth:`MetricRegistry.collect`, so those live counters need no push
  hook at all.

``exporters.render_prometheus`` turns a registry into text exposition;
``exporters.JsonlSink`` snapshots it to disk.  With nothing enabled no
registry exists on any hot path — Executor/serving publish sites stay the
single falsy checks they already were.
"""
from __future__ import annotations

import math
import re
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricRegistry",
    "DEFAULT_MS_BUCKETS", "default_registry", "set_default_registry",
    "install_bridge", "uninstall_bridge", "bridge_installed",
]

#: latency buckets (milliseconds) shared by every *_ms histogram — fixed
#: so text exposition stays aggregatable across processes
DEFAULT_MS_BUCKETS = (0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0,
                      200.0, 500.0, 1000.0, 2000.0, 5000.0, 10000.0,
                      float("inf"))

_NAME_OK = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SANITIZE = re.compile(r"[^a-zA-Z0-9_:]")

#: per-metric label-cardinality cap — a runaway label (request ids,
#: user strings) otherwise grows ``_Metric._children`` without bound
DEFAULT_MAX_CHILDREN = 256

#: reserved child key for label sets past the cap; rendered with every
#: label value "other" plus ``overflow="true"``
_OVERFLOW_KEY = ("__overflow__",)

#: registry counter that tallies label sets routed to the overflow child
DROPPED_LABELS_COUNTER = "paddle_tpu_metric_labels_dropped_total"


def sanitize_name(name: str) -> str:
    """Coerce an arbitrary key into a legal Prometheus metric name."""
    name = _SANITIZE.sub("_", str(name))
    if not name or not _NAME_OK.match(name):
        name = "_" + name
    return name


class _Child:
    """One (metric, label-values) time series."""

    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0.0


class _CounterChild(_Child):
    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError(f"counter increment must be >= 0, got {n}")
        with self._lock:
            self.value += n


class _GaugeChild(_Child):
    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)


class _HistogramChild:
    __slots__ = ("_lock", "buckets", "counts", "sum", "count")

    def __init__(self, buckets: Sequence[float]):
        self._lock = threading.Lock()
        self.buckets = buckets
        self.counts = [0] * len(buckets)
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self.sum += v
            self.count += 1
            for i, le in enumerate(self.buckets):
                if v <= le:
                    self.counts[i] += 1
                    break


class _Metric:
    """Base: a named family of children keyed by label-value tuples."""

    type: str = ""

    def __init__(self, name: str, help_str: str, labelnames: Sequence[str],
                 *, max_children: int = DEFAULT_MAX_CHILDREN,
                 overflow_cb: Optional[Callable[[str], None]] = None):
        if not _NAME_OK.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        self.name = name
        self.help = help_str
        self.labelnames = tuple(labelnames)
        self._max_children = int(max_children)  # <= 0 means unbounded
        self._overflow_cb = overflow_cb
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], object] = {}
        if not self.labelnames:
            self._children[()] = self._new_child()

    def _new_child(self):
        raise NotImplementedError

    def labels(self, *values, **kw):
        if kw:
            if values:
                raise ValueError("pass label values positionally OR by name")
            values = tuple(str(kw[n]) for n in self.labelnames)
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, got "
                f"{values!r}")
        overflowed = False
        with self._lock:
            child = self._children.get(values)
            if child is None:
                if (self._max_children > 0
                        and len(self._children) >= self._max_children):
                    # cap hit: route this NEW label set to the shared
                    # overflow child so the family stays bounded
                    overflowed = True
                    child = self._children.get(_OVERFLOW_KEY)
                    if child is None:
                        child = self._children[_OVERFLOW_KEY] = \
                            self._new_child()
                else:
                    child = self._children[values] = self._new_child()
        if overflowed and self._overflow_cb is not None:
            # outside our lock: the callback increments a registry
            # counter, which takes the registry + counter locks
            self._overflow_cb(self.name)
        return child

    def _label_dict(self, values: Tuple[str, ...]) -> Dict[str, str]:
        if values == _OVERFLOW_KEY:
            labels = {n: "other" for n in self.labelnames}
            labels["overflow"] = "true"
            return labels
        return dict(zip(self.labelnames, values))

    def _default(self):
        if self.labelnames:
            raise ValueError(
                f"{self.name} is labeled {self.labelnames}; call "
                f".labels(...) first")
        return self._children[()]

    def children(self) -> List[Tuple[Tuple[str, ...], object]]:
        with self._lock:
            return list(self._children.items())

    def expose(self) -> List[Tuple[str, Dict[str, str], float]]:
        """``(sample_name, labels, value)`` triples for text exposition."""
        out = []
        for values, child in self.children():
            out.append((self.name, self._label_dict(values), child.value))
        return out


class Counter(_Metric):
    type = "counter"

    def _new_child(self):
        return _CounterChild()

    def inc(self, n: float = 1.0) -> None:
        self._default().inc(n)


class Gauge(_Metric):
    type = "gauge"

    def _new_child(self):
        return _GaugeChild()

    def set(self, v: float) -> None:
        self._default().set(v)

    def inc(self, n: float = 1.0) -> None:
        self._default().inc(n)


class Histogram(_Metric):
    type = "histogram"

    def __init__(self, name, help_str, labelnames,
                 buckets: Sequence[float] = DEFAULT_MS_BUCKETS, **kw):
        bs = sorted(float(b) for b in buckets)
        if not bs or bs != sorted(set(bs)):
            raise ValueError(f"{name}: buckets must be distinct, got "
                             f"{buckets!r}")
        if not math.isinf(bs[-1]):
            bs.append(float("inf"))
        self.buckets = tuple(bs)
        super().__init__(name, help_str, labelnames, **kw)

    def _new_child(self):
        return _HistogramChild(self.buckets)

    def observe(self, v: float) -> None:
        self._default().observe(v)

    def expose(self):
        out = []
        for values, child in self.children():
            labels = self._label_dict(values)
            cum = 0
            for le, n in zip(child.buckets, child.counts):
                cum += n
                le_s = "+Inf" if math.isinf(le) else format(le, "g")
                out.append((f"{self.name}_bucket",
                            {**labels, "le": le_s}, float(cum)))
            out.append((f"{self.name}_sum", labels, child.sum))
            out.append((f"{self.name}_count", labels, float(child.count)))
        return out


class MetricRegistry:
    """Get-or-create metric families + pull-time collectors.

    Re-requesting a name returns the existing family; a type or labelname
    conflict raises (two subsystems silently sharing one name with
    different meanings is the bug this catches).
    """

    def __init__(self, max_label_children: int = DEFAULT_MAX_CHILDREN):
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}
        self._collectors: List[Callable] = []
        self._max_label_children = int(max_label_children)

    def _get_or_create(self, cls, name, help_str, labelnames, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if type(m) is not cls or m.labelnames != tuple(labelnames):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{m.type} with labels {m.labelnames}")
                return m
            kw.setdefault("max_children", self._max_label_children)
            kw.setdefault("overflow_cb", self._count_dropped_labels)
            m = cls(name, help_str, labelnames, **kw)
            self._metrics[name] = m
            return m

    def _count_dropped_labels(self, metric_name: str) -> None:
        # the drop counter itself is uncapped and has no overflow_cb —
        # a capped-or-recursing accountant would hide the drops it counts
        self._get_or_create(
            Counter, DROPPED_LABELS_COUNTER,
            "label sets routed to the overflow child past the per-metric "
            "cardinality cap", ("metric",),
            max_children=0, overflow_cb=None,
        ).labels(metric_name).inc()

    def get(self, name: str) -> Optional[_Metric]:
        """The already-registered family (no create) — readers like the
        SLO engine use this so they never conjure empty metrics."""
        with self._lock:
            return self._metrics.get(name)

    def counter(self, name: str, help_str: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help_str, labelnames)

    def gauge(self, name: str, help_str: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help_str, labelnames)

    def histogram(self, name: str, help_str: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_MS_BUCKETS
                  ) -> Histogram:
        return self._get_or_create(Histogram, name, help_str, labelnames,
                                   buckets=buckets)

    def register_collector(self, fn: Callable) -> Callable:
        """``fn(registry)`` runs at every :meth:`collect` — the pull seam
        for live counters that have no push hook (monitor stats, profiler
        drop counts)."""
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)
        return fn

    def collect(self) -> List[_Metric]:
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn(self)
            except Exception:
                pass  # a broken collector must not take down exposition
        with self._lock:
            return list(self._metrics.values())

    def snapshot(self) -> Dict[str, dict]:
        """Flat dict view (for the JSONL sink): metric name →
        ``{type, samples: [[sample_name, labels, value], ...]}``."""
        out = {}
        for m in self.collect():
            out[m.name] = {
                "type": m.type,
                "samples": [[n, labels, v] for n, labels, v in m.expose()],
            }
        return out

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()
            self._collectors.clear()


# -- default registry ---------------------------------------------------------
_default: Optional[MetricRegistry] = None
_default_lock = threading.Lock()


def default_registry() -> MetricRegistry:
    global _default
    with _default_lock:
        if _default is None:
            _default = MetricRegistry()
        return _default


def set_default_registry(reg: Optional[MetricRegistry]) -> None:
    global _default
    with _default_lock:
        _default = reg


# -- trace_events bridge -------------------------------------------------------
#: snapshot family → the label name its site[1] becomes
_FAMILY_LABEL = {
    "executor_cache": "executor",
    "serving": "engine",
    "resilience": "site",
    "autotune": "name",
    "steptrace": "name",
    "router": "replica",
    "slo": "engine",
    "supervisor": "name",
    "amp": "scaler",
}

_bridge_fn: Optional[Callable] = None
_bridge_lock = threading.Lock()


def _numeric(v) -> Optional[float]:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    if isinstance(v, float) and not math.isfinite(v):
        return None
    return float(v)


def install_bridge(registry: Optional[MetricRegistry] = None) -> Callable:
    """Subscribe a trace_events observer that republishes every numeric
    field of the snapshot families as gauges
    ``paddle_tpu_<family>_<field>{<label>="<site name>"}``.  Nested dicts
    (the measured searches' ``counters``) flatten one level.  Idempotent; returns
    the observer so tests can unregister it directly."""
    global _bridge_fn
    from ..framework import trace_events

    reg = registry or default_registry()
    with _bridge_lock:
        if _bridge_fn is not None:
            return _bridge_fn

        def _observe(site, info):
            family = site[0]
            label = _FAMILY_LABEL.get(family)
            if label is None or not isinstance(info, dict):
                return
            flat = []
            for k, v in info.items():
                if isinstance(v, dict):
                    for k2, v2 in v.items():
                        flat.append((f"{k}_{k2}", v2))
                else:
                    flat.append((k, v))
            for k, v in flat:
                num = _numeric(v)
                if num is None:
                    continue
                g = reg.gauge(
                    sanitize_name(f"paddle_tpu_{family}_{k}"),
                    f"latest {family} snapshot field {k!r} "
                    f"(trace_events bridge)", (label,))
                g.labels(str(site[1])).set(num)

        trace_events.register(_observe)
        _bridge_fn = _observe
        return _observe


def uninstall_bridge() -> None:
    global _bridge_fn
    from ..framework import trace_events

    with _bridge_lock:
        if _bridge_fn is not None:
            trace_events.unregister(_bridge_fn)
            _bridge_fn = None


def bridge_installed() -> bool:
    return _bridge_fn is not None


def install_standard_collectors(registry: Optional[MetricRegistry] = None
                                ) -> None:
    """Register the pull collectors for the counters that predate this
    registry: ``monitor.all_stats()``, the profiler's dropped-span gauge,
    and ``trace_events.dropped_notifications()``."""
    reg = registry or default_registry()

    def _collect_monitor(r):
        from ..framework import monitor

        g = r.gauge("paddle_tpu_monitor",
                    "framework.monitor stat counters", ("stat",))
        for name, value in monitor.all_stats().items():
            g.labels(sanitize_name(name)).set(float(value))

    def _collect_drops(r):
        from ..framework import trace_events
        from .. import profiler

        r.gauge("paddle_tpu_profiler_dropped_spans",
                "host spans dropped past the profiler span cap"
                ).set(float(profiler.dropped_spans()))
        r.gauge("paddle_tpu_trace_events_dropped_notifications",
                "observer exceptions swallowed by trace_events.notify"
                ).set(float(trace_events.dropped_notifications()))

    reg.register_collector(_collect_monitor)
    reg.register_collector(_collect_drops)
