"""Profiler — device traces + host-side event timing.

Parity: paddle/fluid/platform/profiler.h:40-212 (RecordEvent, Enable/
DisableProfiler, the event table printed by PrintProfiler) and
python/paddle/fluid/profiler.py (profiler context manager,
start_profiler/stop_profiler/reset_profiler).

TPU-native design: the *device* timeline comes from the XLA profiler —
``start_profiler(log_dir)`` wraps ``jax.profiler.start_trace`` and writes a
TensorBoard/perfetto-loadable trace of every compiled computation, transfer
and ICI collective (far richer than the reference's per-op CUDA event
pairs).  The *host* table the reference prints is kept too: ``RecordEvent``
annotates the device trace AND accumulates wall-clock stats, and
``stop_profiler``/``summary`` prints the familiar
name/calls/total/avg/min/max table.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional

import jax

__all__ = [
    "RecordEvent",
    "start_profiler",
    "stop_profiler",
    "reset_profiler",
    "profiler",
    "profiling_active",
    "record_span",
    "dropped_spans",
    "summary",
    "export_chrome_tracing",
    "register_summary_section",
]

_lock = threading.Lock()
_events: Dict[str, dict] = {}
_spans: list = []  # (name, tid, start_us, dur_us, cat, args) while profiling
_SPAN_CAP = 200_000  # keep the host-side buffer bounded
_dropped_spans = 0  # spans past the cap — counted, not silently lost
_trace_dir: Optional[str] = None
_started = False
_sections: list = []  # (render_fn, on_reset) extra summary() sections


def register_summary_section(render_fn, on_reset=None) -> None:
    """Let a subsystem append its own block to ``summary()``.

    ``render_fn() -> str`` runs at summary time; an empty string means
    "nothing to report" and the section is skipped (so ``summary()``
    still returns ``""`` when there is nothing at all to show).
    ``on_reset`` (optional) runs inside ``reset_profiler()`` so the
    subsystem can snapshot its counters — sections report activity since
    the last reset, matching the host-event table's lifecycle.  Used by
    ``tuning.engine`` for the measured searches' cache statistics."""
    with _lock:
        _sections.append((render_fn, on_reset))


class RecordEvent:
    """Annotate a region: shows up named in the device trace and in the
    host event table.  Context manager or decorator.  Keyword arguments
    become the trace event's metadata; the host table keys on ``name``
    alone, so keep what varies (a bucket, a count) out of the name.

    Parity: platform/profiler.h:121 RecordEvent.
    """

    def __init__(self, name: str, **args):
        self.name = name
        self._args = args
        self._ann = None
        self._t0 = 0.0

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name, **self._args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        global _dropped_spans
        t1 = time.perf_counter()
        dt = (t1 - self._t0) * 1e3  # ms
        self._ann.__exit__(*exc)
        with _lock:
            e = _events.setdefault(
                self.name,
                {"calls": 0, "total": 0.0, "min": float("inf"), "max": 0.0})
            e["calls"] += 1
            e["total"] += dt
            e["min"] = min(e["min"], dt)
            e["max"] = max(e["max"], dt)
            if _started:
                if len(_spans) < _SPAN_CAP:
                    _spans.append((self.name, threading.get_ident(),
                                   self._t0 * 1e6, dt * 1e3, "host", None))
                else:
                    _dropped_spans += 1
        return False

    def __call__(self, fn):
        def wrapped(*a, **k):
            with RecordEvent(self.name, **self._args):
                return fn(*a, **k)

        return wrapped


def start_profiler(log_dir: Optional[str] = None, state: str = "All",
                   tracer_option: str = "Default"):
    """Begin profiling.  ``log_dir`` set → also capture the XLA device trace
    there (view in TensorBoard's profile plugin / Perfetto).

    Parity: fluid/profiler.py start_profiler (state/tracer_option accepted
    for signature compatibility; the XLA trace always covers both CPU and
    device activity).
    """
    global _trace_dir, _started
    if _started:
        raise RuntimeError(
            "profiler already running — call stop_profiler() first")
    reset_profiler()
    if log_dir is not None:
        jax.profiler.start_trace(log_dir)
        _trace_dir = log_dir
    _started = True


def stop_profiler(sorted_key: Optional[str] = "total",
                  profile_path: Optional[str] = None) -> str:
    """End profiling; returns (and prints) the host event table.  With a
    ``log_dir`` given at start, finalizes the device trace.

    Parity: fluid/profiler.py stop_profiler (sorted_key: one of
    calls/total/max/min/ave)."""
    global _trace_dir, _started
    if not _started:
        return ""  # stop without start: nothing to finalize
    if _trace_dir is not None:
        jax.profiler.stop_trace()
        _trace_dir = None
    _started = False
    table = summary(sorted_key=sorted_key)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(table)
    if table:
        print(table)
    return table


def profiling_active() -> bool:
    """True between start_profiler and stop_profiler — span producers
    outside this module (the serving batcher) check it before paying the
    span-assembly cost."""
    return _started


def record_span(name: str, start_s: float, dur_ms: float, *,
                tid: Optional[int] = None, cat: str = "host",
                args: Optional[dict] = None) -> bool:
    """Record an externally-timed span (``start_s`` on the perf_counter /
    monotonic clock base) into the chrome-trace buffer.  Used by the
    serving layer for per-request queue/execute spans.  No-op unless the
    profiler is running; respects (and counts overflow past) the span
    cap.  Returns whether the span was kept."""
    global _dropped_spans
    if not _started:
        return False
    with _lock:
        if not _started:
            return False
        if len(_spans) >= _SPAN_CAP:
            _dropped_spans += 1
            return False
        _spans.append((name, tid if tid is not None
                       else threading.get_ident(),
                       start_s * 1e6, dur_ms * 1e3, cat, args))
        return True


def dropped_spans() -> int:
    """Spans lost past ``_SPAN_CAP`` since the last reset."""
    with _lock:
        return _dropped_spans


def reset_profiler():
    """Parity: fluid/profiler.py reset_profiler."""
    global _dropped_spans
    with _lock:
        _events.clear()
        _spans.clear()
        _dropped_spans = 0
        hooks = [h for _, h in _sections if h is not None]
    for hook in hooks:
        hook()


def export_chrome_tracing(path: str) -> int:
    """Write the recorded host spans as a chrome://tracing /
    ui.perfetto.dev JSON file (capability of the reference's
    tools/timeline.py, which converted profiler protos the same way).
    Returns the number of spans written.  Device-side timelines come
    from the XLA trace (``start_profiler(log_dir=...)``) — this covers
    the host RecordEvent annotations."""
    import json

    with _lock:
        spans = list(_spans)
        dropped = _dropped_spans
    events = []
    for name, tid, ts_us, dur_us, cat, args in spans:
        ev = {"name": name, "ph": "X", "pid": 0, "tid": tid,
              "ts": round(ts_us, 3), "dur": round(dur_us, 3),
              "cat": cat}
        if args:
            ev["args"] = args
        events.append(ev)
    # request-tracing spans share the monotonic base (perf_counter and
    # monotonic are both CLOCK_MONOTONIC on Linux), so they land on the
    # same timeline as the RecordEvent spans
    from .observability import tracing as _tracing

    tr = _tracing._active
    if tr is not None:
        events.extend(tr.chrome_events())
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms",
                   "otherData": {"dropped_spans": dropped}}, f)
    return len(events)


def summary(sorted_key: Optional[str] = "total") -> str:
    """The reference's PrintProfiler table (profiler.cc) from host events,
    followed by any registered subsystem sections (see
    ``register_summary_section``)."""
    with _lock:
        rows = [
            (name, e["calls"], e["total"], e["total"] / e["calls"],
             e["min"], e["max"])
            for name, e in _events.items()
        ]
        sections = [fn for fn, _ in _sections]
        dropped = _dropped_spans
    extra = [s for s in (fn() for fn in sections) if s]
    if dropped:
        extra.append(f"[profiler] {dropped} span(s) dropped past the "
                     f"{_SPAN_CAP} span cap — the chrome trace is "
                     f"truncated; profile a shorter window")
    if not rows:
        return "\n\n".join(extra) if extra else ""
    key_idx = {"calls": 1, "total": 2, "ave": 3, "min": 4, "max": 5}.get(
        sorted_key or "total", 2)
    rows.sort(key=lambda r: r[key_idx], reverse=True)
    grand = sum(r[2] for r in rows) or 1.0
    w = max(len(r[0]) for r in rows) + 2
    lines = [
        f"{'Event':<{w}}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>10}"
        f"{'Min(ms)':>10}{'Max(ms)':>10}{'Ratio':>8}"
    ]
    for name, calls, total, avg, mn, mx in rows:
        lines.append(
            f"{name:<{w}}{calls:>8}{total:>12.3f}{avg:>10.3f}"
            f"{mn:>10.3f}{mx:>10.3f}{total / grand:>8.2%}")
    table = "\n".join(lines)
    return "\n\n".join([table] + extra) if extra else table


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = "total",
             profile_path: Optional[str] = None,
             log_dir: Optional[str] = None):
    """``with profiler(...):`` — parity with fluid.profiler.profiler.

    The reference's ``state`` chose CPU vs GPU event capture; the XLA trace
    captures both, so it is accepted and ignored.
    """
    start_profiler(log_dir=log_dir, state=state)
    try:
        yield
    finally:
        stop_profiler(sorted_key=sorted_key, profile_path=profile_path)
