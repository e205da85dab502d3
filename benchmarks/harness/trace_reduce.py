"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read.  Only ``jax.profiler.ProfileData`` is needed.

What a v5e trace looks like (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per execution
of a compiled program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one
event per HLO op that ran; a Pallas/Mosaic kernel is an op whose text holds
``custom_call_target="tpu_custom_call"``) and ``Async XLA Ops`` (DMA
lifetimes, which overlap compute and are not busy time).  The plane
``/host:CPU`` has one line per thread; ``jax.profiler.TraceAnnotation``
spans land on the thread that opened them.  All times are nanoseconds on
one clock; the device clock was seen about a millisecond behind the host's,
so a gap shorter than that cannot be attributed reliably.
"""
import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path, host_span_names=()):
    """Read an xplane file into plain lists:
    ``{"devices": {id: {"modules": [(name, start, dur)], "ops": [...]}},
    "host_spans": [(name, start, dur)]}`` (nanoseconds)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    wanted = set(host_span_names)
    devices, host_spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name == "/host:CPU" and wanted:
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        host_spans.append((e.name, float(e.start_ns),
                                           float(e.duration_ns)))
    host_spans.sort(key=lambda s: s[1])
    return {"devices": devices, "host_spans": host_spans}


def union_ns(intervals):
    """Total length of the union of ``(start, dur)`` intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, lo, hi):
    """Idle gaps ``(start, dur)`` of ``intervals`` inside ``[lo, hi]``."""
    out, cur = [], lo
    for s, d in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi) - cur))
        cur = max(cur, s + d)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi - cur))
    return [(s, d) for s, d in out if d > 0]


def short_op_name(text):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12`` plus the HLO
    opcode, the name the trace prints without its operand list."""
    head = text.split(" = ", 1)
    name = head[0].lstrip("%")
    if len(head) == 1:
        return name[:80]
    m = re.search(r"\}?\)?\s([a-z][a-z0-9\-]*)\(", head[1])
    op = m.group(1) if m else ""
    if MOSAIC_MARK in text:
        op = "tpu_custom_call"
    return f"{name}:{op}"[:80]


def clip(events, lo, hi):
    """Events cut to the span ``[lo, hi]``."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def reduce(trace, span=None, step_module=None, steps_per_execution=1,
           gap_span_names=()):
    """The reduction.  ``span`` is ``(lo, hi)`` ns, by default from the first
    to the last device event.  ``step_module`` is a substring that names the
    step program on the ``XLA Modules`` line (by default the module with the
    most device time).  An idle gap is named after the one of
    ``gap_span_names`` (all host spans read, by default) that covers most of
    it.  Returns a dict of plain numbers, per device and averaged, that the
    per-layer readers pick from."""
    devs = trace["devices"]
    if not devs or not any(d["ops"] for d in devs.values()):
        raise ValueError("the trace holds no device operation")
    if span is None:
        lo = min(e[1] for d in devs.values() for e in d["ops"])
        hi = max(e[1] + e[2] for d in devs.values() for e in d["ops"])
    else:
        lo, hi = span
    window_ns = hi - lo
    per_dev = {}
    for i, d in sorted(devs.items()):
        ops = clip(d["ops"], lo, hi)
        busy = union_ns([(s, du) for _, s, du in ops])
        per_dev[i] = {"busy_ns": busy,
                      "op_time_ns": sum(du for _, _, du in ops),
                      "n_ops": len(ops)}
    first = sorted(devs)[0]
    d0 = devs[first]
    # whole executions only: the profiler cuts an execution that is running
    # when the trace starts or stops down to the part it saw, so the first
    # and last events of the line are not durations (a 1.79 s BERT window
    # read 1.53 s and 1.47 s that way, PR 23)
    edge = 1e6
    t_first = min((s for _, s, _ in d0["modules"]), default=lo)
    t_last = max((s + du for _, s, du in d0["modules"]), default=hi)
    by_mod = {}
    for n, s, du in d0["modules"]:
        if (s >= lo and s + du <= hi and s > t_first + edge
                and s + du < t_last - edge):
            by_mod.setdefault(n, []).append((s, du))
    step = None
    if by_mod:
        if step_module is not None:
            cands = [n for n in by_mod if step_module in n]
        else:
            cands = list(by_mod)
        if cands:
            step = max(cands, key=lambda n: sum(du for _, du in by_mod[n]))
    step_info = None
    if step is not None:
        runs = sorted(by_mod[step])
        durs = [du for _, du in runs]
        between = [runs[k + 1][0] - (runs[k][0] + runs[k][1])
                   for k in range(len(runs) - 1)]
        step_info = {"module": step, "executions": len(runs),
                     "durations_ns": durs, "gaps_ns": between,
                     "steps_per_execution": steps_per_execution}
    # top ops by total time on the first device
    agg = {}
    for n, _, du in clip(d0["ops"], lo, hi):
        k = short_op_name(n)
        agg[k] = agg.get(k, 0.0) + du
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:10]
    # longest idle gaps on the first device, by the host span that covers them
    idle = sorted(gaps([(s, du) for _, s, du in clip(d0["ops"], lo, hi)],
                       lo, hi), key=lambda g: -g[1])
    spans = _Spans(s for s in trace["host_spans"]
                   if not gap_span_names or s[0] in gap_span_names)
    by_cause = {}
    for s, du in idle:
        by_cause.setdefault(spans.covering(s, du), []).append(du)
    gap_rows = sorted(((c, sum(v)) for c, v in by_cause.items()),
                      key=lambda kv: -kv[1])[:10]
    longest = [(spans.covering(s, du), du) for s, du in idle[:5]]
    n = len(per_dev)
    return {
        "window_ns": window_ns,
        "busy_ns": sum(p["busy_ns"] for p in per_dev.values()) / n,
        "per_device": per_dev,
        "first_device": first,
        "modules": {k: {"executions": len(v),
                        "total_ns": sum(du for _, du in v)}
                    for k, v in by_mod.items()},
        "step": step_info,
        "top_ops": [[k, v / 1e9] for k, v in top],
        "idle_by_cause": [[k, v / 1e9] for k, v in gap_rows],
        "longest_gaps": [[k, v / 1e9] for k, v in longest],
    }


class _Spans:
    """Host spans indexed for lookup by time: sorted by start, with the
    running maximum of their ends, so that the spans that can overlap a gap
    are found by bisection (a traced window of a loaded serving engine holds
    tens of thousands of spans and as many gaps)."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.ends_so_far, end = [], float("-inf")
        for _, s, d in self.spans:
            end = max(end, s + d)
            self.ends_so_far.append(end)

    def covering(self, start, dur):
        """Name of the span that overlaps the gap most (the innermost of
        equal overlaps, i.e. the shortest), or ``unattributed``."""
        best, best_key = "unattributed", (0.0, 0.0)
        lo = bisect.bisect_right(self.ends_so_far, start)
        hi = bisect.bisect_left(self.starts, start + dur)
        for name, s, d in self.spans[lo:hi]:
            ov = min(s + d, start + dur) - max(s, start)
            if ov > 0:
                key = (ov, -d)
                if key > best_key:
                    best, best_key = name, key
        return best
