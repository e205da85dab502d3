"""Arithmetic the per-layer readers share.  A reader is a file
``layer_metrics/<metric name>.py`` with ``read(ev)``; ``ev`` holds the
reduced trace (``trace``), the runner's facts and counters (``facts``),
the end-to-end metrics of this run (``metrics``), the chip's published
peaks (``peaks``) and the number of chips (``chips``).  A reader that
finds nothing to read returns None and the metric is left out."""
import statistics


def device_idle_share(ev):
    t = ev.get("trace")
    if not t or not t["window_ns"]:
        return None
    return 100.0 * (1.0 - t["per_device"][t["first_device"]]["busy_ns"]
                    / t["window_ns"])


def step_device_ms(ev):
    t = ev.get("trace")
    if not t or not t.get("step") or not t["step"]["durations_ns"]:
        return None
    s = t["step"]
    return (statistics.median(s["durations_ns"]) / s["steps_per_execution"]
            / 1e6)


def host_gap_ms(ev):
    t = ev.get("trace")
    if not t or not t.get("step") or not t["step"]["gaps_ns"]:
        return None
    return statistics.median(t["step"]["gaps_ns"]) / 1e6


def mfu(ev):
    ms = step_device_ms(ev)
    flops = ev["facts"].get("flops_per_step")
    if ms is None or not flops:
        return None
    return 100.0 * flops / (ms / 1e3 * ev["chips"] * ev["peaks"]["bf16_flops"])


def generator_lateness_p95_ms(ev):
    from benchmarks.harness import stats

    late = ev["facts"].get("lateness_ms")
    if not late or ev["facts"].get("loop") != "open":
        return None
    return stats.percentile(late, 95)
