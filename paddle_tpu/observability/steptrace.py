"""Per-step training telemetry: where does a training step's time go?

A step has three host-observable phases: waiting on the input pipeline
(``data_wait_ms``, timed inside ``DataLoader``'s staging iterator),
dispatching the jitted computation (``dispatch_ms``, the Python-side
runner call), and the device actually computing
(``device_step_ms``, ``block_until_ready``-timed).  ``StepTelemetry``
aggregates all three plus steps/s, examples/s, an MFU estimate from the
lowered executable's ``cost_analysis()`` FLOPs, and HBM high-water
gauges from ``device.memory_stats()``.

Hot-path contract: ``Executor._dispatch`` and the DataLoader check the
module attribute ``_active`` — a single falsy check when telemetry is
off, so the fused ``run_steps`` dispatch overhead is unchanged
(``tools/perf_smoke.py`` holds the line).  Note the device timing adds a
``block_until_ready`` per dispatch when telemetry is ON — that is the
price of the breakdown, and why it is opt-in.

Snapshots publish on the trace_events bus as ``("steptrace", "train")``
(latest-value family like ``executor_cache``); ``analysis.RetraceMonitor``
turns them into rules M901 (data-starved training) and M902 (HBM
high-water above the alert fraction).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional

__all__ = ["StepTelemetry", "install", "uninstall", "active",
           "estimate_flops", "render_summary_section"]

#: the live telemetry sink, or None — hot paths check this attribute
#: directly (``if _steptrace._active is not None:``), no function call
_active: Optional["StepTelemetry"] = None


def install(registry=None) -> "StepTelemetry":
    """Activate step telemetry (idempotent); returns the live sink."""
    global _active
    if _active is None:
        from .metrics import default_registry

        _active = StepTelemetry(registry or default_registry())
    return _active


def uninstall() -> None:
    global _active
    _active = None


def active() -> Optional["StepTelemetry"]:
    return _active


def estimate_flops(jitted, *args, **kwargs) -> Optional[float]:
    """Best-effort FLOP count of one dispatch of ``jitted(*args)`` from
    XLA's ``cost_analysis()`` on the *lowered* (not compiled) module —
    tracing cost only, no extra XLA compile, and donation annotations are
    inert at lowering time so donated args are not consumed.  None when
    the backend doesn't report FLOPs (e.g. some CPU builds)."""
    try:
        cost = jitted.lower(*args, **kwargs).cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def _peak_flops() -> Optional[float]:
    """Peak chip FLOP/s for the MFU denominator, from the package's one
    table (``framework.device.PEAK_BF16_TFLOPS``).  None for
    a device kind the table does not list (the CPU, an unknown chip):
    then MFU is not reported — there is no default peak to divide by."""
    from ..framework.device import peak_bf16_tflops

    peak = peak_bf16_tflops()
    return None if peak is None else peak * 1e12


class StepTelemetry:
    """Aggregates the step-time breakdown and feeds the metric registry.

    The FIRST dispatch per executor is warmup (it pays trace+compile) and
    is excluded from the post-warm rate/breakdown sums — M901 and the MFU
    estimate would otherwise be dominated by one compile stall."""

    def __init__(self, registry):
        self._lock = threading.Lock()
        self._registry = registry
        self._warmed: Dict[str, bool] = {}
        self._flops: Dict[str, float] = {}
        self.steps = 0
        self.examples = 0
        self.dispatches = 0
        self.warmup_dispatches = 0
        self.data_wait_ms = 0.0
        self.dispatch_ms = 0.0
        self.device_ms = 0.0
        self.flops_post_warm = 0.0
        self.steps_post_warm = 0
        self._t_first_post_warm: Optional[float] = None
        self._t_last = 0.0

        r = registry
        self._c_steps = r.counter(
            "paddle_tpu_steps_total", "optimizer steps dispatched")
        self._c_examples = r.counter(
            "paddle_tpu_examples_total", "training examples consumed")
        self._h_data_wait = r.histogram(
            "paddle_tpu_data_wait_ms",
            "time the consumer blocked on the input pipeline per batch")
        self._h_dispatch = r.histogram(
            "paddle_tpu_dispatch_ms",
            "host time to dispatch one jitted executor call")
        self._h_device = r.histogram(
            "paddle_tpu_device_step_ms",
            "block_until_ready-timed device execution per dispatch")
        # created (and rendered, at 0) even where memory_stats() is
        # unsupported, so dashboards don't need backend-conditional panels
        self._g_hbm_peak = r.gauge(
            "paddle_tpu_hbm_high_water_bytes",
            "max peak_bytes_in_use across local devices")
        self._g_hbm_limit = r.gauge(
            "paddle_tpu_hbm_limit_bytes",
            "max bytes_limit across local devices (0 = unreported)")
        self._g_steps_per_s = r.gauge(
            "paddle_tpu_steps_per_s", "post-warmup optimizer steps per second")
        self._g_examples_per_s = r.gauge(
            "paddle_tpu_examples_per_s", "post-warmup examples per second")
        self._g_mfu = r.gauge(
            "paddle_tpu_mfu",
            "model FLOPs utilization estimate (cost_analysis flops / "
            "elapsed / the device kind's peak; 0 = not reported)")

    # -- producers -----------------------------------------------------------
    def record_data_wait(self, ms: float) -> None:
        with self._lock:
            self.data_wait_ms += ms
        self._h_data_wait.observe(ms)

    def set_flops(self, name: str, flops: Optional[float]) -> None:
        """FLOPs of ONE dispatch of executor ``name``'s current runner
        (a fused run_steps chain counts all its steps)."""
        if flops:
            with self._lock:
                self._flops[name] = float(flops)

    def on_dispatch(self, name: str, *, n_steps: int, examples: int,
                    dispatch_ms: float, device_ms: float) -> None:
        now = time.monotonic()
        with self._lock:
            warm = self._warmed.get(name, False)
            self._warmed[name] = True
            self.dispatches += 1
            self.steps += n_steps
            self.examples += examples
            if warm:
                self.dispatch_ms += dispatch_ms
                self.device_ms += device_ms
                self.steps_post_warm += n_steps
                self.flops_post_warm += self._flops.get(name, 0.0)
                if self._t_first_post_warm is None:
                    self._t_first_post_warm = (
                        now - (dispatch_ms + device_ms) / 1e3)
            else:
                self.warmup_dispatches += 1
            self._t_last = now
        self._c_steps.inc(n_steps)
        if examples:
            self._c_examples.inc(examples)
        if warm:
            self._h_dispatch.observe(dispatch_ms)
            self._h_device.observe(device_ms)
        self._update_derived()
        self.publish()

    # -- derived gauges / snapshot -------------------------------------------
    def _hbm(self):
        from ..framework.device import memory_stats

        peak = limit = 0
        try:
            import jax

            for d in jax.local_devices():
                stats = memory_stats(d)
                peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
                limit = max(limit, int(stats.get("bytes_limit", 0)))
        except Exception:
            pass
        return peak, limit

    def _rates(self):
        with self._lock:
            if self._t_first_post_warm is None:
                return 0.0, 0.0, 0.0
            elapsed = max(self._t_last - self._t_first_post_warm, 1e-9)
            steps_per_s = self.steps_post_warm / elapsed
            # examples are counted from step 0 but rates are post-warm:
            # scale by the post-warm step share so a 1-warmup run stays
            # consistent (examples/step is constant in a train loop)
            ex_per_step = self.examples / max(self.steps, 1)
            peak = _peak_flops()
            mfu = (self.flops_post_warm / elapsed / peak) if peak else 0.0
            return steps_per_s, steps_per_s * ex_per_step, mfu

    def _update_derived(self):
        steps_per_s, examples_per_s, mfu = self._rates()
        self._g_steps_per_s.set(steps_per_s)
        self._g_examples_per_s.set(examples_per_s)
        self._g_mfu.set(mfu)
        peak, limit = self._hbm()
        self._g_hbm_peak.set(float(peak))
        self._g_hbm_limit.set(float(limit))

    def snapshot(self) -> dict:
        from ..framework.flags import flag

        steps_per_s, examples_per_s, mfu = self._rates()
        peak, limit = self._hbm()
        with self._lock:
            return {
                "steps": self.steps,
                "steps_post_warm": self.steps_post_warm,
                "examples": self.examples,
                "dispatches": self.dispatches,
                "warmup_dispatches": self.warmup_dispatches,
                "data_wait_ms": round(self.data_wait_ms, 3),
                "dispatch_ms": round(self.dispatch_ms, 3),
                "device_ms": round(self.device_ms, 3),
                "steps_per_s": round(steps_per_s, 3),
                "examples_per_s": round(examples_per_s, 3),
                "flops_per_dispatch": max(self._flops.values(), default=0.0),
                "mfu": round(mfu, 5),
                "hbm_peak_bytes": peak,
                "hbm_limit_bytes": limit,
                "hbm_threshold": float(flag("hbm_high_water_frac")),
            }

    def publish(self) -> None:
        from ..framework import trace_events

        if not trace_events.active():
            return
        trace_events.notify(("steptrace", "train"), self.snapshot())


def render_summary_section() -> str:
    """The "Training telemetry" block for ``profiler.summary()`` —
    empty string when telemetry is off or saw no dispatches."""
    st = _active
    if st is None or st.dispatches == 0:
        return ""
    snap = st.snapshot()
    lines = ["Training telemetry"]
    busy = snap["data_wait_ms"] + snap["dispatch_ms"] + snap["device_ms"]
    for key, label in (("data_wait_ms", "data wait"),
                       ("dispatch_ms", "dispatch"),
                       ("device_ms", "device")):
        share = snap[key] / busy if busy > 0 else 0.0
        lines.append(f"  {label:<12}{snap[key]:>12.3f} ms{share:>8.1%}")
    lines.append(f"  steps {snap['steps']} "
                 f"({snap['warmup_dispatches']} warmup dispatch(es)); "
                 f"{snap['steps_per_s']:.2f} steps/s, "
                 f"{snap['examples_per_s']:.1f} examples/s post-warmup")
    if snap["mfu"] > 0:
        lines.append(f"  MFU ~{snap['mfu']:.1%} "
                     f"(cost_analysis FLOPs / the device kind's peak)")
    if snap["hbm_limit_bytes"] > 0:
        frac = snap["hbm_peak_bytes"] / snap["hbm_limit_bytes"]
        lines.append(f"  HBM high-water {snap['hbm_peak_bytes'] / 2**30:.2f} "
                     f"GiB of {snap['hbm_limit_bytes'] / 2**30:.2f} GiB "
                     f"({frac:.1%})")
    return "\n".join(lines)
