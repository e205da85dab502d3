"""paddle_tpu.resilience — fault tolerance as a first-class subsystem.

The reference framework treats failure as API surface (the typed enforce
hierarchy of paddle/fluid/platform/enforce.h, auto-checkpoint preemption
resume, chief-side heartbeat monitoring); this package is where those
islands become a system:

* :mod:`~paddle_tpu.resilience.retry` — :class:`RetryPolicy`:
  deadline-aware exponential backoff with seeded jitter over the
  transient/fatal classification (``framework.errors.is_transient``); used by
  the checkpoint async writer, ``Executor.run`` dispatch and serving
  batch execution.
* :mod:`~paddle_tpu.resilience.faults` — deterministic fault injection:
  named :func:`fault_point` hooks at the framework's I/O and dispatch
  seams, driven by a :class:`FaultPlan` (``FLAGS_fault_plan``); a no-op
  falsy check when disabled.
* :mod:`~paddle_tpu.resilience.circuit` — :class:`CircuitBreaker`:
  per-bucket closed → open → half-open degradation for the serving
  engines; open circuits shed with ``UnavailableError`` instead of
  burning device slots.
* :mod:`~paddle_tpu.resilience.preemption` — SIGTERM → one final
  synchronous checkpoint → exit :data:`PREEMPTION_EXIT_CODE` (75), which
  ``distributed.parallel.watch`` restarts without consuming the failure
  budget.

Observability rides the existing rails: counters on ``framework.monitor``,
``("resilience", ...)`` events on ``framework.trace_events`` (analysis
rule F801 flags retry storms / circuit flapping after serving warmup),
and a "Faults & retries" section in ``profiler.summary()``.
"""
from __future__ import annotations

from .circuit import CLOSED, HALF_OPEN, OPEN, CircuitBreaker  # noqa: F401
from .faults import (  # noqa: F401
    FaultPlan, FaultRule, fault_point, install_from_flags)
from .preemption import (  # noqa: F401
    PREEMPTION_EXIT_CODE, PreemptionHandler, install_preemption_handler)
from .retry import RetryPolicy, is_warm, mark_warm  # noqa: F401
from .supervisor import DivergenceError, TrainingSupervisor  # noqa: F401

from . import circuit, faults, retry, supervisor  # noqa: F401

__all__ = [
    "RetryPolicy", "mark_warm", "is_warm",
    "FaultPlan", "FaultRule", "fault_point", "install_from_flags",
    "CircuitBreaker", "CLOSED", "OPEN", "HALF_OPEN",
    "PreemptionHandler", "install_preemption_handler",
    "PREEMPTION_EXIT_CODE",
    "TrainingSupervisor", "DivergenceError",
]


# -- profiler "Faults & retries" summary section -----------------------------
_retry_base: dict = {}
_fault_base: dict = {}
_supervisor_base: dict = {}


def _on_profiler_reset() -> None:
    global _retry_base, _fault_base, _supervisor_base
    _retry_base = retry.stats()
    plan = faults._plan
    _fault_base = plan.stats() if plan is not None else {}
    _supervisor_base = supervisor.stats()


def _summary_section() -> str:
    """Activity since the last profiler reset: injected faults, retries
    per policy, and circuit state — profiler.summary() appends this."""
    lines = []
    plan = faults._plan
    if plan is not None:
        for site, d in sorted(plan.stats().items()):
            base = _fault_base.get(site, {})
            calls = d["calls"] - base.get("calls", 0)
            fired = d["fired"] - base.get("fired", 0)
            if calls or fired:
                lines.append(f"  fault {site:<24} calls {calls:>6}  "
                             f"fired {fired:>5}")
    for name, d in sorted(retry.stats().items()):
        base = _retry_base.get(name, {})
        delta = {k: d[k] - base.get(k, 0) for k in d}
        if any(delta.values()):
            lines.append(
                f"  retry {name:<24} attempts {delta['attempts']:>5}  "
                f"retries {delta['retries']:>4}  giveups "
                f"{delta['giveups'] + delta['deadline_giveups']:>4}  "
                f"after-warm {delta['retries_after_warm']:>4}")
    for name, d in sorted(circuit.all_stats().items()):
        if d["opens"] or d["sheds"] or d["open_keys"]:
            lines.append(
                f"  circuit {name:<22} opens {d['opens']:>6}  shed "
                f"{d['sheds']:>6}  open-keys {d['open_keys']:>3}  "
                f"flaps-after-warm {d['opens_after_warm']:>3}")
    if not lines:
        return ""
    return "\n".join(["Faults & retries"] + lines)


def _supervisor_section() -> str:
    """Divergence-guard activity since the last profiler reset —
    profiler.summary() appends this as "Training supervisor"."""
    d = supervisor.stats()
    delta = {k: d[k] - _supervisor_base.get(k, 0) for k in d}
    if not any(delta.values()):
        return ""
    return "\n".join([
        "Training supervisor",
        f"  rollbacks {delta.get('rollbacks', 0):>6}  "
        f"repeat-trips {delta.get('repeat_trips', 0):>4}  "
        f"fatal {delta.get('fatal_divergences', 0):>3}",
        f"  skipped-batches {delta.get('skipped_batches', 0):>6}  "
        f"exact-resumes {delta.get('exact_resumes', 0):>4}  "
        f"watchdog-trips {delta.get('watchdog_trips', 0):>4}",
    ])


def _register_profiler_section() -> None:
    from .. import profiler

    profiler.register_summary_section(_summary_section,
                                      on_reset=_on_profiler_reset)
    profiler.register_summary_section(_supervisor_section)


_register_profiler_section()

# env-driven fault plans (FLAGS_fault_plan=... in a chaos subprocess)
# install at import so every fault point in the process sees them
install_from_flags()
