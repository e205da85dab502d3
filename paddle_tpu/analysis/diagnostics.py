"""Shared diagnostics core for the `paddle_tpu.analysis` passes.

Every pass (program verifier, dy2static linter, retrace detector, plan
checker) reports findings as :class:`Diagnostic` records — rule id,
severity, message, ``file:line`` location, fix hint — so tooling can render
them uniformly as text (one finding per line, clickable anchors) or JSON
(machine lane for CI).  This is the paddle_tpu analogue of the reference's
scattered PADDLE_ENFORCE strings: the check happens *before* compilation
and the anchor points at user code, not at jax internals.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Iterable, List, Optional

__all__ = [
    "Severity", "Location", "Diagnostic", "DiagnosticCollector",
    "render_text", "render_json", "has_errors", "RULES",
]


class Severity:
    """String-constant severity levels, ordered ERROR > WARNING > INFO."""

    ERROR = "error"
    WARNING = "warning"
    INFO = "info"

    _ORDER = {"error": 2, "warning": 1, "info": 0}

    @classmethod
    def rank(cls, sev: str) -> int:
        return cls._ORDER.get(sev, 0)


#: Rule catalog: id → (severity, one-line summary).  Documented in
#: README "Static analysis"; ids are stable across releases.
RULES = {
    # -- program verifier (V1xx) -------------------------------------------
    "V101": (Severity.ERROR,
             "declared variable shape/dtype disagrees with re-run inference"),
    "V102": (Severity.ERROR, "op fails shape inference"),
    "V103": (Severity.ERROR,
             "variable consumed but never produced (foreign program, "
             "use-before-def, or missing feed)"),
    "V104": (Severity.ERROR, "duplicate variable name in program"),
    "V105": (Severity.WARNING, "op unreachable from any fetch root"),
    "V106": (Severity.WARNING, "op output produced but never consumed"),
    "V107": (Severity.ERROR, "parameter mutated outside an optimizer update"),
    "V108": (Severity.WARNING, "feed placeholder with fully-unknown shape"),
    # -- dy2static linter (D2xx/D3xx) --------------------------------------
    "D201": (Severity.WARNING,
             "generator/async function silently falls back to native trace"),
    "D202": (Severity.WARNING,
             "nonlocal/global mutation inside a control-flow block"),
    "D203": (Severity.ERROR,
             "return/raise inside a tensor-dependent branch or loop"),
    "D204": (Severity.ERROR,
             "break/continue in a tensor-dependent loop"),
    "D301": (Severity.WARNING,
             "host sync (.numpy()/.item()/float()) on a traced value "
             "inside a loop"),
    "D302": (Severity.WARNING,
             "side-effecting call on a traced value inside a loop"),
    # -- retrace hazard detector (R4xx) ------------------------------------
    "R401": (Severity.WARNING, "to_static signature explosion (jit retraces)"),
    "R402": (Severity.WARNING, "Executor signature explosion (recompiles)"),
    "R403": (Severity.WARNING,
             "Executor compile-cache churn (LRU evictions past budget)"),
    # -- sharding plan checker (P5xx) --------------------------------------
    "P501": (Severity.ERROR, "partition spec names an axis not in the mesh"),
    "P502": (Severity.ERROR,
             "parameter dim not divisible by its sharding axis size"),
    "P503": (Severity.ERROR, "mesh axis double-booked within one spec"),
    "P504": (Severity.ERROR, "partition spec rank exceeds parameter rank"),
    "P505": (Severity.WARNING,
             "ZeRO enabled but optimizer state stays replicated"),
    # -- serving monitor (S6xx) ---------------------------------------------
    "S601": (Severity.WARNING,
             "serving bucket-miss churn (requests falling outside the "
             "configured shape buckets)"),
    "S602": (Severity.WARNING,
             "serving router instability after warmup (replica health "
             "flapping, or hedged requests pinned at their budget)"),
    "S607": (Severity.WARNING,
             "multi-tenant isolation failure (an in-budget tenant "
             "sustainedly starved past the weighted-fair share, or "
             "installed LoRA adapters never matched by any request)"),
    # -- measured search (K7xx) ----------------------------------------------
    "K701": (Severity.WARNING,
             "measured search inside a serving hot path (tuning cache "
             "miss after warmup)"),
    # -- resilience monitor (F8xx) -------------------------------------------
    "F801": (Severity.WARNING,
             "resilience instability in a warmed serving path (transient "
             "retry storm or circuit flapping)"),
    "F802": (Severity.WARNING,
             "training supervisor rollback loop (re-divergence after "
             "restoring the same checkpoint)"),
    "F803": (Severity.WARNING,
             "gang instability in a multi-host pod (gang-restore storm, "
             "or a peer rank still lost after a completed gang restore)"),
    # -- training telemetry (M9xx) -------------------------------------------
    "M901": (Severity.WARNING,
             "data-starved training (input-pipeline wait dominates the "
             "post-warmup step time)"),
    "M902": (Severity.WARNING,
             "HBM high-water above the alert fraction of device memory"),
    "M903": (Severity.WARNING,
             "SLO error-budget burn after serving warmup (multi-window "
             "burn-rate alert on live traffic)"),
    # -- quantized serving monitor (Q8xx) ------------------------------------
    "Q801": (Severity.WARNING,
             "quantization integrity hazard (post-warmup dequantize "
             "fallback in a quantized engine, or never-calibrated "
             "observers at convert time)"),
    # -- concurrency: static lint + runtime sanitizer (C10xx) ----------------
    "C1001": (Severity.ERROR,
              "lock-order inversion (cycle in the static lock-acquisition "
              "graph — two code paths take the same locks in opposite "
              "order)"),
    "C1002": (Severity.WARNING,
              "lock held across a blocking call (executor dispatch, "
              "device sync, queue wait, sleep, or collective — every "
              "other thread contending for the lock stalls behind it)"),
    "C1003": (Severity.WARNING,
              "attribute written from two thread entry points with no "
              "guarding lock (racy shared state)"),
    "C1004": (Severity.ERROR,
              "runtime lock-order cycle detected by the lock sanitizer "
              "at acquire time (potential deadlock)"),
    "C1005": (Severity.WARNING,
              "lock held longer than FLAGS_lock_hold_warn_ms (long "
              "critical section stalls every contending thread)"),
    "C1006": (Severity.WARNING,
              "Condition.wait outside a predicate re-check loop (misses "
              "spurious wakeups and stolen wakeups)"),
}


@dataclasses.dataclass
class Location:
    """A source anchor.  ``file`` may be a module path or ``<program>``
    pseudo-file for graph-level findings; ``line`` is 1-based."""

    file: Optional[str] = None
    line: Optional[int] = None
    function: Optional[str] = None

    def __str__(self) -> str:
        base = self.file or "<unknown>"
        s = f"{base}:{self.line}" if self.line else base
        if self.function:
            s += f" (in {self.function})"
        return s


@dataclasses.dataclass
class Diagnostic:
    rule: str
    message: str
    severity: Optional[str] = None  # defaults to the catalog severity
    location: Optional[Location] = None
    hint: Optional[str] = None

    def __post_init__(self):
        if self.severity is None:
            self.severity = RULES.get(self.rule, (Severity.WARNING, ""))[0]

    def render(self) -> str:
        loc = f"{self.location}: " if self.location else ""
        s = f"{loc}{self.severity} [{self.rule}] {self.message}"
        if self.hint:
            s += f"\n    hint: {self.hint}"
        return s

    def to_dict(self) -> dict:
        d = {"rule": self.rule, "severity": self.severity,
             "message": self.message}
        if self.location:
            d["location"] = {"file": self.location.file,
                            "line": self.location.line,
                            "function": self.location.function}
        if self.hint:
            d["hint"] = self.hint
        return d


class DiagnosticCollector:
    """Accumulates diagnostics across passes; passes take one of these (or
    create their own) and call :meth:`add`."""

    def __init__(self):
        self.diagnostics: List[Diagnostic] = []
        self._seen = set()

    def add(self, rule: str, message: str, *, location: Location = None,
            hint: str = None, severity: str = None) -> Optional[Diagnostic]:
        # one finding per (rule, anchor): nested block checks may observe
        # the same offending statement from two enclosing constructs
        key = (rule, location.file if location else None,
               location.line if location is not None
               and location.line is not None else message)
        if key in self._seen:
            return None
        self._seen.add(key)
        d = Diagnostic(rule=rule, message=message, severity=severity,
                       location=location, hint=hint)
        self.diagnostics.append(d)
        return d

    def extend(self, diags: Iterable[Diagnostic]):
        self.diagnostics.extend(diags)

    def __iter__(self):
        return iter(self.diagnostics)

    def __len__(self):
        return len(self.diagnostics)


def has_errors(diags: Iterable[Diagnostic]) -> bool:
    return any(d.severity == Severity.ERROR for d in diags)


def render_text(diags: Iterable[Diagnostic]) -> str:
    diags = sorted(diags, key=lambda d: -Severity.rank(d.severity))
    if not diags:
        return "no findings"
    lines = [d.render() for d in diags]
    n_err = sum(1 for d in diags if d.severity == Severity.ERROR)
    n_warn = sum(1 for d in diags if d.severity == Severity.WARNING)
    lines.append(f"{len(diags)} finding(s): {n_err} error(s), "
                 f"{n_warn} warning(s)")
    return "\n".join(lines)


def render_json(diags: Iterable[Diagnostic]) -> str:
    return json.dumps([d.to_dict() for d in diags], indent=2)
