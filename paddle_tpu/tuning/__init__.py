"""Measured-search tuning: one engine, two config spaces.

``tuning.engine`` is the generic search core (enumerate → pre-filter →
compile+time on the real backend → persistent JSON cache → counters /
trace events).  Its clients:

* ``tuning.plan_space`` — per-parameter-group mesh-axis assignment and
  collective schedule dials, pre-filtered by ``analysis.check_plan``,
  timed as real train steps (space ``"plan"``);
* ``tuning.serving_space`` — bucket sets, slot count, batching delay,
  KV page size, speculative k, timed against a replayed request trace
  under a latency budget (space ``"serving"``).

``tuning.trace`` records and replays the deterministic request traces
the serving space measures against.

Only the engine is imported eagerly — ``ops.autotune`` re-exports two of
its names, so the config-space modules (which import analysis/distributed/
serving machinery on top of ops) load lazily via ``__getattr__``.
"""
from . import engine  # noqa: F401
from .engine import (  # noqa: F401
    CandidateError,
    clear_cache,
    get_counters,
    is_warm,
    mark_warm,
    measure_ms,
    reset_counters,
    reset_warm,
    resolve,
)

__all__ = [
    "engine", "CandidateError", "resolve", "measure_ms", "clear_cache",
    "get_counters", "reset_counters", "mark_warm", "is_warm", "reset_warm",
    "RequestTrace", "TraceRecorder", "replay",
    "plan_candidates", "tune_plan", "apply_plan",
    "serving_candidates", "tune_serving",
]

_LAZY = {
    "RequestTrace": "trace", "TraceRecorder": "trace", "replay": "trace",
    "plan_candidates": "plan_space", "tune_plan": "plan_space",
    "apply_plan": "plan_space",
    "serving_candidates": "serving_space", "tune_serving": "serving_space",
    "trace": None, "plan_space": None, "serving_space": None,
}


def __getattr__(name):
    mod = _LAZY.get(name, KeyError)
    if mod is KeyError:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    module = importlib.import_module(f".{mod or name}", __name__)
    return module if mod is None else getattr(module, name)
