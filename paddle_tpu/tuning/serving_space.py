"""Serving-config measured search — the ``"serving"`` client of the
engine.

Every serving dial added since PR 7 is hand-set: the bucket set, the
slot count B, the micro-batcher's ``max_batch_size`` /
``max_queue_delay_ms``, and PR 11's ``FLAGS_kv_page_size`` /
``FLAGS_speculative_k``.  This module races candidate dial settings
against a DETERMINISTIC replayed request trace (``tuning.trace``) —
same prompts, same output lengths, same submission order for every
candidate — scoring milliseconds per generated token (lower is better)
under a hard p99 latency budget: a throughput winner that blows the
declared p99 is rejected (``CandidateError`` → a counted search
failure), so the tuner can never trade tail latency for tokens/s.

A candidate config is JSON-plain and maps onto
``GenerationEngine.from_tuned`` / ``InferenceEngine.from_tuned``::

    {"buckets": [16, 48], "batch_size": 8, "max_queue_delay_ms": 1.0,
     "kv_page_size": 64, "speculative_k": 4, "quantization": "int8"}

Winners persist in the shared tuning cache keyed
``serving | tag | trace digest | mesh | device_kind`` — a tuned config
is only a cache hit against the workload it was measured on.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

from ..framework.flags import flag
from . import engine
from .trace import RequestTrace, replay

__all__ = ["serving_candidates", "tune_serving", "make_replay_measure"]

#: per-dial sweep values for the coordinate search; ``None`` entries in
#: a dial's sweep mean "leave at the base value"
DIAL_SWEEPS = {
    "batch_size": (2, 4, 8, 16),
    "max_queue_delay_ms": (0.5, 1.0, 2.0, 5.0),
    "kv_page_size": (32, 64, 128),
    "speculative_k": (0, 2, 4),
    # serving precision is a measured dial like any other: the replay
    # scores quantized candidates on the same trace, so int8/fp8 wins
    # only where its tokens/s actually beats the float engine's
    "quantization": ("none", "int8", "fp8"),
    # multi-LoRA adapter-table capacity (GPTConfig.lora_capacity): every
    # decode step gathers over the whole fixed table, so capacity is a
    # per-step cost dial — swept only when the base config exposes it
    # (dials absent from base are skipped, like every other dial)
    "lora_capacity": (4, 8, 16),
}


def serving_candidates(base: Dict, *,
                       bucket_sets: Optional[Sequence[Sequence[int]]] = None,
                       sweeps: Optional[Dict[str, Sequence]] = None,
                       max_candidates: int = 24) -> List[dict]:
    """Coordinate sweep around ``base``: one dial varied at a time (plus
    each alternative bucket set), base first — so the hand-set default is
    always in the running and measurement cost stays linear in the knob
    count rather than exponential."""
    base = dict(base)
    out: List[dict] = [dict(base)]
    for bs in (bucket_sets or []):
        c = dict(base)
        c["buckets"] = [int(b) for b in bs]
        out.append(c)
    for dial, values in sorted((sweeps or DIAL_SWEEPS).items()):
        if dial not in base:
            continue  # dial not exposed by this engine's config
        for v in values:
            if v is None:
                continue
            c = dict(base)
            c[dial] = v
            out.append(c)
    return engine.dedup_candidates(out[:max_candidates], dict(base))


def make_replay_measure(factory: Callable[[dict], object],
                        trace: RequestTrace, *,
                        latency_budget_ms: Optional[float] = None,
                        results: Optional[dict] = None,
                        ) -> Callable[[dict], float]:
    """The default serving measure: build the engine for one candidate
    (``factory(config)`` returns a context manager — e.g.
    ``lambda cfg: GenerationEngine.from_tuned(model, cfg)``), warm it,
    replay the trace, and score ms per generated token.  Candidates whose
    p99 exceeds the budget raise :class:`engine.CandidateError` and count
    as search failures.  ``results`` (optional dict) collects each
    candidate's full replay stats keyed by config repr, for gate
    assertions."""

    def measure(config: dict) -> float:
        # each candidate's warmup() calls mark_warm(), but a throwaway
        # measurement engine is not the production engine going hot —
        # restore the flag so the tuner's own search can't raise K701
        was_warm = engine.is_warm()
        try:
            with factory(config) as eng:
                eng.warmup()
                stats = replay(eng, trace)
        finally:
            if not was_warm:
                engine.reset_warm()
        if results is not None:
            results[repr(sorted(config.items()))] = dict(stats)
        if (latency_budget_ms is not None
                and stats["p99_ms"] > float(latency_budget_ms)):
            raise engine.CandidateError(
                f"p99 {stats['p99_ms']}ms exceeds the "
                f"{latency_budget_ms}ms budget")
        return 1e3 / max(stats["tokens_per_sec"], 1e-9)  # ms per token

    return measure


def tune_serving(tag: str, base: Dict, *,
                 trace: RequestTrace,
                 factory: Optional[Callable[[dict], object]] = None,
                 measure: Optional[Callable[[dict], float]] = None,
                 latency_budget_ms: Optional[float] = None,
                 bucket_sets: Optional[Sequence[Sequence[int]]] = None,
                 sweeps: Optional[Dict[str, Sequence]] = None,
                 max_candidates: int = 24,
                 results: Optional[dict] = None,
                 details: Optional[dict] = None) -> dict:
    """Measured search over serving configs for one workload ``tag``.

    Supply either ``factory`` (engine builder — the default measure
    warms it and replays ``trace``) or a custom ``measure(config) ->
    score`` (lower is better; tests inject deterministic scorers).  Off
    (``FLAGS_measured_search=off``) the hand-set ``base`` is returned
    untimed.  The winner persists in the shared tuning cache and is
    applied by the caller via ``*.from_tuned``."""
    if measure is None:
        if factory is None:
            raise TypeError("tune_serving needs a factory or a measure")
        measure = make_replay_measure(factory, trace,
                                      latency_budget_ms=latency_budget_ms,
                                      results=results)
    key = "|".join([tag, trace.key(), engine.mesh_key(),
                    engine.device_kind()])
    measurable = str(flag("measured_search")).lower() != "off"
    return engine.resolve(
        "serving", tag, key,
        candidates=lambda: serving_candidates(
            base, bucket_sets=bucket_sets, sweeps=sweeps,
            max_candidates=max_candidates),
        measure=measure,
        heuristic=dict(base),
        measurable=measurable,
        details=details)
