"""Tracing + SLO gate: end-to-end spans, burn-rate alert, scale signal (CPU).

One-command proof of the request-tracing and SLO-engine contracts over a
live 2-replica continuous-batching router:

1. **Trace completeness + closed compile set** — with tracing enabled, a
   routed generation produces router/submit, router/dispatch,
   batcher/queue, slot/admit, slot/decode and slot/evict spans sharing
   one trace_id in the merged chrome export, with zero post-warmup XLA
   compiles (tracing must not perturb the compile cache).
2. **Burn-rate alert + scale signal** — an injected decode latency fault
   (150 ms per step) burns the p99 latency budget: the SLO engine
   alerts on both windows, analysis rule M903 fires (post-warmup burn),
   and the Router receives a scale-up :class:`ScaleSignal` through
   ``bind_router``.
3. **Off means off** — with tracing disabled, routed traffic records
   nothing (a fresh tracer enabled afterwards has seen zero spans).

Prints one JSON line; exit 0 iff all three gates hold.
"""
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.monitoring  # noqa: E402
import numpy as np  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.analysis import RetraceMonitor  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402
from paddle_tpu.observability.slo import Objective, SloEngine  # noqa: E402
from paddle_tpu.resilience import FaultPlan  # noqa: E402
from paddle_tpu.resilience import retry as _retry  # noqa: E402
from paddle_tpu.serving import GenerationEngine, Router  # noqa: E402

BUCKETS = [8, 16]
REQUIRED_SPANS = ("router/submit", "router/dispatch", "batcher/queue",
                  "slot/admit", "slot/decode", "slot/evict")

_XLA_COMPILES = [0]
jax.monitoring.register_event_listener(
    lambda name, **kw: _XLA_COMPILES.__setitem__(0, _XLA_COMPILES[0] + 1)
    if name == "/jax/compilation_cache/compile_requests_use_cache" else None)


def _model():
    pt.seed(11)
    cfg = GPTConfig(vocab_size=97, hidden_size=128, num_layers=2,
                    num_heads=4, max_position=256, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _traffic(router, n=4, tokens=3):
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 97, size=4 + k).astype(np.int32)
               for k in range(n)]
    futs = [router.submit(p, max_new_tokens=tokens) for p in prompts]
    return [f.result(120) for f in futs]


def gate_trace(router, workdir):
    """Full router->slot span tree in the merged chrome export, zero
    post-warmup compiles with tracing on."""
    tracing.enable()
    xla0 = _XLA_COMPILES[0]
    _traffic(router)
    time.sleep(0.3)  # let the engine loops commit the evict spans
    recompiles = _XLA_COMPILES[0] - xla0

    base = os.path.join(workdir, "requests.jsonl")
    out = os.path.join(workdir, "requests.chrome.json")
    tracing.export_jsonl(base, process_index=0)
    n_events = tracing.merge_chrome(base, out)
    with open(out) as f:
        doc = json.load(f)
    by_trace = {}
    for ev in doc["traceEvents"]:
        by_trace.setdefault(ev["args"]["trace_id"], set()).add(ev["name"])
    complete = [tid for tid, names in by_trace.items()
                if all(r in names for r in REQUIRED_SPANS)]
    return {
        "merged_events": n_events,
        "traces": len(by_trace),
        "complete_traces": len(complete),
        "trace_complete": bool(complete),
        "xla_recompiles_post_warmup": recompiles,
        "closed_compile_set": recompiles == 0,
        "tracer": tracing.active().stats(),
    }


def gate_slo(router):
    """Injected decode latency burns the budget: multi-window alert, M903
    after warmup, scale-up signal delivered to the router."""
    obs.enable()
    mon = RetraceMonitor().install()
    eng = SloEngine(
        [Objective.latency("gen_p99", threshold_ms=100.0,
                           engine=router.name, goal=0.99,
                           windows=((8.0, 2.0, 2.0),))])
    eng.install()
    eng.bind_router(router)
    _retry.mark_warm()  # post-warmup burn is what M903 is about
    up0 = router.metrics.snapshot().get("scale_up_signals", 0)
    try:
        with FaultPlan.parse("site=serving.decode,every=1,latency_ms=150"):
            for _ in range(3):
                _traffic(router, n=2)
                eng.tick()
                time.sleep(0.2)
        eng.tick()
        snap = eng.snapshot()
        rules = [d.rule for d in mon.diagnostics()]
        up = router.metrics.snapshot().get("scale_up_signals", 0) - up0
        return {
            "alerts": snap["alerts"],
            "alerts_after_warm": snap["alerts_after_warm"],
            "max_burn": round(snap["max_burn"], 1),
            "alerting": snap["alerting"],
            "m903": "M903" in rules,
            "scale_up_signals": up,
            "scaled_up": up >= 1,
            "last_signal": snap["last_signal"],
        }
    finally:
        eng.close()
        mon.uninstall()
        obs.disable()  # also disables tracing


def gate_off(router):
    """Disabled tracing records nothing — the single-falsy-check hooks
    must be inert."""
    assert tracing.active() is None
    _traffic(router, n=2)
    time.sleep(0.2)
    tr = tracing.enable()  # fresh tracer, after the traffic
    try:
        return {"recorded_while_off": tr.stats()["recorded"],
                "off_means_off": tr.stats()["recorded"] == 0}
    finally:
        tracing.disable()


def main():
    import tempfile

    t0 = time.time()
    model = _model()
    engines = [GenerationEngine(model, prompt_buckets=BUCKETS, batch_size=2,
                                name=f"slo-smoke-g{i}")
               for i in range(2)]
    router = Router(engines, name="slo-smoke-router", probe_interval_s=0.2)
    try:
        router.warmup()
        with tempfile.TemporaryDirectory() as d:
            trace = gate_trace(router, d)
        slo = gate_slo(router)
        off = gate_off(router)
    finally:
        router.close(timeout=30)
    passed = (trace["trace_complete"] and trace["closed_compile_set"]
              and slo["alerts_after_warm"] >= 1 and slo["m903"]
              and slo["scaled_up"] and off["off_means_off"])
    print(json.dumps({"pass": bool(passed), "trace": trace, "slo": slo,
                      "off": off, "seconds": round(time.time() - t0, 1)}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
