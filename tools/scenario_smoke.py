"""Autoscaling-loop + disaggregation gate: deterministic traffic chaos (CPU).

One-command proof that the closed autoscaling loop and prefill/decode
disaggregation hold their invariants under seeded open-loop traffic —
router + ``SloEngine`` + ``ReplicaPool`` driven together through
``serving.scenarios``:

1. **Lifecycle** — a flash crowd burns the latency budget, the SLO
   engine signals up, the :class:`ReplicaPool` cold-starts warmed
   replicas through the half-open admit path; the quiet tail scales back
   down.  Gates: the fleet scales up AND down inside its
   ``min..max`` bounds, zero thrash (rule S605 stays silent), zero
   accepted requests lost across four scenarios (flash crowd, diurnal,
   heavy tail, poison), every poison request cleanly rejected, no alert
   left burning at the end, and zero post-warmup XLA compiles outside
   pool cold-start windows — per-engine compile sets stay closed.
2. **Disaggregation** — the same prefill-heavy burst scenario replayed
   against a 2-replica co-located fleet and a 1+1
   prefill/decode-disaggregated fleet: decode-class (short-prompt) p99
   must be strictly better disaggregated, with bit-identical tokens
   request-for-request.

Prints one JSON line; exit 0 iff both gates hold.
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
from paddle_tpu.observability.slo import Objective, SloEngine  # noqa: E402
from paddle_tpu.serving import (DisaggServer, GenerationEngine,  # noqa: E402
                                ReplicaPool, Router, diurnal, flash_crowd,
                                heavy_tail, poison, run_scenario)

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


def _p99(values):
    return float(np.percentile(np.asarray(values, np.float64), 99))


def gate_lifecycle():
    """Flash crowd -> scale up through warm+probe admission; quiet tail
    -> drain-based scale down; four chaos scenarios with zero loss and a
    closed post-warmup compile set."""
    model = _model()
    made = []  # every engine the fleet ever ran, for the compile audit

    def factory():
        eng = GenerationEngine(model, prompt_buckets=[8, 16], batch_size=2,
                               kv_page_size=16,
                               name=f"scn-g{len(made)}")
        made.append(eng)
        return eng

    router = Router([factory()], name="scn-router")
    obs.enable()
    mon = RetraceMonitor().install()
    slo = SloEngine(
        [Objective.latency("gen_p99", threshold_ms=20.0,
                           engine=router.name, goal=0.9,
                           windows=((4.0, 1.0, 1.5),))],
        scale_down_burn=0.2)
    slo.install()
    slo.bind_router(router)
    pool = ReplicaPool(router, factory, min_replicas=1, max_replicas=3,
                       cooldown_s=1.5, up_consecutive=1, down_consecutive=2,
                       thrash_window_s=2.0, drain_timeout_s=30.0,
                       async_actions=False, name="scn-pool")
    warm_compiles = router.warmup()

    fleet_sizes = []
    samples = [(_XLA_COMPILES[0], 0, 0)]

    def tick(_t):
        slo.tick()
        fleet_sizes.append(len(router.replicas))
        snap = pool.stats()
        samples.append((_XLA_COMPILES[0], len(pool.action_spans),
                        snap["actions_inflight"]))

    scenarios = [
        flash_crowd(duration_s=8.0, base_rps=2.0, burst_rps=40.0,
                    burst_at=0.15, burst_frac=0.4, prompt_len=(4, 12),
                    max_new_tokens=(4, 8), burst_max_new_tokens=(16, 24),
                    seed=101),
        diurnal(duration_s=8.0, base_rps=1.0, peak_rps=2.5,
                prompt_len=(4, 12), max_new_tokens=(3, 6), seed=102),
        heavy_tail(duration_s=6.0, rps=2.5, prompt_len=(4, 12),
                   max_budget=16, seed=103),
        poison(duration_s=5.0, rps=4.0, poison_frac=0.3,
               oversize_len=4096, prompt_len=(4, 12),
               max_new_tokens=(3, 6), seed=104),
    ]
    try:
        reports = [run_scenario(router, s, tick=tick, tick_s=0.5,
                                result_timeout_s=120.0) for s in scenarios]
    finally:
        final = slo.snapshot()
        rules = [d.rule for d in mon.diagnostics()]
        pstats = pool.stats()
        pool.close()
        slo.close()
        mon.uninstall()
        obs.disable()
        router.close(timeout=30)

    # XLA attribution: between consecutive ticks where NO pool action
    # started, finished, or was in flight, the process must not compile —
    # serving replicas run a closed set; only cold-start windows compile.
    unattributed = 0
    for (c0, s0, i0), (c1, s1, i1) in zip(samples, samples[1:]):
        if s0 == s1 and i0 == 0 and i1 == 0 and c1 != c0:
            unattributed += c1 - c0
    # per-engine audit: every engine the fleet ever ran still has exactly
    # its warmup-time compile count (buckets + 3 paged executables, +0 for
    # the default role)
    per_engine = {e.name: e.compile_count for e in made}
    engines_closed = all(c == len([8, 16]) + 3 for c in per_engine.values())

    n_poison = sum(1 for ev in scenarios[3].events if ev.poison)
    return {
        "reports": [{k: v for k, v in r.items() if k != "records"}
                    for r in reports],
        "warm_compiles": warm_compiles,
        "scale_ups": pstats["scale_ups"],
        "scale_downs": pstats["scale_downs"],
        "scaled_up_and_down": (pstats["scale_ups"] >= 1
                               and pstats["scale_downs"] >= 1),
        "fleet_min": min(fleet_sizes),
        "fleet_max": max(fleet_sizes),
        "bounded": 1 <= min(fleet_sizes) and max(fleet_sizes) <= 3,
        "thrash_after_warm": pstats["thrash_events_after_warm"],
        "s605_silent": "S605" not in rules,
        "stale_signals": pstats["stale_signals"],
        "lost": sum(r["lost"] for r in reports),
        "failed": sum(r["failed"] for r in reports),
        "zero_loss": all(r["lost"] == 0 and r["failed"] == 0
                         for r in reports),
        "poison_events": n_poison,
        "poison_rejected": reports[3]["rejected"],
        "poison_clean": (reports[3]["rejected"] == n_poison
                         and reports[3]["poison_accepted"] == 0),
        "alerting_at_end": final.get("alerting", []),
        "budget_recovered": not final.get("alerting"),
        "unattributed_compiles": unattributed,
        "per_engine_compiles": per_engine,
        "compile_set_closed": engines_closed and unattributed == 0,
        "pool": pstats,
    }


def gate_disagg():
    """One prefill-heavy burst scenario, two fleet layouts, same total
    replica count: decode-class p99 must be strictly better
    disaggregated, tokens bit-identical request-for-request."""
    model = _model()
    buckets = [8, 64]

    def eng(role, name):
        return GenerationEngine(model, prompt_buckets=buckets, batch_size=2,
                                kv_page_size=16,
                                role=role, name=name)

    # decode-class victims: short prompts with LONG budgets, arriving
    # before and through a heavy burst of long-prompt/1-2-token requests
    # — pure prefill pressure.  Co-located, every burst admission runs a
    # 64-bucket forward between the victims' decode steps; disaggregated,
    # victims decode on a replica that only ever adopts pages.
    scenario = flash_crowd(
        duration_s=8.0, base_rps=3.0, burst_rps=60.0, burst_at=0.25,
        burst_frac=0.35, prompt_len=(4, 8), burst_prompt_len=(48, 64),
        max_new_tokens=(48, 64), burst_max_new_tokens=(1, 2), seed=211)

    colo = Router([eng("any", "colo-g0"), eng("any", "colo-g1")],
                  name="colo-rt")
    colo.warmup()
    try:
        colo_report = run_scenario(colo, scenario, result_timeout_s=120.0)
    finally:
        colo.close(timeout=30)

    disagg = DisaggServer(eng("prefill", "dis-pre"),
                          eng("decode", "dis-dec"), name="dis")
    disagg.warmup()
    try:
        dis_report = run_scenario(disagg, scenario, result_timeout_s=120.0)
    finally:
        disagg.close(timeout=30)

    def decode_class(report):
        return [r["latency_ms"] for r in report["records"]
                if r["ok"] and r["prompt_len"] <= 8]

    colo_p99 = _p99(decode_class(colo_report))
    dis_p99 = _p99(decode_class(dis_report))
    identical = (
        colo_report["completed"] == dis_report["completed"]
        and all(a["tokens"] == b["tokens"]
                for a, b in zip(colo_report["records"],
                                dis_report["records"])))
    return {
        "colo": {k: v for k, v in colo_report.items() if k != "records"},
        "disagg": {k: v for k, v in dis_report.items() if k != "records"},
        "colo_decode_p99_ms": round(colo_p99, 1),
        "disagg_decode_p99_ms": round(dis_p99, 1),
        "decode_p99_improved": dis_p99 < colo_p99,
        "zero_loss": (colo_report["lost"] == 0 and dis_report["lost"] == 0
                      and colo_report["failed"] == 0
                      and dis_report["failed"] == 0),
        "tokens_identical": identical,
    }


def main():
    t0 = time.time()
    life = gate_lifecycle()
    dis = gate_disagg()
    passed = (life["scaled_up_and_down"] and life["bounded"]
              and life["s605_silent"] and life["thrash_after_warm"] == 0
              and life["zero_loss"] and life["poison_clean"]
              and life["budget_recovered"] and life["compile_set_closed"]
              and dis["decode_p99_improved"] and dis["zero_loss"]
              and dis["tokens_identical"])
    print(json.dumps({"pass": bool(passed), "lifecycle": life,
                      "disagg": dis,
                      "seconds": round(time.time() - t0, 1)}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
