"""Multi-tenant serving gate: isolation, identity, closed set (CPU).

One-command proof of the tenancy subsystem's contracts, cheap enough
for every gate run:

1. **Mixed-vs-serial bit identity** — two LoRA tenants plus a base
   tenant interleaved on ONE paged engine under a
   :class:`TenantScheduler` must produce tokens bit-identical to
   per-tenant serial baselines on a fresh engine with explicit adapter
   ids: the batched adapter gather and the weighted-fair interleaving
   are invisible to every tenant's output.
2. **Adapter hot-add on a warm engine** — the second adapter installs
   MID-TRAFFIC and serves immediately, with ZERO post-warmup XLA
   compile events (table edits are argument edits, never recompiles).
3. **Noisy neighbor** — the seeded ``noisy_neighbor`` scenario with a
   hard (no-refill) token budget on the flooder: the flooder is capped
   near its budget while the victims' p99 stays within a bound of the
   flood-free run of the SAME victim schedule; zero victims lost.
4. **S607 silent on a healthy run** — the analysis monitor watching the
   mixed run must report no multi-tenant isolation findings.

Prints one JSON line; exit 0 iff all four gates hold.
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
from paddle_tpu.analysis import RetraceMonitor  # noqa: E402
from paddle_tpu.lora import random_adapter  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.serving import (GenerationEngine, TenantScheduler,  # noqa: E402
                                TenantSpec, noisy_neighbor, run_scenario)

# ground truth for "zero post-warmup recompiles": count actual XLA
# backend compile requests (fires even when the jaxpr cache hits)
_XLA_COMPILES = [0]
jax.monitoring.register_event_listener(
    lambda name, **kw: _XLA_COMPILES.__setitem__(0, _XLA_COMPILES[0] + 1)
    if name == "/jax/compilation_cache/compile_requests_use_cache" else None)

FLOOD_BUDGET = 30  # hard one-shot token cap for the flooder tenant
NOISY_SLOTS = 4


def _lora_model():
    pt.seed(11)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_position=64, dropout=0.0,
                    lora_capacity=2, lora_rank=4)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _plain_model():
    pt.seed(13)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_position=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def gate_mixed_and_hot_add(model):
    """Gates 1 + 2 + 4: serial baselines, then the mixed tenancy run
    with a mid-traffic adapter install, under the analysis monitor."""
    a0 = random_adapter(model, "acme-a", rank=4, seed=20, alpha=32.0,
                        std=0.2)
    a1 = random_adapter(model, "globex-a", rank=4, seed=21, alpha=32.0,
                        std=0.2)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 97, size=4 + (k % 5)).astype(np.int32)
               for k in range(4)]
    budgets = [6, 8, 5, 7]

    refs = {}
    with GenerationEngine(model, prompt_buckets=[16], batch_size=2,
                          cache_len=48, kv_page_size=8,
                          name="ten-smoke-serial") as ser:
        ser.install_adapter(0, a0)
        ser.install_adapter(1, a1)
        ser.warmup()
        for tn, aid in (("acme", 0), ("globex", 1), ("base", -1)):
            refs[tn] = [ser.generate(p, b, timeout=120,
                                     adapter_id=aid).tolist()
                        for p, b in zip(prompts, budgets)]

    ten = TenantScheduler([TenantSpec("acme", weight=2.0, adapter_id=0),
                           TenantSpec("globex", adapter_id=1),
                           TenantSpec("base", adapter_id=-1)])
    with RetraceMonitor(budget=8) as mon:
        with GenerationEngine(model, prompt_buckets=[16], batch_size=2,
                              cache_len=48, kv_page_size=8,
                              tenancy=ten, name="ten-smoke-mixed") as eng:
            eng.install_adapter(0, a0)  # adapter 1 hot-adds mid-traffic
            warm = eng.warmup()
            xla0 = _XLA_COMPILES[0]
            outs = {}
            # phase 1: acme + base interleaved
            futs = [(tn, i, eng.submit(p, b, tenant=tn))
                    for i, (p, b) in enumerate(zip(prompts, budgets))
                    for tn in ("acme", "base")]
            # phase 2: hot-add adapter 1 while phase-1 decode is live,
            # then serve globex through it immediately
            eng.install_adapter(1, a1)
            futs += [("globex", i, eng.submit(p, b, tenant="globex"))
                     for i, (p, b) in enumerate(zip(prompts, budgets))]
            mismatches = 0
            for tn, i, f in futs:
                out = f.result(120).tolist()
                outs.setdefault(tn, {})[i] = out
                if out != refs[tn][i]:
                    mismatches += 1
            xla_recompiles = _XLA_COMPILES[0] - xla0
            st = eng.stats()
            time.sleep(0.15)  # one publish tick carries the bus snapshot
        s607 = [d for d in mon.diagnostics() if d.rule == "S607"]
    return {
        "bit_identical_mixed_vs_serial": mismatches == 0,
        "mismatches": mismatches,
        "warmup_compiles": warm,
        "hot_add_xla_recompiles": xla_recompiles,
        "hot_add_closed": (xla_recompiles == 0
                           and st["compile_count"] == warm),
        "adapter_installs": int(st.get("adapter_installs", 0)),
        "completed": int(st.get("completed", 0)),
        "s607_findings": len(s607),
        "s607_silent": not s607,
    }


def gate_noisy_neighbor(model):
    """Gate 3: the flooder's hard budget caps its delivered tokens while
    the victims' p99 stays within a bound of the flood-free run."""
    kw = dict(duration_s=4.0, tenants=("acme", "globex"),
              flooder="initech", rps=3.0, flood_at=0.2, seed=5)
    flooded = noisy_neighbor(flood_rps=15.0, **kw)
    calm = noisy_neighbor(flood_rps=0.001, **kw)  # no flood arrivals

    def run(scenario):
        ten = TenantScheduler([
            TenantSpec("acme"), TenantSpec("globex"),
            TenantSpec("initech", token_budget=FLOOD_BUDGET)])
        with GenerationEngine(model, prompt_buckets=[16],
                              batch_size=NOISY_SLOTS, cache_len=32,
                              kv_page_size=8, tenancy=ten,
                              name="ten-smoke-noisy") as eng:
            eng.warmup()
            rep = run_scenario(eng, scenario, deadline_ms=8000.0,
                               result_timeout_s=120.0)
            stats = eng.stats()
        return rep, stats

    rep_f, st_f = run(flooded)
    rep_c, _ = run(calm)

    def victim_p99(rep):
        lat = sorted(r["latency_ms"] for r in rep["records"]
                     if r["tenant"] in ("acme", "globex") and r.get("ok"))
        return lat[min(int(round(0.99 * len(lat))), len(lat) - 1)] \
            if lat else -1.0

    def victims_done(rep):
        recs = [r for r in rep["records"]
                if r["tenant"] in ("acme", "globex")]
        return (len(recs),
                sum(1 for r in recs if r.get("ok")))

    flood_tokens = sum(len(r["tokens"]) for r in rep_f["records"]
                       if r["tenant"] == "initech" and r.get("ok"))
    n_victims, ok_victims = victims_done(rep_f)
    p99_f, p99_c = victim_p99(rep_f), victim_p99(rep_c)
    # the flooder can overshoot by at most the in-flight slots' budgets
    # (charges land at harvest; the next step preempts)
    cap = FLOOD_BUDGET + NOISY_SLOTS * 8
    # generous CPU-timing bound: flooded victim p99 within 4x + 250ms of
    # the flood-free p99 of the SAME victim arrival schedule
    bound_ms = 4.0 * max(p99_c, 1.0) + 250.0
    return {
        "flood_requests": sum(1 for r in rep_f["records"]
                              if r["tenant"] == "initech"),
        "flooder_tokens": flood_tokens,
        "flooder_budget": FLOOD_BUDGET,
        "flooder_capped": bool(flood_tokens <= cap),
        "victims": n_victims,
        "victims_completed": ok_victims,
        "victims_all_served": bool(ok_victims == n_victims
                                   and rep_f["lost"] == 0),
        "victim_p99_ms_flooded": round(p99_f, 1),
        "victim_p99_ms_calm": round(p99_c, 1),
        "victim_p99_bound_ms": round(bound_ms, 1),
        "victim_p99_within_bound": bool(0 < p99_f <= bound_ms),
        "tenant_preempted": int(st_f.get("tenant_preempted", 0)),
        "throttled_steps": int(st_f.get("tenant_throttled_steps", 0)),
    }


def main():
    t0 = time.time()
    mixed = gate_mixed_and_hot_add(_lora_model())
    noisy = gate_noisy_neighbor(_plain_model())
    passed = (mixed["bit_identical_mixed_vs_serial"]
              and mixed["hot_add_closed"]
              and mixed["s607_silent"]
              and noisy["flooder_capped"]
              and noisy["victims_all_served"]
              and noisy["victim_p99_within_bound"])
    print(json.dumps({"pass": bool(passed), "mixed": mixed,
                      "noisy": noisy,
                      "seconds": round(time.time() - t0, 1)}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
