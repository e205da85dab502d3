#!/usr/bin/env python
"""Measured-search gate: plan + serving spaces end-to-end, on CPU.

One-command proof of the ``paddle_tpu.tuning`` contracts:

1. **Cold process** — with a fresh cache file, a sharding-plan search
   times REAL fused train steps (``Executor.run_steps`` on a tiny MLP
   program) per candidate, and a serving-config search replays the
   SAME deterministic fixed-seed request trace
   (``RequestTrace.synthetic``) against a real ``GenerationEngine``
   per candidate under a p99 budget.  Both winners persist to disk (schema v2, space-tagged),
   and — because the hand-set default is always in the running — the
   winner's measured score is no worse than the default's in the same
   search (tokens/s for serving, step time for the plan).
2. **Warm process** — a second, separate process over the same cache
   file resolves BOTH configs as pure disk hits with ZERO measured
   searches (the measure callbacks are rigged to explode if invoked),
   builds the tuned serving engine via ``from_tuned``, replays the
   trace after ``mark_warm()`` with K701 silent — then INJECTS a
   fresh post-warm search and requires K701 to fire, proving the
   detector still has teeth.

Prints one JSON line; exit 0 iff every phase holds.
"""
import json
import os
import subprocess
import sys
import tempfile
import time

_COMMON = """
import json, sys

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.static.graph import reset_default_programs
from paddle_tpu.tuning import (RequestTrace, engine, plan_space, replay,
                               serving_space)

N_STEPS = 4
PLAN_SHAPES = {"fc1.weight": (16, 32), "fc1.bias": (32,),
               "fc2.weight": (32, 1), "fc2.bias": (1,)}
BASE_SERVING = {"buckets": [16, 48], "batch_size": 8,
                "max_queue_delay_ms": 1.0}
TRACE = RequestTrace.synthetic(n=16)
BUDGET_MS = 120000.0  # generous on CPU: the budget MACHINERY is under test


def build_train():
    paddle.seed(0)
    reset_default_programs()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 16])
        y = fluid.data("y", [-1, 1])
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    return exe, main, loss


def plan_measure_factory():
    from paddle_tpu.distributed.fleet import DistributedStrategy

    exe, main, loss = build_train()
    rng = np.random.RandomState(0)
    X = rng.rand(N_STEPS, 8, 16).astype(np.float32)
    Y = rng.rand(N_STEPS, 8, 1).astype(np.float32)

    def run_step(config):
        # apply the candidate's collective dials, then run REAL fused
        # train steps — what run_steps returns is what gets timed
        plan_space.apply_plan(config, strategy=DistributedStrategy())
        return exe.run_steps(main, feed={"x": X, "y": Y},
                             fetch_list=[loss], iterations=N_STEPS)

    return plan_space.make_step_measure(run_step, repeats=2)


def build_model():
    paddle.seed(1234)
    cfg = GPTConfig(vocab_size=256, hidden_size=32, num_layers=1,
                    num_heads=2, max_position=128, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m
"""

_COLD = _COMMON + """
pd, sd, results = {}, {}, {}
plan_won = plan_space.tune_plan(
    "gate-plan", shapes=PLAN_SHAPES, measure=plan_measure_factory(),
    details=pd)

model = build_model()
factory = lambda cfg: GenerationEngine.from_tuned(model, cfg)
serve_won = serving_space.tune_serving(
    "gate-serve", BASE_SERVING, trace=TRACE, factory=factory,
    latency_budget_ms=BUDGET_MS,
    sweeps={"batch_size": (4, 16), "max_queue_delay_ms": (0.5,)},
    results=results, details=sd)

print(json.dumps({"counters": engine.get_counters(),
                  "plan": {"won": plan_won, "details": pd},
                  "serve": {"won": serve_won, "details": sd},
                  "cache_path": engine.cache_path()}))
"""

_WARM = _COMMON + """
from paddle_tpu.analysis import RetraceMonitor

boom = lambda cfg: (_ for _ in ()).throw(
    AssertionError("measured search ran in the warm process"))

with RetraceMonitor() as mon:
    plan_won = plan_space.tune_plan("gate-plan", shapes=PLAN_SHAPES,
                                    measure=boom)
    serve_won = serving_space.tune_serving("gate-serve", BASE_SERVING,
                                           trace=TRACE, measure=boom)
    # serve live traffic on the tuned config: warmup closes the compile
    # set and marks warm; the replayed trace must hit only cached configs
    model = build_model()
    with GenerationEngine.from_tuned(model, serve_won,
                                     name="tuned-replay") as eng:
        eng.warmup()
        stats = replay(eng, TRACE)
    k701_clean = [d for d in mon.diagnostics() if d.rule == "K701"]

# inject a post-warm search: K701 must fire for the serving space
with RetraceMonitor() as mon2:
    engine.mark_warm()
    serving_space.tune_serving("gate-serve-injected", BASE_SERVING,
                               trace=TRACE, measure=lambda cfg: 1.0)
    k701_injected = [d.message for d in mon2.diagnostics()
                     if d.rule == "K701"]

print(json.dumps({"counters": engine.get_counters(),
                  "plan_won": plan_won, "serve_won": serve_won,
                  "replay": stats,
                  "k701_clean": [d.message for d in k701_clean],
                  "k701_injected": k701_injected}))
"""


def _run_child(code, cache_file):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               FLAGS_measured_search="on",
               FLAGS_kernel_tuning_cache=cache_file)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise SystemExit(f"tune_smoke child failed (rc={proc.returncode})")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    t0 = time.time()
    fd, cache_file = tempfile.mkstemp(suffix=".json", prefix="tune_")
    os.close(fd)
    os.unlink(cache_file)  # children create it; start truly cold
    try:
        cold = _run_child(_COLD, cache_file)
        warm = _run_child(_WARM, cache_file)
        entries = json.load(open(cache_file)).get("entries", {})
    finally:
        if os.path.exists(cache_file):
            os.unlink(cache_file)

    cc, wc = cold["counters"], warm["counters"]
    pd = cold["plan"]["details"]
    sd = cold["serve"]["details"]
    spaces = sorted(e.get("space") for e in entries.values()
                    if e.get("name") in ("gate-plan", "gate-serve"))
    checks = {
        # cold: both spaces ran a real measured search and persisted
        "cold_plan_search": cc.get("gate-plan", {}).get("searches") == 1,
        "cold_serve_search": cc.get("gate-serve", {}).get("searches") == 1,
        "cold_plan_timed": pd.get("n_timed", 0) >= 2,
        "cold_serve_timed": sd.get("n_timed", 0) >= 2,
        "cache_both_spaces": spaces == ["plan", "serving"],
        "cache_schema_v2": all(e.get("version") == 2
                               for e in entries.values()),
        # winner no worse than the hand-set default IN THE SAME SEARCH
        # (the default is always a candidate, so this is measured, not
        # assumed: step ms for the plan, ms/token for serving)
        "plan_winner_no_worse": (pd.get("default_ms") is not None
                                 and pd["best_ms"] <= pd["default_ms"]),
        "serve_winner_no_worse": (sd.get("default_ms") is not None
                                  and sd["best_ms"] <= sd["default_ms"]),
        # warm: pure disk hits, zero measured searches, same winners
        "warm_zero_searches": all(
            wc.get(k, {}).get("searches", 0) == 0
            and wc.get(k, {}).get("configs_timed", 0) == 0
            for k in ("gate-plan", "gate-serve")),
        "warm_disk_hits": all(
            wc.get(k, {}).get("disk_hits") == 1
            for k in ("gate-plan", "gate-serve")),
        "winners_stable": (warm["plan_won"] == cold["plan"]["won"]
                           and warm["serve_won"] == cold["serve"]["won"]),
        # tuned engine actually serves the trace, p99 inside the budget
        "replay_tokens": warm["replay"]["tokens"] > 0,
        "replay_p99_in_budget": warm["replay"]["p99_ms"] <= 120000.0,
        # K701: silent on post-warm cache hits, fires on an injected
        # post-warm serving search
        "k701_clean_on_hits": warm["k701_clean"] == [],
        "k701_fires_injected": any(
            "gate-serve-injected" in m and "serving config" in m
            for m in warm["k701_injected"]),
    }
    ok = all(checks.values())
    print(json.dumps({
        "gate": "tune_smoke", "ok": ok, "checks": checks,
        "plan_won": cold["plan"]["won"],
        "serve_won": cold["serve"]["won"],
        "plan_ms": {"best": pd.get("best_ms"),
                    "default": pd.get("default_ms")},
        "serve_ms_per_tok": {"best": sd.get("best_ms"),
                             "default": sd.get("default_ms")},
        "replay": warm.get("replay"),
        "cache_entries": len(entries),
        "seconds": round(time.time() - t0, 1)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
