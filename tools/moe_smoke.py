"""Expert-sharded decode gate: closed compile set + balanced routing (CPU).

One-command proof of the MoE serving contracts (paddle_tpu/moe):

1. **Closed compile set, tokens exact** — a 4-expert top-2 GPT behind the
   continuous-batching engine decodes with the per-step router INSIDE the
   jitted step: ``compile_count`` stays at ``len(prompt_buckets) + 3`` and
   zero post-warmup XLA compile requests fire.  With ample expert capacity
   (``moe_capacity_factor >= num_experts`` ⇒ no token ever dropped) the
   generated tokens are bit-identical to the eager greedy reference —
   routing inside the engine's padded batch changes nothing.
2. **Occupancy counters on the bus** — the ``("serving", <name>)``
   snapshot carries the ``moe_routed_tokens`` / ``moe_dropped_tokens`` /
   post-warmup step counters plus the ``moe_overflow_frac`` and
   ``moe_dead_experts`` gauges; the healthy run must show every expert
   receiving traffic (no dead experts), zero overflow, and rule S606
   silent on a live RetraceMonitor.
3. **Zero-expert config untouched** — the same engine build with
   ``moe_experts=0`` produces identical tokens to an unwrapped dense run
   and publishes NO moe keys (the tap is never installed).

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
from paddle_tpu.analysis import RetraceMonitor  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.serving import GenerationEngine  # noqa: E402

BUCKETS = [16]
EXPERTS = 4
REQS = 6
NEW_TOKENS = 12

# ground truth for "zero post-warmup recompiles": actual XLA backend
# compile requests, which fire even when the jaxpr cache hits
_XLA_COMPILES = [0]
jax.monitoring.register_event_listener(
    lambda name, **kw: _XLA_COMPILES.__setitem__(0, _XLA_COMPILES[0] + 1)
    if name == "/jax/compilation_cache/compile_requests_use_cache" else None)


def _model(experts: int):
    pt.seed(21)
    # capacity_factor = num_experts makes C = top_k * tokens: no token can
    # overflow, so engine-batched routing is per-token independent and the
    # tokens must match the eager reference bit-for-bit
    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                    num_heads=4, max_position=128, dropout=0.0,
                    moe_experts=experts, moe_top_k=2,
                    moe_capacity_factor=float(max(experts, 1)),
                    moe_jitter=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _ref(model, prompt, n):
    import jax.numpy as jnp
    ids, outs = list(map(int, prompt)), []
    for _ in range(n):
        logits = np.asarray(model(jnp.asarray([ids], jnp.int32)))[0]
        outs.append(int(np.argmax(logits[-1])))
        ids.append(outs[-1])
    return outs


def _drive(model, name):
    """Run the mixed workload; returns (outs, refs, engine stats, compile
    accounting)."""
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 97, size=4 + (k % 9)).astype(np.int32)
               for k in range(REQS)]
    refs = [_ref(model, p, NEW_TOKENS) for p in prompts]
    with GenerationEngine(model, prompt_buckets=BUCKETS, batch_size=2,
                          name=name) as eng:
        warm = eng.warmup()
        xla0 = _XLA_COMPILES[0]
        futs = [eng.submit(p, NEW_TOKENS) for p in prompts]
        outs = [f.result(600).tolist() for f in futs]
        # the harvest is one step deferred; one more publish closes it out
        time.sleep(0.05)
        st = eng.stats()
        compiles = eng.compile_count
    return {"outs": outs, "refs": refs, "stats": st, "warm": warm,
            "compiles": compiles, "xla": _XLA_COMPILES[0] - xla0}


def gate_moe():
    with RetraceMonitor() as mon:
        r = _drive(_model(EXPERTS), "moe-smoke")
        s606 = [d for d in mon.diagnostics() if d.rule == "S606"]
    st = r["stats"]
    routed = int(st.get("moe_routed_tokens", 0))
    dropped = int(st.get("moe_dropped_tokens", 0))
    sampled = int(st.get("moe_sampled_steps_after_warm", 0))
    return {
        "token_identical": bool(r["outs"] == r["refs"]),
        "warmup_compiles": r["warm"],
        "closed_compile_set": (r["compiles"] == len(BUCKETS) + 3
                               and r["xla"] == 0),
        "xla_recompiles_post_warmup": r["xla"],
        "moe_routed_tokens": routed,
        "moe_dropped_tokens": dropped,
        "moe_sampled_steps_after_warm": sampled,
        "moe_overflow_frac": float(st.get("moe_overflow_frac", -1.0)),
        "moe_dead_experts": float(st.get("moe_dead_experts", -1.0)),
        "counters_flow": bool(routed > 0 and sampled > 0),
        "balanced": bool(dropped == 0
                         and float(st.get("moe_overflow_frac", 1.0)) == 0.0
                         and float(st.get("moe_dead_experts", 1.0)) == 0.0),
        "s606_silent": not s606,
    }


def gate_dense():
    r = _drive(_model(0), "moe-smoke-dense")
    moe_keys = [k for k in r["stats"] if k.startswith("moe_")]
    return {
        "token_identical": bool(r["outs"] == r["refs"]),
        "closed_compile_set": (r["compiles"] == len(BUCKETS) + 3
                               and r["xla"] == 0),
        "no_moe_keys": not moe_keys,
        "moe_keys": moe_keys,
    }


def main():
    t0 = time.time()
    moe = gate_moe()
    dense = gate_dense()
    passed = (moe["token_identical"] and moe["closed_compile_set"]
              and moe["counters_flow"] and moe["balanced"]
              and moe["s606_silent"]
              and dense["token_identical"] and dense["closed_compile_set"]
              and dense["no_moe_keys"])
    print(json.dumps({"pass": bool(passed), "moe": moe, "dense": dense,
                      "seconds": round(time.time() - t0, 1)}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
