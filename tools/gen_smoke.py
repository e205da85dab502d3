"""Continuous-batching gate: zero loss, closed compile set, probes (CPU).

One-command proof of the decode data plane's contracts, cheap enough for
every gate run:

1. **Token identity + closed compile set** — 1 long request and many
   short ones admitted while it decodes must come back token-identical to
   uncached greedy, none lost, with zero post-warmup recompiles
   (``compile_count`` stays at ``len(prompt_buckets) + 3``: the default
   engine speculates).
2. **Router probe compat** — a health-probed :class:`Router` over two
   engines stays green (``synthetic_inputs`` probes succeed, routed
   generations are token-identical).
3. **Page sharing + speculative decoding** — a mixed shared-prefix
   workload through a 4-slot engine whose pool is HALF of what four whole
   ``cache_len`` windows would take (32 pages of 16 = two windows of
   256): more than two slots resident at the peak, tokens bit-identical
   to uncached greedy, zero post-warmup XLA compiles on the compile set
   (``len(prompt_buckets) + 3``).

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
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.serving import GenerationEngine, Router  # noqa: E402

BUCKETS = [8, 16]
LONG_TOKENS = 240  # prompt 12 + 240 stays inside the 256-position window
SHORTS = 6
SHORT_TOKENS = 3

# page-sharing gate geometry: a pool of two whole windows (2 x 256
# positions in pages of 16) behind four slots — the engine must hold
# strictly more resident slots in it than whole windows would fit
CACHE = 256
PAGE_SIZE = 16
WINDOW_SLOTS = 2
POOL_PAGES = WINDOW_SLOTS * CACHE // PAGE_SIZE  # 32 pages
PAGED_SLOTS = 4
PAGED_REQS = 12
PAGED_TOKENS = 32
PREFIX_LEN = 20  # shared system prompt: 1 full page + a CoW'd boundary

# ground truth for "zero post-warmup recompiles": count actual XLA backend
# compile requests, which fire even when the jaxpr cache hits (e.g. the
# silent placement-specialised recompiles the trace counter cannot see)
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


def _paged_model():
    pt.seed(13)
    # hidden 32 keeps the CPU decode step dispatch-dominated rather than
    # FLOP-dominated — the regime the paged gate is about (accelerator
    # decode is latency-bound, so batching 4 slots x 5 verify positions
    # into one step costs ~one step, not 20 token-forwards)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_position=CACHE, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _is_greedy(model, prompt, out, n):
    """``out`` is the ``n`` tokens uncached greedy decoding gives after
    ``prompt``: one teacher-forced forward over prompt + out, whose argmax
    after every prefix must be the next token (by induction the same as
    decoding token by token, at one forward instead of ``n``)."""
    import jax.numpy as jnp
    if out is None or len(out) != n:
        return False
    ids = list(map(int, prompt)) + list(out)
    logits = np.asarray(model(jnp.asarray([ids], jnp.int32)))[0]
    nxt = np.argmax(logits, axis=-1)[len(prompt) - 1:-1]
    return nxt.tolist() == list(out)


def _mixed_traffic(eng):
    """1 long + SHORTS shorts submitted while the long one decodes.
    Returns ((long prompt, short prompts, results), lost)."""
    rng = np.random.RandomState(3)
    long_p = rng.randint(1, 97, size=12).astype(np.int32)
    shorts = [rng.randint(1, 97, size=3 + (k % 5)).astype(np.int32)
              for k in range(SHORTS)]
    fl = eng.submit(long_p, LONG_TOKENS)
    time.sleep(0.01)  # the long request is decoding by now
    fs = [eng.submit(p, SHORT_TOKENS) for p in shorts]
    lost = 0
    results = {}
    try:
        results["long"] = fl.result(600).tolist()
    except Exception:
        lost += 1
    for k, f in enumerate(fs):
        try:
            results[k] = f.result(600).tolist()
        except Exception:
            lost += 1
    return (long_p, shorts, results), lost


def gate_mixed(model):
    with GenerationEngine(model, prompt_buckets=BUCKETS, batch_size=2,
                          name="gen-smoke-mixed") as eng:
        warm = eng.warmup()
        xla0 = _XLA_COMPILES[0]
        (long_p, shorts, results), lost = _mixed_traffic(eng)
        xla_recompiles = _XLA_COMPILES[0] - xla0
        compiles = eng.compile_count

    identical = (_is_greedy(model, long_p, results.get("long"), LONG_TOKENS)
                 and all(_is_greedy(model, p, results.get(k), SHORT_TOKENS)
                         for k, p in enumerate(shorts)))
    return {
        "token_identical": bool(identical),
        "warmup_compiles": warm,
        "closed_compile_set": (compiles == len(BUCKETS) + 3
                               and xla_recompiles == 0),
        "xla_recompiles_post_warmup": xla_recompiles,
        "lost": lost,
    }


def gate_router_probe(model):
    engines = [GenerationEngine(model, prompt_buckets=BUCKETS, batch_size=2,
                                name=f"gen-smoke-r{i}")
               for i in range(2)]
    router = Router(engines, name="gen-smoke-router", probe_interval_s=0.2)
    try:
        router.warmup()
        rng = np.random.RandomState(5)
        prompts = [rng.randint(1, 97, size=4 + k).astype(np.int32)
                   for k in range(4)]
        outs = [router.submit(p, max_new_tokens=3).result(120).tolist()
                for p in prompts]
        identical = all(_is_greedy(model, p, o, 3)
                        for p, o in zip(prompts, outs))
        time.sleep(0.6)  # a few background probe sweeps
        st = router.stats()
        return {"routed_identical": bool(identical),
                "healthy": router.healthy_count(),
                "replicas": len(engines),
                "probes": st.get("probes", 0),
                "probe_failures": st.get("probe_failures", 0)}
    finally:
        router.close(timeout=30)  # close_engines=True: replicas too


def gate_paged(model):
    """Four slots over a pool of two whole windows, on one shared-prefix
    workload: more than two slots resident, bit-identical, zero
    post-warmup compiles."""
    rng = np.random.RandomState(7)
    sysp = rng.randint(1, 97, size=PREFIX_LEN).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.randint(1, 97, size=2 + (k % 7))])
               .astype(np.int32) for k in range(PAGED_REQS)]

    with GenerationEngine(
            model, prompt_buckets=[32], batch_size=PAGED_SLOTS,
            cache_len=CACHE, kv_pages=POOL_PAGES, kv_page_size=PAGE_SIZE,
            speculative_k=4, name="gen-smoke-paged") as eng:
        eng.warmup()
        xla0 = _XLA_COMPILES[0]
        futs = [eng.submit(p, PAGED_TOKENS, prefix_key="sys",
                           prefix_len=PREFIX_LEN) for p in prompts]
        # peak resident slots: admitted/evicted counters update at
        # the event (the occupancy gauge only publishes every 0.1s)
        peak, pend = 0, set(range(len(futs)))
        while pend:
            pend = {k for k in pend if not futs[k].done()}
            st = eng.stats()
            peak = max(peak, min(int(st.get("admitted", 0))
                                 - int(st.get("evicted", 0)), PAGED_SLOTS))
            time.sleep(0.005)
        outs = []
        for f in futs:
            try:
                outs.append(f.result(1).tolist())
            except Exception:
                outs.append(None)
        pst = eng.stats()
        xla = _XLA_COMPILES[0] - xla0
    drafted = int(pst.get("spec_drafted", 0))
    # paged-flash dispatch gate: on this CPU host the engine MUST have
    # used the gather-then-attend fallback (so the bit-identity above is
    # the fallback's correctness proof), while the same geometry on a
    # TPU backend must select the Pallas kernel (ops/paged_attention.py)
    from paddle_tpu.ops.paged_attention import paged_flash_eligible
    hd = 32 // 4  # gate_paged model: hidden 32, 4 heads
    return {
        "flash_fallback_on_cpu": not paged_flash_eligible(hd, PAGE_SIZE),
        "flash_selected_on_tpu": paged_flash_eligible(hd, PAGE_SIZE,
                                                      backend="tpu"),
        "token_identical": all(_is_greedy(model, p, o, PAGED_TOKENS)
                               for p, o in zip(prompts, outs)),
        "hbm_budget_pages": POOL_PAGES,  # WINDOW_SLOTS * CACHE / PAGE_SIZE
        "whole_window_slots": WINDOW_SLOTS,
        "peak_slots": peak,
        "resident_slots_up": bool(peak > WINDOW_SLOTS),
        # buckets [32] -> admit + verify step + [B,1] fast step + cow
        "closed_compile_set": (pst["compile_count"] == 1 + 3
                               and xla == 0),
        "xla_recompiles_post_warmup": xla,
        "prefix_hits": int(pst.get("prefix_hits", 0)),
        "cow_copies": int(pst.get("cow_copies", 0)),
        "spec_accept_rate": round(
            int(pst.get("spec_accepted", 0)) / drafted, 2) if drafted else 0.0,
        "preempted": int(pst.get("preempted", 0)),
    }


def main():
    t0 = time.time()
    model = _model()
    mixed = gate_mixed(model)
    probe = gate_router_probe(model)
    paged = gate_paged(_paged_model())
    passed = (mixed["token_identical"]
              and mixed["closed_compile_set"] and mixed["lost"] == 0
              and probe["routed_identical"]
              and probe["healthy"] == probe["replicas"]
              and probe["probe_failures"] == 0
              and paged["token_identical"]
              and paged["resident_slots_up"]
              and paged["closed_compile_set"]
              and paged["flash_fallback_on_cpu"]
              and paged["flash_selected_on_tpu"])
    print(json.dumps({"pass": bool(passed), "mixed": mixed, "probe": probe,
                      "paged": paged,
                      "seconds": round(time.time() - t0, 1)}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
