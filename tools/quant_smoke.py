"""Quantized-serving gate: int8/fp8 numerics, HBM economics, hot swap (CPU).

One-command proof of the quantized serving path's contracts, cheap enough
for every gate run:

1. **Token agreement with margin accounting** — int8 and fp8 engines
   decode a seeded workload; every emitted token is teacher-forced
   through the fp32 model and through a weight-quantized clone.  Steps
   where fp32's greedy margin (top1 - top2 logit gap) exceeds
   ``MARGIN_K`` x the measured quantized-logit perturbation must agree
   EXACTLY — a quantized engine may flip genuine near-ties, never a
   clear-margin decision.  Overall agreement is reported and floored.
2. **Equal-HBM resident slots** — a float paged engine and an int8-KV
   paged engine run the same workload on EQUAL pool bytes (int8 pages +
   their fp32 scale planes must measure <= the float pool's bytes, from
   the live arrays): the int8 engine must hold STRICTLY more peak
   resident decode slots, and its tokens/s must be at or above the
   float baseline (interleaved best-of-2 walls).
3. **Quantized rolling swap, zero compiles** — a :class:`Router` over
   two int8 engines hot-swaps a ``slim.export_quantized`` artifact via
   ``swap_weights_rolling`` under the XLA compile-event listener: zero
   post-warmup compile events across drain + swap + re-probe + serve,
   and the served tokens actually change (the swap took).

Prints one JSON line; exit 0 iff all three gates hold.
"""
import copy
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.monitoring  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import slim  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.serving import GenerationEngine, Router  # noqa: E402

# dispatch-dominated CPU geometry (hidden 32, hd 8): the regime serving
# decode actually lives in, where the int8 path's smaller KV gathers and
# MXU-shaped matmuls are pure win rather than a FLOP tradeoff
CACHE = 64
PAGE = 16
SLOTS = 4
NTOK = 16
REQS = 8
# equal-HBM pool sizing: per token-head the float pool stores hd*4 bytes,
# the int8 pool hd*1 + 4 (scale plane) — at hd=8 that is 32 vs 12 bytes,
# so 8 float pages buy 21 int8 pages in the same budget (asserted from
# the live arrays, not this comment)
F32_PAGES = 8
INT8_PAGES = 21
# a clear-margin flip is a quantization bug, not noise: the fp32 margin
# must exceed MARGIN_K x the measured teacher-forced logit perturbation
# before a disagreement counts against the gate (and at least one served
# token must clear the bar, or the check would be vacuous)
MARGIN_K = 4.0
AGREE_FLOOR = 0.85

_XLA_COMPILES = [0]
jax.monitoring.register_event_listener(
    lambda name, **kw: _XLA_COMPILES.__setitem__(0, _XLA_COMPILES[0] + 1)
    if name == "/jax/compilation_cache/compile_requests_use_cache" else None)


def _model(seed=13):
    pt.seed(seed)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_position=CACHE, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _prompts(rng, n, lo, hi):
    return [rng.randint(1, 97, size=lo + (k % (hi - lo))).astype(np.int32)
            for k in range(n)]


def _run_engine(model, quantized, pages, prompts, name):
    """Serve the workload; return (best_wall_s, outputs, peak_slots)."""
    eng = GenerationEngine(model, prompt_buckets=[48], batch_size=SLOTS,
                           cache_len=CACHE,
                           kv_pages=pages, kv_page_size=PAGE,
                           speculative_k=0, quantized=quantized, name=name)
    with eng:
        eng.warmup()
        t0 = time.monotonic()
        futs = [eng.submit(p, NTOK) for p in prompts]
        peak, pend = 0, set(range(len(futs)))
        while pend:
            pend = {k for k in pend if not futs[k].done()}
            st = eng.stats()
            peak = max(peak, min(int(st.get("admitted", 0))
                                 - int(st.get("evicted", 0)), SLOTS))
            time.sleep(0.002)
        wall = time.monotonic() - t0
        outs = [f.result(1).tolist() for f in futs]
    return wall, outs, peak


def gate_agreement(model):
    """Quantized engines may flip near-ties, never clear-margin tokens."""
    rng = np.random.RandomState(7)
    prompts = _prompts(rng, REQS, 17, 26)

    def logits_at(m, hist):
        return np.asarray(m(jnp.asarray([hist], jnp.int32)))[0, -1]

    out = {}
    for mode in ("int8", "fp8"):
        _, outs, _ = _run_engine(model, mode, INT8_PAGES, prompts,
                                 f"quant-smoke-{mode}")
        qm = copy.deepcopy(model)
        slim.quantize_weights(qm, mode)
        # pass 1: the actual quantized-logit perturbation on the served
        # histories — the noise floor the margin filter calibrates to
        steps = []
        delta = 0.0
        for p, toks in zip(prompts, outs):
            hist = [int(x) for x in p]
            for t in toks:
                lf = logits_at(model, hist)
                delta = max(delta, float(np.max(np.abs(
                    logits_at(qm, hist) - lf))))
                steps.append((lf, int(t)))
                hist.append(int(t))
        # pass 2: margin accounting against the calibrated floor
        tau = MARGIN_K * delta
        total = agree = clear = clear_flips = 0
        for lf, t in steps:
            order = np.argsort(lf)
            margin = float(lf[order[-1]] - lf[order[-2]])
            ok = int(np.argmax(lf)) == t
            total += 1
            agree += int(ok)
            if margin > tau:
                clear += 1
                clear_flips += int(not ok)
        out[mode] = {
            "tokens": total,
            "agreement": round(agree / total, 3),
            "logit_delta": round(delta, 4),
            "margin_tau": round(tau, 4),
            "clear_margin_tokens": clear,
            "clear_margin_flips": clear_flips,
            "ok": bool(clear_flips == 0 and clear > 0
                       and agree / total >= AGREE_FLOOR),
        }
    out["ok"] = bool(out["int8"]["ok"] and out["fp8"]["ok"])
    return out


def gate_hbm(model):
    """Strictly more resident slots + tokens/s >= float, at equal bytes."""
    gpt = model.gpt

    def pool_bytes(pages, dtype=None):
        cache = gpt.init_paged_cache(pages, PAGE, dtype=dtype)
        return sum(int(t.nbytes) for layer in cache["layers"]
                   for t in layer.values())

    f32_bytes = pool_bytes(F32_PAGES)
    int8_bytes = pool_bytes(INT8_PAGES, dtype=jnp.int8)

    rng = np.random.RandomState(17)
    # 3-page prompts: the float pool admits 2 slots (3 pages each, 8
    # total), the int8 pool all 4 — same bytes, double the residency
    prompts = _prompts(rng, REQS, 36, 44)
    wf, outs_f, peak_f = _run_engine(model, None, F32_PAGES, prompts,
                                     "quant-smoke-f32")
    wq, outs_q, peak_q = _run_engine(model, "int8", INT8_PAGES, prompts,
                                     "quant-smoke-i8")
    # interleaved best-of-2 walls: background noise can't pick the winner
    wf2, _, pf2 = _run_engine(model, None, F32_PAGES, prompts,
                              "quant-smoke-f32b")
    wq2, _, pq2 = _run_engine(model, "int8", INT8_PAGES, prompts,
                              "quant-smoke-i8b")
    wf, wq = min(wf, wf2), min(wq, wq2)
    peak_f, peak_q = max(peak_f, pf2), max(peak_q, pq2)
    total = REQS * NTOK
    f_tps, q_tps = total / wf, total / wq
    return {
        "f32_pool_bytes": f32_bytes,
        "int8_pool_bytes": int8_bytes,
        "equal_hbm": bool(int8_bytes <= f32_bytes),
        "f32_pages": F32_PAGES,
        "int8_pages": INT8_PAGES,
        "f32_peak_slots": peak_f,
        "int8_peak_slots": peak_q,
        "resident_slots_up": bool(peak_q > peak_f),
        "f32_tokens_per_s": round(f_tps, 1),
        "int8_tokens_per_s": round(q_tps, 1),
        "tps_not_worse": bool(q_tps >= f_tps),
        "lost": sum(o is None for o in outs_f + outs_q),
        "ok": bool(int8_bytes <= f32_bytes and peak_q > peak_f
                   and q_tps >= f_tps),
    }


def _count_eqns(jaxpr, pred):
    n = 0
    for eqn in jaxpr.eqns:
        n += int(pred(eqn))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    n += _count_eqns(inner, pred)
    return n


def gate_flash_dispatch(model):
    """The quantized paged decode's kernel-dispatch contract: on this CPU
    host the engine keeps the gather-then-attend fallback (whose output
    the agreement gate above scores), the same geometry on a TPU backend
    selects the Pallas paged-flash kernel, and that kernel's dispatch
    graph contains NO pool-sized int8→float conversion — pages are
    dequantized per-block inside the kernel, so the quantized pool's
    HBM-byte advantage (gate_hbm) survives the attention read."""
    from paddle_tpu.ops.paged_attention import paged_flash_decode
    from paddle_tpu.ops.paged_attention import paged_flash_eligible

    cfg = model.gpt.cfg
    hd = cfg.hidden_size // cfg.num_heads
    H, P = cfg.num_heads, INT8_PAGES
    rng = np.random.RandomState(23)
    q = jnp.asarray(rng.randn(SLOTS, H, 1, hd), jnp.float32)
    pool = jnp.asarray(rng.randint(-127, 128, (P + 1, PAGE, H * hd)),
                       jnp.int8)
    scale = jnp.asarray(rng.rand(P + 1, PAGE, H), jnp.float32)
    tables = jnp.zeros((SLOTS, CACHE // PAGE), jnp.int32)
    pos_map = jnp.tile(jnp.arange(CACHE, dtype=jnp.int32), (SLOTS, 1))
    positions = jnp.full((SLOTS, 1), CACHE - 1, jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: paged_flash_decode(*a, block_h=H))(
            q, pool, pool, tables, pos_map, positions, None, scale, scale)
    pool_shape = tuple(pool.shape)
    full_dequants = _count_eqns(
        jaxpr.jaxpr,
        lambda e: (e.primitive.name == "convert_element_type"
                   and tuple(getattr(e.invars[0].aval, "shape", ())) ==
                   pool_shape
                   and str(e.outvars[0].aval.dtype) == "float32"))
    kernel_calls = _count_eqns(
        jaxpr.jaxpr, lambda e: e.primitive.name == "pallas_call")
    return {
        "fallback_on_cpu": not paged_flash_eligible(hd, PAGE),
        "selected_on_tpu": paged_flash_eligible(hd, PAGE, backend="tpu"),
        "kernel_calls_in_graph": kernel_calls,
        "full_pool_float_dequants": full_dequants,
        "ok": bool(not paged_flash_eligible(hd, PAGE)
                   and paged_flash_eligible(hd, PAGE, backend="tpu")
                   and kernel_calls == 1 and full_dequants == 0),
    }


def gate_rolling_swap(model):
    """Quantized rolling swap across a router: zero XLA compile events."""
    donor = _model(seed=29)  # different weights, same tree geometry
    tmp = tempfile.mkdtemp(prefix="quant_smoke_")
    artifact = slim.export_quantized(
        donor, os.path.join(tmp, "donor"), mode="int8")
    rng = np.random.RandomState(23)
    prompts = _prompts(rng, 4, 17, 24)
    engines = [GenerationEngine(model, prompt_buckets=[48], batch_size=2,
                                cache_len=CACHE, kv_pages=INT8_PAGES,
                                kv_page_size=PAGE, speculative_k=0,
                                quantized="int8", name=f"quant-smoke-r{i}")
               for i in range(2)]
    router = Router(engines, name="quant-smoke-router",
                    probe_interval_s=60.0)
    try:
        router.warmup()
        before = [router.submit(p, max_new_tokens=4).result(120).tolist()
                  for p in prompts]
        xla0 = _XLA_COMPILES[0]
        swapped = router.swap_weights_rolling(artifact, drain_timeout=60.0)
        after = [router.submit(p, max_new_tokens=4).result(120).tolist()
                 for p in prompts]
        xla_events = _XLA_COMPILES[0] - xla0
        manifest = json.load(open(artifact + ".manifest.json"))
        return {
            "replicas_swapped": swapped,
            "xla_compiles_across_swap": xla_events,
            "weights_took": bool(before != after),
            "manifest_quantization": manifest["quantization"],
            "healthy_after": router.healthy_count(),
            "ok": bool(swapped == 2 and xla_events == 0
                       and before != after
                       and router.healthy_count() == 2),
        }
    finally:
        router.close(timeout=30)


def main():
    t0 = time.time()
    model = _model()
    agreement = gate_agreement(model)
    hbm = gate_hbm(model)
    flash = gate_flash_dispatch(model)
    swap = gate_rolling_swap(model)
    passed = (agreement["ok"] and hbm["ok"] and flash["ok"]
              and swap["ok"])
    print(json.dumps({"pass": bool(passed), "agreement": agreement,
                      "hbm": hbm, "flash_dispatch": flash,
                      "rolling_swap": swap,
                      "seconds": round(time.time() - t0, 1)}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
