"""``runners/serve_engine.py``'s serving run for a model whose weights fill
most of the chip: the same engine, the same ``drive()`` (loaded from that
file, not copied), the same end-to-end numbers and checks.  What differs:
the engine is given the configuration's ``cache_len``; the reference is
handed the weights as they are served (it upcasts them where it uses them:
11 GB of bfloat16 would be 22 GB of float32 at once); and the facts carry
what this model's per-layer readers need (the trace's directory, the
per-expert routed counts over the window, the configuration's sizes, the
engine's slots and page size).
"""
import gc
import time

import numpy as np

from benchmarks.harness import latent_moe_lib, loader, stats

_base = loader.load_module("runners", "serve_engine")


def _build(ctx, weights):
    from paddle_tpu.serving import GenerationEngine

    cfg, traffic = ctx.config, ctx.traffic
    serve = {**cfg["serve"], **(cfg.get("serve_rehearse", {})
                                if ctx.rehearse else {})}
    model = ctx.family.build_model({**cfg, "serve": serve}, weights)
    model.eval()
    return model, GenerationEngine(
        model, prompt_buckets=list(traffic["prompt_buckets"]),
        batch_size=serve["batch_size"], cache_len=serve["cache_len"],
        paged=True, continuous=True, kv_page_size=serve["kv_page_size"],
        speculative_k=serve["speculative_k"], eos_token_id=None,
        max_queue_depth=serve["max_queue_depth"], name="bench")


def run(ctx):
    import jax

    cfg, traffic, fam = ctx.config, ctx.traffic, ctx.family
    reqs = ctx.generator.generate(traffic, cfg, ctx.seed, ctx.seconds)
    ctx.emit({"schedule": ctx.generator.summary(reqs, ctx.seconds),
              "loop": traffic["loop"]})
    with ctx.phase("weights"):
        weights = fam.make_weights(cfg, ctx.seed)
        jax.block_until_ready(weights)
    with ctx.phase("model_build"):
        model, engine = _build(ctx, weights)
    with ctx.phase("warmup_compile"):
        compiled = engine.warmup()
    seconds = ctx.seconds
    t_warm = time.perf_counter()
    experts = {}

    def at_open():
        experts["open"] = engine.expert_counts()
        ctx.phases["warm_traffic"] = {
            "s": round(time.perf_counter() - t_warm, 3)}
        ctx.setup_done()

    recs, window, marks = _base.drive(engine, traffic, reqs, seconds,
                                      ctx.span, ctx.trace_tick, at_open)
    routed = engine.expert_counts() - experts["open"]
    ctx.trace_stop()
    ctx.window_done()
    if ctx.trace and not ctx.rehearse:
        # the trace names ops without their named scopes: the engine's own
        # compiled programs say which mechanism each instruction came from
        t0 = time.perf_counter()
        ctx.facts["op_scopes"] = latent_moe_lib.scope_map(
            engine.compiled_programs())
        ctx.emit({"op_scopes": len(ctx.facts["op_scopes"]),
                  "compiled_programs_s": round(time.perf_counter() - t0, 2)})
    ctx.emit({"engine_stats": {k: v for k, v in engine.stats().items()
                               if isinstance(v, (int, float, str, bool))}})
    recs = [r for r in recs if r.done is not None or r in window]

    # -- end-to-end numbers: as runners/serve_engine.py's closed loop --------
    in_window = [r for r in recs if r.done is not None and r.error is None
                 and 0 <= r.done <= seconds]
    tokens = sum(len(r.tokens) for r in in_window)
    t_last = max((r.done for r in in_window), default=0.0)
    ctx.metric("serve_tok_s", tokens / t_last if t_last > 0 else 0.0)
    failed = [r for r in recs if r.error is not None
              and r.done is not None and 0 <= r.done <= seconds]
    ctx.attempted = len(in_window) + len(failed)
    ctx.failed = len(failed)
    delta = {k: marks["close"][k] - v for k, v in marks["open"].items()
             if isinstance(v, int) and isinstance(marks["close"].get(k), int)}
    serve = {**cfg["serve"], **(cfg.get("serve_rehearse", {})
                                if ctx.rehearse else {})}
    if not ctx.rehearse:
        ctx.emit({"requests_completed_in_window": len(in_window),
                  "tokens_completed_in_window": tokens,
                  "last_completion_s": t_last,
                  "in_flight_at_middle": _base.in_flight(recs, seconds / 2),
                  "in_flight_at_close": _base.in_flight(recs, seconds)})
    ctx.emit({"counter_deltas": delta, "failed": [r.error for r in failed][:5],
              "expert_routed_tokens": routed.tolist()})
    ctx.facts.update(
        counters=delta, loop=traffic["loop"], warmup_executables=compiled,
        trace_dir=ctx.trace_dir, expert_routed=routed.tolist(),
        kv_page_size=serve["kv_page_size"], slots=serve["batch_size"],
        prompt_pairs_mean=float(np.mean([
            len(r["prompt"]) * (len(r["prompt"]) + 1) / 2 for r in reqs])),
        sizes={k: v for k, v in cfg.items() if isinstance(v, (int, float))
               and not isinstance(v, bool)})

    # -- correct: outside the window ---------------------------------------
    ck = ctx.checks
    finished = [r for r in recs if r.done is not None and r.error is None]
    ck.true("every_request_answered_in_full",
            bool(finished) and not failed and all(
                len(r.tokens) == r.req["max_new_tokens"] for r in finished))
    ck.upper("executables_after_warmup", engine.compile_count, compiled)
    ck.upper("moe_dropped_tokens", delta.get("moe_dropped_tokens", 0), 0)
    ctx.read_memory(reserved_is_program_temp=False)
    k = min(int(ctx.cell["check_requests"]), len(finished))
    rng = np.random.default_rng(ctx.seed)
    longest = max(range(len(finished)), key=lambda i: len(
        finished[i].req["prompt"]) + len(finished[i].tokens))
    pick = {longest} | set(rng.choice(len(finished), size=k, replace=False)
                           .tolist()) if finished else set()
    sample = [finished[i] for i in sorted(pick)]
    engine.close(drain=False, timeout=60)
    del engine, model
    gc.collect()

    t_ref = time.perf_counter()
    longest_hist = (traffic["prompt_len"]["max"]
                    + traffic["output_len"]["max"])
    res = ctx.reference.served_token_gaps(
        weights, cfg, [r.req["prompt"] for r in sample],
        [r.tokens for r in sample],
        control_mode=ctx.control_mode if ctx.control else None,
        pad_len=-(-longest_hist // 128) * 128,
        pad_out=traffic["output_len"]["max"])
    lim = ctx.cell["limits"]

    def account(key, record, tag=""):
        gaps = np.concatenate([r[key] for r in res])
        record(f"{tag}max_gap", float(gaps.max()), lim["max_gap"])
        record(f"{tag}mean_gap", float(gaps.mean()), lim["mean_gap"])
        record(f"{tag}deep_gap_share",
               float((gaps > ctx.cell["deep_gap"]).mean()),
               lim["deep_gap_share"], note=f"gap > {ctx.cell['deep_gap']}")
        return gaps

    def tail(g):
        return {str(t): int((g > t).sum())
                for t in (0.0, 0.002, 0.004, 0.006, 0.008, 0.012, 0.016,
                          0.024, 0.05, 0.1)}

    gaps = account("gap", ck.upper)
    # where the widest gaps lie: request (prompt length), answer position
    where = sorted(((float(g), len(r.req["prompt"]), j)
                    for r, x in zip(sample, res)
                    for j, g in enumerate(x["gap"])), reverse=True)[:5]
    ctx.emit({"widest_gaps": [{"gap": g, "prompt_len": n, "answer_pos": j}
                              for g, n, j in where]})
    margins = np.concatenate([r["margin"] for r in res])
    ctx.emit({"gap_tail_counts": tail(gaps),
              "gap_quantiles": stats.summary(gaps.tolist())})
    ctx.emit({"reference_s": round(time.perf_counter() - t_ref, 2),
              "checked_requests": len(sample), "checked_tokens": len(gaps),
              "flip_share": float((gaps > 0).mean()),
              "median_top2_margin": float(np.median(margins))})
    if ctx.control:
        cg = account("control_gap", ctx.control_checks.upper, "control.")
        ctx.emit({"control_flip_share": float((cg > 0).mean()),
                  "control_gap_tail_counts": tail(cg),
                  "control_gap_quantiles": stats.summary(cg.tolist())})
