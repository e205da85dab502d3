"""Serving through ``serving.GenerationEngine(paged=True, continuous=True)``:
one engine, one load-generator thread (this one), an open loop that sends
on the schedule whether or not earlier requests have finished, or a closed
loop that keeps a fixed number of requests in flight.

Times are taken on the host's clock from when a request was *due*, and a
completion is stamped by the request's future as it resolves.  After the
window the plain reference runs once over prompt + served tokens of a
seeded sample of finished requests (the longest among them) and reads how
far each served token's logit lies below the reference's best.
"""
import gc
import queue
import time

import numpy as np

from benchmarks.harness import stats


def _build(ctx, weights):
    from paddle_tpu.serving import GenerationEngine

    cfg, traffic = ctx.config, ctx.traffic
    serve = {**cfg["serve"], **(cfg.get("serve_rehearse", {})
                                if ctx.rehearse else {})}
    model = ctx.family.build_model(cfg, weights)
    model.eval()
    engine = GenerationEngine(
        model, prompt_buckets=list(traffic["prompt_buckets"]),
        batch_size=serve["batch_size"], paged=True, continuous=True,
        kv_page_size=serve["kv_page_size"],
        speculative_k=serve["speculative_k"], eos_token_id=None,
        max_queue_depth=serve["max_queue_depth"], name="bench")
    return model, engine


class _Rec:
    __slots__ = ("req", "due", "sent", "done", "tokens", "error", "future",
                 "refused")

    def __init__(self, req, due):
        self.req, self.due = req, due
        self.sent = self.done = self.tokens = self.error = self.future = None
        self.refused = 0  # times the engine's door turned the request away


def drive(engine, traffic, reqs, seconds, span, tick=lambda t: None,
          at_open=lambda: None):
    """Offer ``reqs`` to ``engine``: ``warm_seconds`` of the traffic, then
    the window of ``seconds``.  Returns every request's record (times in
    seconds from the window's opening), those that count as the window's,
    and the engine's counter snapshots at its opening and close."""
    warm_s = float(traffic.get("warm_seconds", 0.0))
    t0 = time.perf_counter() + warm_s  # the window opens here
    now = lambda: time.perf_counter() - t0  # noqa: E731
    recs, done_q, marks = [], queue.SimpleQueue(), {}

    def offer(rec):
        """Hand the request to the engine; False where its door refuses."""
        try:
            rec.future = engine.submit(rec.req["prompt"],
                                       rec.req["max_new_tokens"])
        except Exception as e:  # shed at the door: a full queue
            rec.error, rec.refused = repr(e), rec.refused + 1
            return False

        def on_done(f):
            t = now()
            try:
                rec.tokens = np.asarray(f.result(), np.int32)
            except Exception as e:  # shed, expired, failed
                rec.error = repr(e)
            rec.done = t  # last: a record with ``done`` set is complete
            done_q.put(rec)

        rec.error = None
        rec.future.add_done_callback(on_done)
        return True

    def send(req, due):
        rec = _Rec(req, due)
        recs.append(rec)
        with span("submit"):
            rec.sent = now()
            if not offer(rec):
                turned_away(rec)
        return rec

    # the door's own words are "load shed (retry with backoff)": an open
    # loop's caller does, after 0.1 s doubling to 1 s, and the wait is in its
    # latency; one still refused a minute past the close has failed.  A
    # closed loop's caller fails at once: its loop never fills the queue
    retries = [] if traffic["loop"] == "open" else None

    def turned_away(rec):
        if retries is None:
            rec.done = now()
            done_q.put(rec)
        else:
            retries.append((now() + min(0.05 * 2 ** rec.refused, 1.0), rec))

    def open_window():
        marks["open"] = engine.metrics.snapshot()
        at_open()

    if traffic["loop"] == "open":
        def sleep_until(t):
            while True:
                for item in [x for x in retries if x[0] <= now()]:
                    retries.remove(item)
                    if not offer(item[1]):
                        turned_away(item[1])
                left = t - now()
                if left <= 0:
                    return
                with span("schedule_wait"):  # one span a nap: a span that
                    time.sleep(min(left, 0.05))  # opened before a trace is lost
                if now() >= 0:
                    tick(now())

        opened = False
        for req in sorted(reqs, key=lambda r: r["due_s"]):
            if req["due_s"] >= 0 and not opened:
                sleep_until(0.0)
                open_window()
                opened = True
            sleep_until(req["due_s"])
            send(req, req["due_s"])
        sleep_until(seconds)
        marks["close"] = engine.metrics.snapshot()
        while retries and now() < seconds + 60.0:
            sleep_until(now() + 0.05)
        for _, rec in retries:
            rec.done = now()
        window = [r for r in recs if r.due >= 0]
    else:
        nxt = iter(range(10 ** 9))

        def send_next():
            send(reqs[next(nxt) % len(reqs)], None).due = now()

        def pump(until):
            """Replace each finished request until ``until``."""
            while True:
                left = until - now()
                if left <= 0:
                    return
                try:
                    with span("schedule_wait"):
                        done_q.get(timeout=min(left, 0.05))
                except queue.Empty:
                    continue
                finally:
                    if now() >= 0:
                        tick(now())
                send_next()

        for _ in range(int(traffic["clients"])):
            send_next()
        pump(0.0)
        open_window()
        pump(seconds)
        window = [r for r in recs if r.sent >= 0]
        marks["close"] = engine.metrics.snapshot()
    return recs, window, marks


def in_flight(recs, t):
    return sum(1 for r in recs if r.sent <= t
               and (r.done is None or r.done > t))


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

    def at_open():
        ctx.phases["warm_traffic"] = {
            "s": round(time.perf_counter() - t_warm, 3)}
        ctx.setup_done()

    recs, window, marks = drive(engine, traffic, reqs, seconds, ctx.span,
                                ctx.trace_tick, at_open)
    ctx.trace_stop()
    t_drain = time.perf_counter()
    if traffic["loop"] == "open":
        # every request due in the window is waited for; a closed loop's
        # callers are still waiting when the window closes, and are let go
        with ctx.span("drain"):
            for r in window:
                try:
                    r.future.result(timeout=max(
                        1.0, 240.0 - (time.perf_counter() - t_drain)))
                except Exception:
                    pass
            time.sleep(0.05)  # let the last callbacks stamp
    ctx.window_done()
    ctx.emit({"drain_s": round(time.perf_counter() - t_drain, 3),
              "engine_stats": {k: v for k, v in engine.stats().items()
                               if isinstance(v, (int, float, str, bool))}})
    recs = [r for r in recs if r.done is not None or r in window]

    # -- end-to-end numbers ------------------------------------------------
    in_window = [r for r in recs if r.done is not None and r.error is None
                 and 0 <= r.done <= seconds]
    tokens = sum(len(r.tokens) for r in in_window)
    # tokens are credited when their request completes, and completions come
    # at the ends of decode steps: over the fixed window the rate would move
    # in jumps of one step's completions (about 1 % in docs_closed), so the
    # time is the window up to its last completion
    t_last = max((r.done for r in in_window), default=0.0)
    ctx.metric("serve_tok_s", tokens / t_last if t_last > 0 else 0.0)
    if traffic["loop"] == "open":
        failed = [r for r in window if r.error is not None or r.done is None]
        ctx.attempted = len(window)
    else:
        failed = [r for r in recs if r.error is not None
                  and r.done is not None and 0 <= r.done <= seconds]
        ctx.attempted = len(in_window) + len(failed)
    ctx.failed = len(failed)
    lat = [(r.done - r.due) * 1e3 for r in window
           if r.error is None and r.done is not None]
    late = [(r.sent - r.due) * 1e3 for r in window]
    if traffic["loop"] == "open" and lat:
        ctx.metric("req_latency_p95_ms", stats.percentile(lat, 95))
    delta = {k: marks["close"][k] - v for k, v in marks["open"].items()
             if isinstance(v, int) and isinstance(marks["close"].get(k), int)}
    if not ctx.rehearse:
        ctx.emit({"requests_completed_in_window": len(in_window),
                  "tokens_completed_in_window": tokens,
                  "last_completion_s": t_last,
                  "latency_ms": stats.summary(lat) if lat else None,
                  "latency_samples_enough": len(lat) >= 200,
                  "generator_lateness_ms": stats.summary(late)
                  if late else None,
                  "in_flight_at_middle": in_flight(recs, seconds / 2),
                  "in_flight_at_close": in_flight(recs, seconds)})
    ctx.emit({"counter_deltas": delta, "failed": [r.error for r in failed][:5]})
    ctx.facts.update(counters=delta, lateness_ms=late, loop=traffic["loop"],
                     warmup_executables=compiled)

    # -- correct: outside the window ---------------------------------------
    ck = ctx.checks
    finished = [r for r in recs if r.done is not None and r.error is None]
    ck.true("every_request_answered_in_full",
            bool(finished) and not failed and all(
                len(r.tokens) == r.req["max_new_tokens"] for r in finished))
    ck.upper("executables_after_warmup", engine.compile_count, compiled)
    ctx.read_memory(reserved_is_program_temp=False)
    k = min(int(ctx.cell["check_requests"]), len(finished))
    rng = np.random.default_rng(ctx.seed)
    longest = max(range(len(finished)), key=lambda i: len(
        finished[i].req["prompt"]) + len(finished[i].tokens))
    pick = {longest} | set(rng.choice(len(finished), size=k, replace=False)
                           .tolist()) if finished else set()
    sample = [finished[i] for i in sorted(pick)]
    prompts = [r.req["prompt"] for r in sample]
    served = [r.tokens for r in sample]
    engine.close(drain=traffic["loop"] == "open", timeout=60)
    del engine, model
    gc.collect()

    t_ref = time.perf_counter()
    import jax.numpy as jnp

    params = {n: v.astype(jnp.float32) for n, v in weights.items()}
    longest_hist = (traffic["prompt_len"]["max"]
                    + traffic["output_len"]["max"])
    res = ctx.reference.served_token_gaps(
        params, cfg, prompts, served,
        control_mode=ctx.control_mode if ctx.control else None,
        pad_len=-(-longest_hist // 128) * 128,
        pad_out=traffic["output_len"]["max"])
    lim = ctx.cell["limits"]

    def account(key, record, tag=""):
        gaps = np.concatenate([r[key] for r in res])
        record(f"{tag}max_gap", float(gaps.max()), lim["max_gap"])
        record(f"{tag}mean_gap", float(gaps.mean()), lim["mean_gap"])
        # the widest gap swings by its nature and the mean is moved by many
        # shallow flips; the share of tokens lying deeper below the
        # reference's best than the stated precision's own noise reaches is
        # what tells a lower precision apart
        record(f"{tag}deep_gap_share",
               float((gaps > ctx.cell["deep_gap"]).mean()),
               lim["deep_gap_share"], note=f"gap > {ctx.cell['deep_gap']}")
        return gaps

    def tail(g):
        """How many checked tokens lie further below the reference's best
        than each threshold (the shape of the gap's distribution)."""
        return {str(t): int((g > t).sum())
                for t in (0.0, 0.002, 0.004, 0.006, 0.008, 0.012, 0.016, 0.024)}

    gaps = account("gap", ck.upper)
    margins = np.concatenate([r["margin"] for r in res])
    ctx.emit({"gap_tail_counts": tail(gaps)})
    ctx.emit({"reference_s": round(time.perf_counter() - t_ref, 2),
              "checked_requests": len(sample), "checked_tokens": len(gaps),
              "flip_share": float((gaps > 0).mean()),
              "median_top2_margin": float(np.median(margins))})
    if ctx.control:
        cg = account("control_gap", ctx.control_checks.upper, "control.")
        ctx.emit({"control_flip_share": float((cg > 0).mean()),
                  "control_gap_tail_counts": tail(cg)})
