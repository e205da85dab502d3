"""Find an open-loop cell's knee once, on the chip: one process, one engine,
the cell's own traffic at each of a few fixed rates.

    python benchmarks/sweep.py --workload gpt2_small.chat_open \
        --rates 56,64,72,80 --seconds 51 --seed 1

The knee is the highest rate at which the backlog does not grow through
the window and nobody is refused: the mean number of requests in flight
over the window's last fifth is no more than over its first fifth plus
``SLACK``.  The first and the last fifth are what the schedule offers alike
(order 1 at 98/s: 95.5 and 93.8 requests/s; its middle fifth is 5-9 % hot
at every rate, so the end is not compared with the middle: PR 49's first
form of this rule did that and passed 98/s, where the fifths read 42.5,
40.3, 57.3, 82.4, 56.6).  A request lives a fraction of a second (PR 49:
median 0.22-0.26 s, p95 0.53-0.62 s at 56-72/s; it was 10-30 s before
PR 25), so the run's own window of 51 s holds thousands of them after the
mix's 30 s of warm traffic.  At the rates PR 49's sweeps sustained (74-96/s)
the last fifth read between 5.9 under and 4.1 over the first, where a rate
1/s over what the engine sustains adds 41 between their centres: hence
``SLACK``.  A rate the engine cannot sustain for long fills the engine's
queue (256 deep) and is refused at the door: that counts as not sustained
whatever the readings say.  The means of all five fifths, the most in
flight against the engine's slots, the tokens completed against those
offered and the generator's lateness are printed beside them.  Two rates in
a row that are not sustained end the sweep.  The cell's traffic file then
carries 0.8 x the knee as ``rate_per_s``; a run never searches.  Prints one
JSON line per rate and a last line with the knee.  Not part of a benchmark
run.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

#: requests by which the mean in flight may rise from the window's first
#: fifth to its last
SLACK = 8.0


def sustained(fifths, failed):
    """Whether a rate was sustained: ``fifths`` are the means of the
    requests in flight over the five fifths of the window, ``failed`` the
    requests lost plus the times the door refused one.  A full queue sheds,
    and the backlog then stops growing because requests are refused: not a
    sustained rate."""
    return fifths[-1] <= fifths[0] + SLACK and failed == 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from benchmarks.harness import loader, stats
    from benchmarks.harness.compile_monitor import CompileMonitor
    from benchmarks.harness.context import RunContext

    cell, config, traffic = loader.load_cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no chip", file=sys.stderr)
        return 2
    from paddle_tpu import sysconfig

    sysconfig.enable_persistent_compilation_cache()
    family = loader.load_module("families", config["family"])
    generator = loader.load_module("generators", traffic["generator"])
    runner = loader.load_module("runners", cell["runner"])
    ctx = RunContext(cell_name=args.workload, cell=cell, config=config,
                     traffic=traffic, family=family, generator=generator,
                     reference=None, seed=args.seed, seconds=args.seconds,
                     trace=False, rehearse=False, control=False,
                     t_start=time.perf_counter(), monitor=CompileMonitor(),
                     trace_dir=None)
    weights = family.make_weights(config, args.seed)
    model, engine = runner._build(ctx, weights)
    engine.warmup()
    knee, misses = None, 0
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = {**traffic, "rate_per_s": rate}
        reqs = generator.generate(tr, config, args.seed, args.seconds)
        recs, window, marks = runner.drive(engine, tr, reqs, args.seconds,
                                           ctx.span)
        for r in recs:  # empty the engine before the next rate
            try:
                r.future.result(timeout=300)
            except Exception:
                pass
        time.sleep(0.1)
        T = args.seconds

        def mean_in_flight(lo, hi):
            ts = [lo + (hi - lo) * (k + 0.5) / 40 for k in range(40)]
            return sum(runner.in_flight(recs, t) for t in ts) / len(ts)

        fifths = [mean_in_flight(k * T / 5, (k + 1) * T / 5)
                  for k in range(5)]
        done = [r for r in recs if r.error is None and r.done is not None
                and 0 <= r.done <= args.seconds]
        lat = [(r.done - r.due) * 1e3 for r in window if r.error is None]
        steps = (marks["close"]["decode_steps"]
                 - marks["open"]["decode_steps"])
        failed = sum(1 for r in recs if r.error is not None)
        refused = sum(r.refused for r in recs)  # each came again, and counts
        ok = sustained(fifths, failed + refused)
        late = [(r.sent - r.due) * 1e3 for r in window]
        if ok:
            knee = rate if knee is None else max(knee, rate)
        misses = 0 if ok else misses + 1
        print(json.dumps({
            "rate_per_s": rate, "sustained": ok,
            "in_flight_fifths": [round(f, 2) for f in fifths],
            "in_flight_max": max(runner.in_flight(recs, r.sent)
                                 for r in window),
            "slots": config["serve"]["batch_size"],
            "tok_s": sum(len(r.tokens) for r in done) / args.seconds,
            "offered_tok_s": sum(r.req["max_new_tokens"] for r in window)
            / args.seconds,
            "latency_ms": stats.summary(lat) if lat else None,
            "generator_lateness_p95_ms": stats.percentile(late, 95),
            "decode_steps_per_s": steps / args.seconds,
            "requests_due_in_window": len(window), "failed": failed,
            "refused_at_the_door": refused}),
            flush=True)
        if misses >= 2:
            break
    engine.close()
    print(json.dumps({"knee_per_s": knee, "rate_at_0.8": None if knee is None
                      else round(0.8 * knee, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
