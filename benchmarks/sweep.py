"""Find an open-loop cell's knee once, on the chip: one process, one engine,
the cell's own traffic at each of a few fixed rates.

    python benchmarks/sweep.py --workload gpt2_small.chat_open \
        --rates 1.2,1.4,1.6,1.8 --seconds 100 --seed 1

The knee is the highest rate at which the number of requests in flight at
the end of the window is no more than at its middle (the backlog is not
growing).  Requests live 10-30 s and the count in flight swings by a few
from second to second, so the window is 100 s or more, both readings are
means over a fifth of it (40-60 % and 80-100 %) and ``SLACK`` requests (the
swing of such a mean under a steady load) are allowed between them; the
readings at four instants, the most in flight against the engine's slots
and the tokens completed against those offered are printed beside them.
Two rates in a row that are not sustained end the sweep.  The cell's
traffic file then carries 0.8 x the knee as ``rate_per_s``; a run never
searches.  Prints one JSON line per rate and a last line with the knee.
Not part of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

#: requests by which the mean in flight may rise from the middle to the end
SLACK = 3.0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=100.0)
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

        mid, end = mean_in_flight(0.4 * T, 0.6 * T), mean_in_flight(0.8 * T, T)
        done = [r for r in recs if r.error is None and r.done is not None
                and 0 <= r.done <= args.seconds]
        lat = [(r.done - r.due) * 1e3 for r in window if r.error is None]
        steps = (marks["close"]["decode_steps"]
                 - marks["open"]["decode_steps"])
        sustained = end <= mid + SLACK
        if sustained:
            knee = rate if knee is None else max(knee, rate)
        misses = 0 if sustained else misses + 1
        print(json.dumps({
            "rate_per_s": rate, "sustained": sustained,
            "in_flight_middle": mid, "in_flight_end": end,
            "in_flight_at": {str(q): runner.in_flight(recs, q * T)
                             for q in (0.25, 0.5, 0.75, 1.0)},
            "in_flight_max": max(runner.in_flight(recs, r.sent)
                                 for r in window),
            "slots": config["serve"]["batch_size"],
            "tok_s": sum(len(r.tokens) for r in done) / args.seconds,
            "offered_tok_s": sum(r.req["max_new_tokens"] for r in window)
            / args.seconds,
            "latency_ms": stats.summary(lat) if lat else None,
            "step_wall_ms": args.seconds * 1e3 / steps if steps else None,
            "failed": sum(1 for r in recs if r.error is not None)}),
            flush=True)
        if misses >= 2:
            break
    engine.close()
    print(json.dumps({"knee_per_s": knee, "rate_at_0.8": None if knee is None
                      else round(0.8 * knee, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
