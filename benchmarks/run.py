"""paddle_tpu's chip benchmark: one cell, one process, one JSON result line.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the chip (a platform other than ``tpu``, or fewer chips than the cell
asks for, is a failed run: exit code 2 and no result; never a CPU
fall-back, never ``JAX_PLATFORMS`` set here), builds the model from
``--seed``, warms only this cell's shapes, measures for ``--seconds``,
checks what the timed path produced against the plain reference outside
the window, and prints the contract's one JSON line last.  Earlier lines
carry set-up phases, the schedule summary, compile counts and every
compared number beside its limit.

``--rehearse`` (CPU, tiny family presets) exists for ``benchmarks/tests``
and for rehearsing a change to the harness: it prints no device metric and
its last line says ``"platform": "cpu"``.  ``--control`` also computes the
lower-precision control of the cell and prints its numbers (the runs the
limits were set from); the benchmark's own runs do not pass it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

EXIT_NO_CHIP = 2
EXIT_NO_PROGRAM = 3


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--bench-dir", default=HERE, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    from benchmarks.harness import loader, peaks, trace_reduce
    from benchmarks.harness.compile_monitor import CompileMonitor
    from benchmarks.harness.context import HOST_SPANS, RunContext

    bench_dir = os.path.abspath(args.bench_dir)
    cell, config, traffic = loader.load_cell(args.workload, bench_dir)
    man = loader.manifest(os.path.dirname(bench_dir))
    e2e, layer = loader.metrics_of(args.workload, man)
    try:
        import paddle_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"the program under test is not here: {e}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    chips = int(cell["chips"])
    if args.rehearse:
        if device["platform"] == "tpu":
            print("--rehearse is the CPU path", file=sys.stderr)
            return EXIT_NO_CHIP
    elif device["platform"] != "tpu" or len(devs) < chips:
        print(f"no chip: need {chips} TPU chip(s), JAX reports {device}",
              file=sys.stderr)
        return EXIT_NO_CHIP
    emit = RunContext.emit
    emit({"cell": args.workload, "seed": args.seed, "seconds": args.seconds,
          "trace": args.trace, "device": device})

    from paddle_tpu import sysconfig
    from paddle_tpu.framework.flags import set_flags

    cache_dir = sysconfig.enable_persistent_compilation_cache()
    # a device error surfaces at its first occurrence, not after a backoff
    set_flags({"transient_max_retries": 1})
    emit({"xla_cache_dir": cache_dir})

    family = loader.load_module("families", config["family"], bench_dir)
    if args.rehearse:
        # the tiny preset has its own noise, so its own limits
        config = {**config, **family.TINY}
        traffic = {**traffic, **traffic.get("rehearse", {})}
        cell = {**cell, "limits": cell["rehearse_limits"]}
    generator = loader.load_module("generators", traffic["generator"],
                                   bench_dir)
    reference = loader.load_module("reference", family.REFERENCE, bench_dir)
    runner = loader.load_module("runners", cell["runner"], bench_dir)
    trace_dir = os.path.join(sysconfig.cache_root(), "bench_trace",
                             args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = RunContext(cell_name=args.workload, cell=cell, config=config,
                     traffic=traffic, family=family, generator=generator,
                     reference=reference, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     rehearse=args.rehearse, control=args.control,
                     t_start=T_START, monitor=CompileMonitor(),
                     trace_dir=trace_dir)
    runner.run(ctx)
    ctx.metric("setup_s", ctx.setup_s)
    if not ctx.checks.rows or ctx.setup_s is None:
        raise RuntimeError("the runner checked nothing or never ended set-up")
    emit({"end_to_end": ctx.metrics})
    if args.control:
        emit({"control_correct": ctx.control_checks.correct,
              "control_mode": ctx.control_mode})

    units = {m["name"]: m["unit"] for m in e2e + layer}
    result = {"correct": ctx.checks.correct, "attempted": ctx.attempted,
              "failed": ctx.failed}
    device.update(memory_peak_bytes=ctx.memory_peak_bytes, **ctx.memory)
    if args.rehearse:
        # no device metric leaves a CPU run
        result.update(metrics={}, device=device, rehearsal=True)
    elif not args.trace:
        missing = [m["name"] for m in e2e if m["name"] not in ctx.metrics]
        if missing:
            raise RuntimeError(f"runner reported no {missing}")
        result.update(metrics={m["name"]: {"value": ctx.metrics[m["name"]],
                                           "unit": m["unit"]} for m in e2e},
                      device=device)
    else:
        trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir),
                                  HOST_SPANS + ("traced_window",))
        span = next(((s, s + d) for n, s, d in trace["host_spans"]
                     if n == "traced_window"), None)
        red = trace_reduce.reduce(
            trace, span=span, step_module=ctx.facts.get("step_module"),
            steps_per_execution=ctx.facts.get("steps_per_window", 1),
            gap_span_names=HOST_SPANS)
        ev = {"trace": red, "facts": ctx.facts, "metrics": ctx.metrics,
              "chips": chips, "peaks": peaks.peaks_for(device["kind"]),
              "seconds": args.seconds}
        out = {}
        for m in layer:
            value = loader.load_module("layer_metrics", m["name"],
                                       bench_dir).read(ev)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        emit({"trace_modules": red["modules"], "step": {
            k: v for k, v in (red["step"] or {}).items()
            if k not in ("durations_ns", "gaps_ns")}})
        device["busy_s"] = red["busy_ns"] / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        gaps = red["longest_gaps"][:5] + [
            ["sum:" + k, v] for k, v in red["idle_by_cause"][:5]]
        result.update(metrics=out, device=device,
                      breakdown={"device_ops": red["top_ops"],
                                 "idle_gaps": gaps})
    # the builder's contract: each number compared beside its limit as the
    # last lines of standard error and as the last key of the result's
    # line, which is all that is kept of a run that is not correct
    result["compared"] = {r["check"]: {"value": r["value"],
                                       "limit": r["limit"], "ok": r["ok"]}
                          for r in ctx.checks.rows}
    sys.stdout.flush()
    for name, r in result["compared"].items():
        print(f"{name} {r['value']} {'ok' if r['ok'] else 'NOT OK'}: limit "
              f"{r['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
