"""What a runner is handed: the cell's files, the clock of set-up, the
compile monitor, the tracer and the place its numbers go."""
import contextlib
import json
import os
import threading
import time

from . import checks as hc

#: the serving loop's phases (``paddle_tpu/serving/metrics.py:LoopClock``:
#: ``serve/<phase>`` spans on the engine's thread, which tile every iteration)
SERVE_PHASES = tuple("serve/" + p for p in (
    "sched", "admit.host", "admit.device", "decode.pack", "decode.device",
    "harvest", "publish", "wait"))
#: the host spans an idle gap of the device is named after: the benchmark's
#: own and the serving loop's phases.  The load generator's naps
#: (``schedule_wait``, ``drain``) are not among them: it waits all the while
#: the system works, so its spans say nothing of what the device waited for
HOST_SPANS = ("submit", "window_dispatch", "loss_readback") + SERVE_PHASES


class RunContext:
    def __init__(self, *, cell_name, cell, config, traffic, family, generator,
                 reference, seed, seconds, trace, rehearse, control, t_start,
                 monitor, trace_dir):
        self.cell_name, self.cell = cell_name, cell
        self.config, self.traffic = config, traffic
        self.family, self.generator, self.reference = (family, generator,
                                                       reference)
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.rehearse, self.control = trace, rehearse, control
        self.control_mode = cell.get("control_mode")
        self.t_start, self.monitor = t_start, monitor
        self.trace_dir = trace_dir
        self.phases, self.metrics, self.facts = {}, {}, {}
        self.attempted = self.failed = 0
        self.setup_s = None
        self.memory_peak_bytes = None
        self.memory = {}
        self.checks = hc.Checks(self.emit)
        self.control_checks = hc.Checks(self.emit)
        self._snap = None
        self._tracer = None
        self.trace_window = None  # (t_start_wall, t_stop_wall) perf_counter

    # -- output ------------------------------------------------------------
    @staticmethod
    def emit(obj):
        print(json.dumps(obj, default=float), flush=True)

    def metric(self, name, value):
        """An end-to-end number; a CPU rehearsal keeps none."""
        if not self.rehearse:
            self.metrics[name] = float(value)

    # -- set-up clock --------------------------------------------------------
    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        snap = self.monitor.snapshot()
        try:
            yield
        finally:
            self.phases[name] = {"s": round(time.perf_counter() - t0, 3),
                                 **self.monitor.since(snap)}

    def setup_done(self):
        """First timed event: everything before it is set-up."""
        self.setup_s = time.perf_counter() - self.t_start
        self._snap = self.monitor.snapshot()
        self.emit({"setup_s": self.setup_s, "phases": self.phases})

    def window_done(self):
        post = self.monitor.since(self._snap)
        self.emit({"window_compiles": post})
        self.checks.upper("xla_compiles_in_window", post["xla_compiles"], 0)

    # -- host spans and the trace ---------------------------------------------
    @staticmethod
    def span(name):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def trace_tick(self, elapsed):
        """Called by the runner from inside the window: in a traced run,
        starts a helper thread once that traces ``trace_seconds`` of the
        window beginning ``trace_after`` seconds in."""
        if not self.trace or self._tracer is not None:
            return
        after = float(self.cell.get("trace_after_s", 2.0))
        length = float(self.cell.get("trace_seconds", 3.0))

        def work():
            import jax

            time.sleep(max(0.0, after - elapsed))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("traced_window"):
                self._stop.wait(length)
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.trace_window = (t0, t1)

        self._stop = threading.Event()
        self._tracer = threading.Thread(target=work, name="bench-tracer",
                                        daemon=True)
        self._tracer.start()

    def trace_stop(self):
        """Ends the trace (if the window closed first) and waits for it."""
        if self._tracer is not None:
            self._stop.set()
            self._tracer.join()

    # -- device ------------------------------------------------------------------
    def read_memory(self, reserved_is_program_temp):
        """Peak device memory, fullest chip.  The TPU runtime keeps two
        books, printed here and carried on the result's ``device`` under
        keys of their own: ``peak_bytes_in_use`` for arrays (parameters,
        optimizer state, KV pages, feeds) and ``peak_bytes_reserved`` for
        the space it sets aside for compiled programs' temporaries, which
        the first does not include.  Training keeps one program in flight:
        the second book is that program's temporaries (BERT-base: 1.9 GB
        and 12.05 GB, against the 14.65 GB of ``memory_analysis``; chip
        runs, PRs 21 and 23) and the peak is the sum.  Under the serving
        engine the two books add up to within 1 % of the chip's 16.91 GB
        in both cells (6.86 GB beside 9.93 GB of arrays, 7.43 GB beside
        9.44 GB), and nothing the benchmark can read says whether the
        second is what the admit programs need or what the arrays left
        free: there the peak is the arrays alone, a lower bound that leaves
        out the largest program's temporaries."""
        import jax

        stats = [d.memory_stats() or {} for d in jax.devices()]
        in_use = [int(st.get("peak_bytes_in_use", 0)) for st in stats]
        reserved = [int(st.get("peak_bytes_reserved", 0)) for st in stats]
        self.memory_peak_bytes = max(
            (a + (b if reserved_is_program_temp else 0)
             for a, b in zip(in_use, reserved)), default=0)
        self.memory = {"memory_in_use_peak_bytes": max(in_use, default=0),
                       "memory_reserved_peak_bytes": max(reserved, default=0)}
        self.emit({"memory": {
            "peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved,
            "bytes_limit": [int(st.get("bytes_limit", 0)) for st in stats],
            "reserved_counted": reserved_is_program_temp}})
