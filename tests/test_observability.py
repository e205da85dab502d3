"""paddle_tpu.observability — registry, exporters, and step telemetry.

The contract under test (ISSUE 6): one typed labeled metrics registry is
the single sink for every telemetry island the repo has grown —
trace_events families re-published by the bridge, monitor counters pulled
by a collector, per-step training telemetry from the Executor hooks, and
per-request serving spans — exported as Prometheus text and periodic
JSONL, with the M901 (data-starved training) and M902 (HBM high-water)
analysis rules reading the same snapshots.
"""
import json
import math
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import profiler as prof
from paddle_tpu.analysis import RetraceMonitor
from paddle_tpu.framework import monitor, trace_events
from paddle_tpu.observability import exporters, metrics, steptrace
from paddle_tpu.serving.metrics import ServingMetrics
from paddle_tpu.static.graph import reset_default_programs


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    yield
    obs.disable()
    metrics.set_default_registry(metrics.MetricRegistry())


# -- registry semantics ------------------------------------------------------
class TestRegistry:
    def test_counter_inc_and_snapshot(self):
        r = metrics.MetricRegistry()
        c = r.counter("reqs_total", "requests")
        c.inc()
        c.inc(4)
        snap = r.snapshot()
        assert snap["reqs_total"]["type"] == "counter"
        assert snap["reqs_total"]["samples"] == [["reqs_total", {}, 5.0]]

    def test_counter_rejects_negative(self):
        r = metrics.MetricRegistry()
        with pytest.raises(ValueError):
            r.counter("c", "h").inc(-1)

    def test_labeled_children_are_distinct(self):
        r = metrics.MetricRegistry()
        g = r.gauge("depth", "queue depth", labelnames=("engine",))
        g.labels("a").set(3)
        g.labels("b").set(7)
        samples = {tuple(sorted(s[1].items())): s[2]
                   for s in r.snapshot()["depth"]["samples"]}
        assert samples[(("engine", "a"),)] == 3.0
        assert samples[(("engine", "b"),)] == 7.0

    def test_get_or_create_returns_same_metric(self):
        r = metrics.MetricRegistry()
        assert r.counter("c", "h") is r.counter("c", "h")

    def test_type_conflict_raises(self):
        r = metrics.MetricRegistry()
        r.counter("m", "h")
        with pytest.raises(ValueError):
            r.gauge("m", "h")

    def test_labelname_conflict_raises(self):
        r = metrics.MetricRegistry()
        r.gauge("g", "h", labelnames=("a",))
        with pytest.raises(ValueError):
            r.gauge("g", "h", labelnames=("b",))

    def test_histogram_buckets_cumulative(self):
        r = metrics.MetricRegistry()
        h = r.histogram("lat_ms", "latency", buckets=(1, 10, 100,
                                                      math.inf))
        for v in (0.5, 5, 5, 50, 5000):
            h.observe(v)
        by_le = {s[1]["le"]: s[2]
                 for s in r.snapshot()["lat_ms"]["samples"]
                 if s[0] == "lat_ms_bucket"}
        assert by_le == {"1": 1.0, "10": 3.0, "100": 4.0, "+Inf": 5.0}
        samples = {s[0]: s[2] for s in r.snapshot()["lat_ms"]["samples"]
                   if not s[0].endswith("_bucket")}
        assert samples["lat_ms_sum"] == pytest.approx(5060.5)
        assert samples["lat_ms_count"] == 5.0

    def test_sanitize_name(self):
        assert metrics.sanitize_name("a.b c-d") == "a_b_c_d"


# -- Prometheus exposition ---------------------------------------------------
class TestPrometheusRender:
    def test_golden_render(self):
        r = metrics.MetricRegistry()
        r.counter("steps_total", "steps run").inc(3)
        g = r.gauge("occ", "occupancy", labelnames=("engine",))
        g.labels('e"1').set(0.5)
        txt = exporters.render_prometheus(r)
        assert "# HELP steps_total steps run\n" in txt
        assert "# TYPE steps_total counter\n" in txt
        assert "steps_total 3\n" in txt
        # label values escaped per the 0.0.4 text format
        assert 'occ{engine="e\\"1"} 0.5' in txt

    def test_http_endpoint_serves_text(self):
        r = metrics.MetricRegistry()
        r.counter("hits_total", "hits").inc(2)
        exp = exporters.PrometheusExporter(r, port=-1)
        try:
            assert exp.port > 0
            resp = urllib.request.urlopen(exp.url, timeout=5)
            body = resp.read().decode()
            assert "text/plain" in resp.headers["Content-Type"]
            assert "hits_total 2" in body
        finally:
            exp.close()


# -- JSONL sink --------------------------------------------------------------
class TestJsonlSink:
    def test_write_merge(self, tmp_path):
        base = str(tmp_path / "obs.jsonl")
        for idx in (0, 1):
            r = metrics.MetricRegistry()
            r.counter("steps_total", "steps").inc(idx + 1)
            sink = exporters.JsonlSink(base, r, interval_s=3600,
                                       process_index=idx)
            sink.write_now()
            sink.close()
        p0 = exporters.process_jsonl_path(base, 0)
        recs = [json.loads(l) for l in open(p0)]
        assert recs[0]["process_index"] == 0
        assert recs[0]["metrics"]["steps_total"]["samples"][0][2] == 1.0
        merged = exporters.merge_jsonl(base)
        assert {r["process_index"] for r in merged} == {0, 1}
        assert [r["ts"] for r in merged] == sorted(r["ts"] for r in merged)

    def test_periodic_writes(self, tmp_path):
        base = str(tmp_path / "p.jsonl")
        r = metrics.MetricRegistry()
        sink = exporters.JsonlSink(base, r, interval_s=0.05,
                                   process_index=0)
        time.sleep(0.3)
        sink.close()
        lines = open(exporters.process_jsonl_path(base, 0)).readlines()
        assert len(lines) >= 2


# -- trace_events bridge -----------------------------------------------------
class TestBridge:
    def test_families_republished_as_gauges(self):
        r = metrics.MetricRegistry()
        metrics.install_bridge(r)
        try:
            trace_events.notify(("executor_cache", "executor#1"),
                                {"hits": 5, "misses": 2})
            trace_events.notify(("serving", "engine#1"),
                                {"queue_depth": 3})
            trace_events.notify(("resilience", "retry:r"),
                                {"retries": 1})
            trace_events.notify(("autotune", "t-plan"),
                                {"counters": {"searches": 4}})
            trace_events.notify(("steptrace", "train"),
                                {"steps": 7})
            snap = r.snapshot()
            def val(name):
                return snap[name]["samples"][0][2]
            assert val("paddle_tpu_executor_cache_hits") == 5.0
            assert val("paddle_tpu_serving_queue_depth") == 3.0
            assert val("paddle_tpu_resilience_retries") == 1.0
            # nested counter dicts flatten one level
            assert val("paddle_tpu_autotune_counters_searches") == 4.0
            assert val("paddle_tpu_steptrace_steps") == 7.0
            assert (snap["paddle_tpu_executor_cache_hits"]["samples"][0][1]
                    == {"executor": "executor#1"})
        finally:
            metrics.uninstall_bridge()

    def test_bridge_idempotent(self):
        r = metrics.MetricRegistry()
        metrics.install_bridge(r)
        metrics.install_bridge(r)
        try:
            trace_events.notify(("serving", "e"), {"requests": 1})
            # one observer registered, not two: gauge holds the value once
            assert (r.snapshot()["paddle_tpu_serving_requests"]
                    ["samples"][0][2]) == 1.0
        finally:
            metrics.uninstall_bridge()
        assert not metrics.bridge_installed()

    def test_monitor_collector(self):
        r = metrics.MetricRegistry()
        metrics.install_standard_collectors(r)
        monitor.stat_add("obs_test_stat", 11)
        snap = r.snapshot()
        vals = {s[1].get("stat"): s[2]
                for s in snap["paddle_tpu_monitor"]["samples"]}
        assert vals["obs_test_stat"] == 11.0


# -- satellite: trace_events observer isolation ------------------------------
class TestNotifyIsolation:
    def test_raising_subscriber_does_not_break_others(self):
        got = []
        before = trace_events.dropped_notifications()

        def bad(site, info):
            raise RuntimeError("observer bug")

        def good(site, info):
            got.append(site)

        trace_events.register(bad)
        trace_events.register(good)
        try:
            trace_events.notify(("serving", "e"), {"requests": 1})
        finally:
            trace_events.unregister(bad)
            trace_events.unregister(good)
        assert got == [("serving", "e")]
        assert trace_events.dropped_notifications() == before + 1


# -- satellite: profiler span cap -------------------------------------------
class TestSpanCap:
    def test_drops_counted_and_reported(self, tmp_path, monkeypatch):
        monkeypatch.setattr(prof, "_SPAN_CAP", 2)
        prof.reset_profiler()
        prof.start_profiler()
        for i in range(5):
            with prof.RecordEvent(f"s{i}"):
                pass
        prof.stop_profiler(profile_path=None)
        assert prof.dropped_spans() == 3
        assert "3 span(s) dropped" in prof.summary()
        path = str(tmp_path / "t.json")
        assert prof.export_chrome_tracing(path) == 2
        data = json.load(open(path))
        assert data["otherData"]["dropped_spans"] == 3
        prof.reset_profiler()
        assert prof.dropped_spans() == 0

    def test_record_span_noop_when_not_profiling(self):
        prof.reset_profiler()
        assert prof.record_span("x", time.perf_counter(), 1.0) is False


# -- satellite: serving quantile fix ----------------------------------------
class TestServingQuantile:
    def test_ceil_rank_known_values(self):
        from paddle_tpu.serving.metrics import _quantile as q
        vals = [1, 2, 3, 4]
        assert q(vals, 0.25) == 1
        assert q(vals, 0.5) == 2
        assert q(vals, 0.75) == 3
        assert q(vals, 0.99) == 4
        assert q(vals, 1.0) == 4
        assert q([7], 0.99) == 7
        assert q([], 0.5) == 0.0

    def test_observe_span_feeds_snapshot(self):
        m = ServingMetrics("qtest")
        for ms in (1.0, 2.0, 3.0, 4.0):
            m.observe_span(queue_ms=ms, execute_ms=10 * ms)
        snap = m.snapshot()
        assert snap["queue_p50_ms"] == 2.0
        assert snap["execute_p99_ms"] == 40.0


# -- serving spans in the chrome trace --------------------------------------
class TestServingSpans:
    def test_batcher_emits_queue_execute_spans(self, tmp_path):
        from paddle_tpu.serving.batcher import MicroBatcher

        prof.reset_profiler()
        prof.start_profiler()
        try:
            with MicroBatcher(lambda ins: 0,
                              lambda bucket, reqs: [0] * len(reqs),
                              max_batch_size=2, max_queue_delay_ms=1.0,
                              name="spantest") as mb:
                mb.submit(([1],)).result(10)
        finally:
            prof.stop_profiler(profile_path=None)
        path = str(tmp_path / "t.json")
        prof.export_chrome_tracing(path)
        evs = json.load(open(path))["traceEvents"]
        serving = [e for e in evs if e.get("cat") == "serving"]
        names = {e["name"] for e in serving}
        assert "spantest/queue" in names and "spantest/execute" in names
        spans = {e["args"]["span"] for e in serving}
        assert len(spans) == 1  # one request, one span id on both events
        prof.reset_profiler()


# -- steptrace ---------------------------------------------------------------
class TestStepTrace:
    def _train(self, n=4):
        paddle.seed(0)
        reset_default_programs()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [-1, 4])
            y = fluid.data("y", [-1, 1])
            pred = fluid.layers.fc(x, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            paddle.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        for _ in range(n):
            exe.run(main, feed={"x": rng.rand(8, 4).astype(np.float32),
                                "y": rng.rand(8, 1).astype(np.float32)},
                    fetch_list=[loss])
        reset_default_programs()

    def test_executor_run_feeds_telemetry(self):
        r = metrics.MetricRegistry()
        obs.enable(registry=r)
        self._train(n=4)
        st = steptrace.active()
        snap = st.snapshot()
        assert snap["steps"] == 4
        assert snap["examples"] == 32
        assert snap["warmup_dispatches"] == 1
        assert snap["steps_post_warm"] == 3
        assert snap["dispatch_ms"] > 0
        reg = r.snapshot()
        assert (reg["paddle_tpu_steps_total"]["samples"][0][2]) == 4.0
        assert (reg["paddle_tpu_examples_total"]["samples"][0][2]) == 32.0

    def test_data_wait_recorded_from_dataloader(self):
        r = metrics.MetricRegistry()
        obs.enable(registry=r)
        from paddle_tpu.io import DataLoader, TensorDataset

        ds = TensorDataset([np.arange(16, dtype=np.float32).reshape(16, 1),
                            np.zeros((16, 1), np.float32)])
        for _ in DataLoader(ds, batch_size=4):
            pass
        # the blocking get was timed at least once per batch
        count = [s[2] for s
                 in r.snapshot()["paddle_tpu_data_wait_ms"]["samples"]
                 if s[0] == "paddle_tpu_data_wait_ms_count"]
        assert count and count[0] >= 4

    def test_summary_section_renders(self):
        obs.enable()
        self._train(n=3)
        text = steptrace.render_summary_section()
        assert "Training telemetry" in text
        assert "data wait" in text
        # the profiler summary embeds the same section
        assert "Training telemetry" in prof.summary()

    def test_disabled_means_no_active_hook(self):
        assert steptrace._active is None
        assert steptrace.render_summary_section() == ""

    def test_estimate_flops_cpu(self):
        import jax

        f = jax.jit(lambda a, b: a @ b)
        x = np.ones((8, 8), np.float32)
        flops = steptrace.estimate_flops(f, x, x)
        assert flops and flops > 0


# -- analysis rules M901 / M902 ---------------------------------------------
class TestTelemetryRules:
    def test_m901_data_starved(self):
        with RetraceMonitor(budget=2) as mon:
            trace_events.notify(("steptrace", "train"), {
                "steps_post_warm": 10, "data_wait_ms": 900.0,
                "dispatch_ms": 50.0, "device_ms": 50.0,
                "hbm_peak_bytes": 0, "hbm_limit_bytes": 0,
                "hbm_threshold": 0.9,
            })
        diags = mon.diagnostics()
        assert [d.rule for d in diags] == ["M901"]
        assert "input pipeline" in diags[0].message
        assert mon.steptrace_stats("train")["steps_post_warm"] == 10

    def test_m901_quiet_when_device_bound(self):
        with RetraceMonitor(budget=2) as mon:
            trace_events.notify(("steptrace", "train"), {
                "steps_post_warm": 10, "data_wait_ms": 10.0,
                "dispatch_ms": 500.0, "device_ms": 400.0,
                "hbm_peak_bytes": 0, "hbm_limit_bytes": 0,
                "hbm_threshold": 0.9,
            })
        assert mon.diagnostics() == []

    def test_m902_hbm_high_water(self):
        G = 2 ** 30
        with RetraceMonitor() as mon:
            trace_events.notify(("steptrace", "train"), {
                "steps_post_warm": 1, "data_wait_ms": 0.0,
                "dispatch_ms": 1.0, "device_ms": 1.0,
                "hbm_peak_bytes": 15 * G, "hbm_limit_bytes": 16 * G,
                "hbm_threshold": 0.9,
            })
        diags = mon.diagnostics()
        assert [d.rule for d in diags] == ["M902"]
        assert "HBM" in diags[0].message

    def test_m902_quiet_below_threshold(self):
        G = 2 ** 30
        with RetraceMonitor() as mon:
            trace_events.notify(("steptrace", "train"), {
                "steps_post_warm": 1, "data_wait_ms": 0.0,
                "dispatch_ms": 1.0, "device_ms": 1.0,
                "hbm_peak_bytes": 8 * G, "hbm_limit_bytes": 16 * G,
                "hbm_threshold": 0.9,
            })
        assert mon.diagnostics() == []


# -- enable / disable lifecycle ----------------------------------------------
class TestLifecycle:
    def test_enable_disable_roundtrip(self, tmp_path):
        base = str(tmp_path / "m.jsonl")
        obs.enable(port=-1, jsonl=base, jsonl_interval_s=3600)
        status = obs.status()
        assert status["enabled"] and status["port"] > 0
        assert metrics.bridge_installed()
        assert steptrace.active() is not None
        obs.disable()
        status = obs.status()
        assert not status["enabled"] and status["port"] is None
        assert steptrace._active is None

    def test_maybe_enable_from_flags_off_by_default(self):
        assert obs.maybe_enable_from_flags() is False
        assert not obs.enabled()

    def test_maybe_enable_from_flags_port(self):
        from paddle_tpu.framework.flags import set_flags

        set_flags({"metrics_port": -1})
        try:
            assert obs.maybe_enable_from_flags() is True
            assert obs.status()["port"] > 0
        finally:
            set_flags({"metrics_port": 0})
            obs.disable()
