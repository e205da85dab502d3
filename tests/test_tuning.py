"""Measured-search engine (paddle_tpu.tuning; no kernel searches through
it since PR 48, a tile is a rule of its kernel's shapes): plan-space
enumeration + check_plan pre-filtering, deterministic serving-space
search over a fixed trace, v2 disk-cache round-trips for both spaces,
stale-schema tolerance, scope-aware clearing, and K701 on post-warm
plan/serving searches.

All on CPU — plan/serving measures are injected deterministic scorers
(wall-clock scoring would make winner selection flaky), which exercises
the full search/cache/counter machinery; the replay-timing path is
gated end-to-end in tools/tune_smoke.py.
"""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from paddle_tpu.framework.flags import set_flags
from paddle_tpu.tuning import engine, plan_space, serving_space
from paddle_tpu.tuning.trace import RequestTrace, TraceRecorder


@pytest.fixture(autouse=True)
def _clean_tuner_state():
    """Each test starts cold (memory caches, counters, warm flag) and
    leaves the flags at their defaults."""
    engine.clear_cache()
    engine.reset_counters()
    engine.reset_warm()
    yield
    set_flags({"kernel_tuning_cache": "", "measured_search": "on"})
    engine.clear_cache()
    engine.reset_counters()
    engine.reset_warm()


def _mesh(**axes):
    """check_plan and the key builder only read ``mesh.shape``, so a
    stub carries any axis geometry on a single-device CPU test host."""
    shape = {"pipe": 1, "data": 1, "sharding": 1, "sep": 1, "expert": 1,
             "model": 1}
    shape.update(axes)
    return SimpleNamespace(shape=shape)


SHAPES = {"fc.weight": (10, 16), "fc.bias": (16,), "emb.weight": (32, 16)}


def _score_plan(cfg):
    """Deterministic plan scorer: sharding 'emb' over model wins, every
    collective dial at base."""
    ms = 10.0
    if cfg["axes"].get("emb") == "model":
        ms -= 5.0
    ms += cfg["fp16_allreduce"] + cfg["allreduce_bucket_mb"] / 100.0
    ms += 0.0 if cfg["overlap_grad_sync"] else 1.0
    return ms


def _score_serving(cfg):
    """Deterministic serving scorer: batch_size 16 with a 2 ms delay
    wins."""
    return (abs(cfg["batch_size"] - 16) * 0.5
            + abs(cfg["max_queue_delay_ms"] - 2.0)
            + 10.0 / cfg["buckets"][-1])


BASE_SERVING = {"buckets": [16, 48], "batch_size": 8,
                "max_queue_delay_ms": 1.0}


class TestPlanSpace:
    def test_enumeration_prefiltered_by_check_plan(self):
        """With model=4, any candidate putting 'model' on the fc group is
        invalid (fc.weight dim0=10 and dim1=16: first dim >= 4 is 10,
        10 % 4 != 0 → P502) and must be dropped BEFORE measurement."""
        mesh = _mesh(model=4)
        groups = plan_space.param_groups(SHAPES)
        cands = plan_space.plan_candidates(groups, mesh)
        bad = [c for c in cands if c["axes"].get("fc") == "model"]
        assert bad, "space must propose the invalid assignment"
        assert all(not plan_space.is_valid_candidate(c, groups, mesh)
                   for c in bad)
        good = [c for c in cands if c["axes"].get("emb") == "model"
                and c["axes"].get("fc") == "none"]
        assert good, "space must keep the valid assignment"
        assert all(plan_space.is_valid_candidate(c, groups, mesh)
                   for c in good)

    def test_expert_axis_proposed_and_p506_prefiltered(self):
        """With expert=4 in the mesh the space proposes the 'expert'
        axis like any other, but P506 rejects it on non-expert parameter
        groups before any measurement ('emb.weight' dim0=32 divides by 4,
        so only the name rule can catch it); a stacked expert-weight
        group keeps the assignment."""
        mesh = _mesh(expert=4)
        groups = plan_space.param_groups(SHAPES)
        cands = plan_space.plan_candidates(groups, mesh)
        on_expert = [c for c in cands
                     if c["axes"].get("emb") == "expert"]
        assert on_expert, "space must propose the expert axis"
        assert all(not plan_space.is_valid_candidate(c, groups, mesh)
                   for c in on_expert)
        moe_groups = plan_space.param_groups(
            {"expert_fc1.w": (4, 16, 32), "expert_b1.b": (4, 32)})
        mcands = plan_space.plan_candidates(moe_groups, mesh)
        good = [c for c in mcands
                if set(c["axes"].values()) == {"expert"}]
        assert good, "space must propose expert sharding for experts"
        assert all(plan_space.is_valid_candidate(c, moe_groups, mesh)
                   for c in good)

    def test_search_skips_prefiltered_and_picks_valid_winner(self):
        set_flags({"kernel_tuning_cache": "off"})
        details = {}
        won = plan_space.tune_plan(
            "t-plan", shapes=SHAPES, mesh=_mesh(model=4),
            measure=_score_plan, details=details)
        assert won["axes"]["emb"] == "model"
        assert won["axes"]["fc"] == "none"
        assert details["event"] == "search"
        assert details["n_prefiltered"] > 0
        c = engine.get_counters("t-plan")
        assert c["searches"] == 1
        assert c["prefiltered"] == details["n_prefiltered"]
        # every measured candidate passed the filter
        assert c["configs_timed"] + c["prefiltered"] == \
            details["n_candidates"]

    def test_measured_search_off_returns_base_untimed(self):
        set_flags({"measured_search": "off", "kernel_tuning_cache": "off"})
        timed = []
        won = plan_space.tune_plan(
            "t-plan-off", shapes=SHAPES, mesh=_mesh(model=4),
            measure=lambda cfg: timed.append(cfg) or 1.0)
        assert timed == []
        assert won["axes"] == {"emb": "none", "fc": "none"}
        assert engine.get_counters("t-plan-off")["heuristic"] == 1

    def test_apply_plan_sets_strategy_dials(self):
        from paddle_tpu.distributed.fleet import DistributedStrategy
        strat = DistributedStrategy()
        cfg = {"axes": {}, "fp16_allreduce": 1, "allreduce_bucket_mb": 64,
               "overlap_grad_sync": 0}
        plan_space.apply_plan(cfg, strategy=strat)
        assert strat.fp16_allreduce is True
        assert strat.allreduce_bucket_mb == 64
        assert strat.overlap_grad_sync is False

    def test_apply_plan_annotates_network_params(self):
        import paddle_tpu as paddle
        net = paddle.nn.Linear(16, 8)
        mesh = _mesh(model=4)
        cfg = {"axes": {"weight": "model", "bias": "none"}}
        plan_space.apply_plan(cfg, network=net, mesh=mesh)
        specs = {n: getattr(b, "partition_spec", None)
                 for n, b in net.named_parameters()}
        assert specs["weight"] == ("model",)  # dim0=16 divisible by 4
        assert specs["bias"] is None


class TestServingSpace:
    def test_search_deterministic_under_fixed_trace(self):
        set_flags({"kernel_tuning_cache": "off"})
        trace = RequestTrace.synthetic(n=8, seed=3)
        winners = []
        for _ in range(2):
            engine.clear_cache()
            engine.reset_counters()
            winners.append(serving_space.tune_serving(
                "t-serve", BASE_SERVING, trace=trace,
                measure=_score_serving))
        assert winners[0] == winners[1]
        # coordinate sweep: the dominant dial moves, the rest stay base
        assert winners[0]["batch_size"] == 16
        assert winners[0]["max_queue_delay_ms"] == 1.0

    def test_trace_key_binds_workload(self):
        t1 = RequestTrace.synthetic(n=8, seed=3)
        t2 = RequestTrace.synthetic(n=8, seed=4)
        assert t1.key() == RequestTrace.synthetic(n=8, seed=3).key()
        assert t1.key() != t2.key()

    def test_trace_save_load_round_trip(self, tmp_path):
        t = RequestTrace.synthetic(n=6, seed=5)
        p = str(tmp_path / "trace.json")
        t.save(p)
        back = RequestTrace.load(p)
        assert len(back) == len(t)
        for (p1, n1), (p2, n2) in zip(t, back):
            assert n1 == n2 and np.array_equal(p1, p2)
        assert back.key() == t.key()

    def test_recorder_wraps_submit(self):
        rec = TraceRecorder()
        calls = []
        submit = rec.wrap(lambda p, n: calls.append((p, n)) or "fut")
        assert submit(np.arange(4), 7) == "fut"
        assert len(rec) == 1 and len(calls) == 1
        tr = rec.trace()
        assert tr.entries[0][1] == 7

    def test_latency_budget_rejects_candidate(self):
        set_flags({"kernel_tuning_cache": "off"})

        def measure(cfg):
            if cfg["batch_size"] >= 16:  # "fast but blows p99"
                raise engine.CandidateError("p99 over budget")
            return abs(cfg["batch_size"] - 16)

        won = serving_space.tune_serving(
            "t-budget", BASE_SERVING, trace=RequestTrace.synthetic(n=4),
            measure=measure)
        assert won["batch_size"] == 8  # best that fits the budget
        assert engine.get_counters("t-budget")["search_failures"] >= 1


class TestDiskCache:
    def test_round_trips_both_spaces_across_processes(self, tmp_path):
        path = str(tmp_path / "tuning.json")
        set_flags({"kernel_tuning_cache": path})
        trace = RequestTrace.synthetic(n=8, seed=3)
        plan_won = plan_space.tune_plan(
            "t-plan", shapes=SHAPES, mesh=_mesh(model=4),
            measure=_score_plan)
        serve_won = serving_space.tune_serving(
            "t-serve", BASE_SERVING, trace=trace, measure=_score_serving)
        data = json.load(open(path))
        assert data["version"] == engine.SCHEMA_VERSION
        spaces = sorted(e["space"] for e in data["entries"].values())
        assert spaces == ["plan", "serving"]
        assert all(e["version"] == engine.SCHEMA_VERSION
                   for e in data["entries"].values())
        # "restarted process": memory gone, disk stays — zero searches
        engine.clear_cache(memory=True, disk=False)
        engine.reset_counters()
        boom = lambda cfg: (_ for _ in ()).throw(  # noqa: E731
            AssertionError("measured after restart"))
        assert plan_space.tune_plan(
            "t-plan", shapes=SHAPES, mesh=_mesh(model=4),
            measure=boom) == plan_won
        assert serving_space.tune_serving(
            "t-serve", BASE_SERVING, trace=trace, measure=boom) == serve_won
        for name in ("t-plan", "t-serve"):
            c = engine.get_counters(name)
            assert c["disk_hits"] == 1 and c["searches"] == 0

    def test_stale_schema_entries_ignored(self, tmp_path):
        path = str(tmp_path / "tuning.json")
        # a PR-4-era kernel-only cache: no version/space fields
        # and a v2 entry of the kernel tile search that PR 48 removed
        stale = {"version": 1, "entries": {
            "flash_fwd|128x64:float32|TPU v4": {
                "kernel": "flash_fwd", "config": {"block_q": 512},
                "best_ms": 1.0},
            "kernel|flash_fwd|128x64:bfloat16|TPU v5 lite": {
                "space": "kernel", "name": "flash_fwd", "version": 2,
                "kernel": "flash_fwd", "config": {"block_q": 256},
                "best_ms": 1.0}}}
        with open(path, "w") as f:
            json.dump(stale, f)
        set_flags({"kernel_tuning_cache": path})
        assert engine._disk_entries() == {}  # ignored, not a crash
        won = plan_space.tune_plan(
            "t-plan", shapes=SHAPES, mesh=_mesh(model=4),
            measure=_score_plan)
        assert engine.get_counters("t-plan")["searches"] == 1
        data = json.load(open(path))
        # the stale entry was dropped on rewrite, the winner persisted
        assert all(e["version"] == engine.SCHEMA_VERSION
                   for e in data["entries"].values())
        assert [e["config"] for e in data["entries"].values()] == [won]

    def test_clear_cache_scoped_by_space(self, tmp_path):
        path = str(tmp_path / "tuning.json")
        set_flags({"kernel_tuning_cache": path})
        trace = RequestTrace.synthetic(n=8, seed=3)
        plan_space.tune_plan("t-plan", shapes=SHAPES, mesh=_mesh(model=4),
                             measure=_score_plan)
        serving_space.tune_serving("t-serve", BASE_SERVING, trace=trace,
                                   measure=_score_serving)
        engine.clear_cache(disk=True, space="serving")
        data = json.load(open(path))
        spaces = [e["space"] for e in data["entries"].values()]
        assert spaces == ["plan"]
        # memory scoped too: plan resolves as a hit, serving re-searches
        engine.reset_counters()
        plan_space.tune_plan("t-plan", shapes=SHAPES, mesh=_mesh(model=4),
                             measure=_score_plan)
        serving_space.tune_serving("t-serve", BASE_SERVING, trace=trace,
                                   measure=_score_serving)
        assert engine.get_counters("t-plan")["hits"] == 1
        assert engine.get_counters("t-serve")["searches"] == 1


    def test_cache_path_flag_forms(self, tmp_path):
        import os

        from paddle_tpu import sysconfig
        from paddle_tpu.ops import autotune
        set_flags({"kernel_tuning_cache": "off"})
        assert engine.cache_path() is None
        set_flags({"kernel_tuning_cache": str(tmp_path / "t.json")})
        assert engine.cache_path() == str(tmp_path / "t.json")
        set_flags({"kernel_tuning_cache": ""})
        assert engine.cache_path() == os.path.join(
            sysconfig.cache_root(), "kernel_tuning.json")
        # the two other names the path goes by (benchmarks/run.py reads
        # the second)
        assert sysconfig.kernel_tuning_cache_path() == engine.cache_path()
        assert autotune.cache_path() == engine.cache_path()


class TestProfilerSection:
    def test_summary_section_renders_and_resets(self):
        from paddle_tpu import profiler
        profiler.reset_profiler()
        set_flags({"kernel_tuning_cache": "off"})
        plan_space.tune_plan("t-plan", shapes=SHAPES, mesh=_mesh(model=4),
                             measure=_score_plan)
        s = profiler.summary()
        assert "Measured search" in s and "t-plan" in s and "plan" in s
        profiler.reset_profiler()
        assert profiler.summary() == ""  # deltas cleared with the rest


class TestResolve:
    def test_every_candidate_failing_raises_the_first_error(self):
        # an all-failed search is a broken client, not a tuning outcome:
        # it must not quietly hand back the untimed default
        set_flags({"kernel_tuning_cache": "off"})

        def boom(cand):
            raise RuntimeError(f"refused block={cand['block']}")

        with pytest.raises(RuntimeError, match="refused block=8"):
            engine.resolve("plan", "t-broken", "k",
                           candidates=[{"block": 8}, {"block": 16}],
                           measure=boom, heuristic={"block": 8},
                           measurable=True)
        c = engine.get_counters("t-broken")
        assert c["search_failures"] == 2 and c["searches"] == 0

    def test_search_then_hit_events_published(self):
        from paddle_tpu.framework import trace_events
        seen = []
        cb = lambda site, info: seen.append((tuple(site), dict(info)))  # noqa: E731
        trace_events.register(cb)
        set_flags({"kernel_tuning_cache": "off"})
        try:
            for _ in range(2):
                engine.resolve("plan", "t-probe", "k",
                               candidates=[{"block": 8}, {"block": 16}],
                               measure=lambda c: float(c["block"]),
                               heuristic={"block": 8}, measurable=True)
        finally:
            trace_events.unregister(cb)
        kinds = [info["event"] for site, info in seen
                 if site == ("autotune", "t-probe")]
        assert kinds == ["search", "hit"]
        assert seen[0][1]["n_timed"] == 2
        assert seen[0][1]["counters"]["searches"] == 1


class TestMeasure:
    def test_measure_ms_warm_call_plus_best_of_n(self):
        calls = []
        ms = engine.measure_ms(lambda: calls.append(1), repeats=3)
        assert len(calls) == 4  # 1 untimed warm + best-of-3
        assert ms >= 0.0


class TestServingHotPath:
    def test_k701_fires_on_post_warm_plan_search(self):
        from paddle_tpu.analysis import RetraceMonitor
        set_flags({"kernel_tuning_cache": "off"})
        with RetraceMonitor() as mon:
            engine.mark_warm()
            plan_space.tune_plan("t-plan", shapes=SHAPES,
                                 mesh=_mesh(model=4), measure=_score_plan)
        stats = mon.autotune_stats("t-plan")
        assert stats["counters"]["searches_after_warm"] == 1
        assert stats["space"] == "plan"
        k701 = [d for d in mon.diagnostics() if d.rule == "K701"]
        assert len(k701) == 1
        assert "t-plan" in k701[0].message
        assert "sharding plan" in k701[0].message

    def test_k701_silent_on_post_warm_cache_hit(self):
        from paddle_tpu.analysis import RetraceMonitor
        set_flags({"kernel_tuning_cache": "off"})
        # tuned cold (pre-warm), then resolved again on the hot path
        plan_space.tune_plan("t-plan", shapes=SHAPES, mesh=_mesh(model=4),
                             measure=_score_plan)
        with RetraceMonitor() as mon:
            engine.mark_warm()
            plan_space.tune_plan("t-plan", shapes=SHAPES,
                                 mesh=_mesh(model=4), measure=_score_plan)
        assert mon.autotune_stats("t-plan")["event"] == "hit"
        assert not [d for d in mon.diagnostics() if d.rule == "K701"]


class TestFromTuned:
    def test_generation_engine_from_tuned_maps_config(self):
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        from paddle_tpu.serving import GenerationEngine

        paddle.seed(7)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
            max_position=64, dropout=0.0))
        cfg = {"buckets": [8, 16], "batch_size": 3,
               "max_queue_delay_ms": 2.5, "speculative_k": 2}
        with GenerationEngine.from_tuned(model, cfg,
                                         name="tuned-test") as eng:
            assert eng._buckets == [8, 16]
            assert eng._batch == 3
            assert eng._spec_k == 2
            assert eng.name == "tuned-test"
