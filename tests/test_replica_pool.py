"""paddle_tpu.serving pool + scenarios — the closed autoscaling loop.

Covers the replica lifecycle actuator end to end: dynamic fleet
membership under live traffic (``Router.add_replica`` entering through
the half-open probe/admit path, ``remove_replica`` retiring through
graceful drain without losing in-flight work, balancing staying correct
as N changes), the :class:`ReplicaPool` decision gauntlet (hysteresis
streaks, cooldown, min/max bounds, stale ``ScaleSignal.seq`` discard,
thrash detection feeding analysis rule S605), the
``Router.on_scale_signal`` hook-error accounting, ``SloEngine``
sequence stamping, scenario-generator determinism, and the open-loop
runner's loss accounting.  The real-engine disaggregation path is
exercised by ``tools/scenario_smoke.py``; the slow lane here drives a
real paged fleet through the pool for the hand-off identity check.
"""
import threading
import time
import unittest
from concurrent.futures import Future

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.analysis import RetraceMonitor
from paddle_tpu.framework import trace_events
from paddle_tpu.framework.errors import (
    InvalidArgumentError,
    TransientDeviceError,
    UnavailableError,
)
from paddle_tpu.observability.slo import Objective, ScaleSignal, SloEngine
from paddle_tpu.resilience import retry as _retry_mod
from paddle_tpu.serving import (
    DisaggServer,
    GenerationEngine,
    KVHandoff,
    ReplicaPool,
    Router,
    diurnal,
    flash_crowd,
    heavy_tail,
    poison,
    run_scenario,
)
from paddle_tpu.serving.replica import DRAINED, HEALTHY


class FakeEngine:
    """Duck-typed engine: synchronous futures by default, manual
    resolution (``manual=True``) for drain/in-flight tests."""

    def __init__(self, result="ok", manual=False, probe_fail=False):
        self.result = result
        self.manual = manual
        self.probe_fail = probe_fail
        self.pending = []
        self.calls = 0
        self.warmed = 0
        self.closed = False

    def synthetic_inputs(self):
        return [np.zeros((1,), np.float32)]

    def infer(self, inputs, timeout=None):
        if self.probe_fail:
            raise TransientDeviceError("probe failed")
        return [self.result]

    def submit(self, inputs, deadline_ms=None, **kw):
        self.calls += 1
        f = Future()
        if self.manual:
            self.pending.append((f, inputs))
        else:
            f.set_result((self.result, inputs))
        return f

    def resolve_all(self):
        for f, inputs in self.pending:
            f.set_result((self.result, inputs))
        self.pending = []

    def warmup(self):
        self.warmed += 1
        return 3

    def close(self, drain=True, timeout=None):
        self.closed = True


def _sig(direction, seq, at=0.0):
    return ScaleSignal(direction, "test", "obj", 1.0, at, seq)


def _inputs():
    return [np.zeros((1,), np.float32)]


class RouterMembershipTest(unittest.TestCase):
    """Satellite: dynamic fleet membership under live traffic."""

    def test_add_replica_enters_via_probe_and_serves(self):
        e0 = FakeEngine()
        r = Router([e0], name="mem-add")
        try:
            idx = r.add_replica(FakeEngine(result="new"))
            self.assertEqual(idx, 1)
            self.assertEqual(len(r.replicas), 2)
            self.assertEqual(r.replica(idx).state, HEALTHY)
            snap = r.stats()
            self.assertEqual(snap["replicas_added"], 1)
            self.assertGreaterEqual(snap["readmissions"], 1)
        finally:
            r.close()

    def test_add_replica_probe_failure_backs_out(self):
        r = Router([FakeEngine()], name="mem-bad")
        try:
            with self.assertRaises(UnavailableError):
                r.add_replica(FakeEngine(probe_fail=True))
            self.assertEqual(len(r.replicas), 1)
            # the backed-out index is never recycled
            idx = r.add_replica(FakeEngine())
            self.assertEqual(idx, 2)
        finally:
            r.close()

    def test_add_remove_under_live_traffic_zero_loss(self):
        """Membership churn with requests in flight: every accepted
        future resolves, balancing spreads onto the newcomer."""
        e0, e1 = FakeEngine(manual=True), FakeEngine(manual=True)
        r = Router([e0, e1], policy="least", name="mem-live")
        try:
            futs = [r.submit(_inputs()) for _ in range(4)]
            new = FakeEngine(result="new")  # instant completion
            idx = r.add_replica(new)
            # both incumbents hold 2 in-flight each; least-outstanding
            # must prefer the empty newcomer now
            futs += [r.submit(_inputs()) for _ in range(3)]
            self.assertGreaterEqual(new.calls, 3)
            e0.resolve_all()
            e1.resolve_all()
            for f in futs:
                f.result(timeout=5)
            # retire the newcomer under traffic: drain-then-remove
            self.assertTrue(r.remove_replica(idx, timeout=5))
            self.assertEqual(len(r.replicas), 2)
            self.assertEqual(r.stats()["replicas_removed"], 1)
            f = r.submit(_inputs())
            e0.resolve_all()
            e1.resolve_all()
            f.result(timeout=5)
        finally:
            r.close()

    def test_remove_drains_in_flight_work_first(self):
        """remove_replica on a replica holding in-flight work blocks in
        drain until the work resolves — nothing is dropped."""
        e0, e1 = FakeEngine(manual=True), FakeEngine(manual=True)
        r = Router([e0, e1], policy="least", name="mem-drain")
        try:
            # least-outstanding ties break by index: first submit lands
            # on e0, second on e1
            futs = [r.submit(_inputs()), r.submit(_inputs())]
            self.assertTrue(e1.pending)
            done = []
            t = threading.Thread(
                target=lambda: done.append(r.remove_replica(1, timeout=10)))
            t.start()
            time.sleep(0.15)
            self.assertTrue(t.is_alive())  # drain is waiting on e1
            e1.resolve_all()
            t.join(timeout=5)
            self.assertEqual(done, [True])
            self.assertEqual(len(r.replicas), 1)
            e0.resolve_all()
            for f in futs:
                f.result(timeout=5)
        finally:
            r.close()

    def test_remove_timeout_aborts_and_restores(self):
        e0, e1 = FakeEngine(manual=True), FakeEngine(manual=True)
        r = Router([e0, e1], policy="least", name="mem-abort")
        try:
            futs = [r.submit(_inputs()), r.submit(_inputs())]
            self.assertTrue(e1.pending)
            self.assertFalse(r.remove_replica(1, timeout=0.1))
            self.assertEqual(len(r.replicas), 2)
            self.assertEqual(r.replica(1).state, HEALTHY)
            e0.resolve_all()
            e1.resolve_all()
            for f in futs:
                f.result(timeout=5)
            self.assertTrue(r.remove_replica(1, timeout=5))
        finally:
            r.close()

    def test_p2c_stays_correct_as_fleet_changes(self):
        engines = [FakeEngine() for _ in range(2)]
        r = Router(engines, policy="p2c", name="mem-p2c")
        try:
            added = [r.add_replica(FakeEngine()) for _ in range(2)]
            for _ in range(40):
                r.submit(_inputs()).result(timeout=5)
            r.remove_replica(added[0], timeout=5)
            r.remove_replica(0, timeout=5)
            for _ in range(40):
                r.submit(_inputs()).result(timeout=5)
            self.assertEqual(len(r.replicas), 2)
        finally:
            r.close()

    def test_scale_hook_errors_counted_not_raised(self):
        """Satellite: a throwing scale hook is swallowed AND visible."""
        r = Router([FakeEngine()], name="hook-err")
        try:
            seen = []
            r.register_scale_hook(
                lambda s: (_ for _ in ()).throw(RuntimeError("boom")))
            r.register_scale_hook(seen.append)
            r.on_scale_signal(_sig("up", 1))
            r.on_scale_signal(_sig("steady", 2))
            self.assertEqual(len(seen), 2)  # later hooks still ran
            snap = r.stats()
            self.assertEqual(snap["scale_hook_errors"], 2)
            self.assertEqual(snap["scale_up_signals"], 1)
        finally:
            r.close()


class ReplicaPoolTest(unittest.TestCase):
    """The actuator's decision gauntlet, on an injected clock."""

    def _pool(self, **kw):
        self.t = [100.0]
        self.made = []

        def factory():
            e = FakeEngine()
            self.made.append(e)
            return e

        self.router = Router([FakeEngine()], name=f"pl-{id(self)}")
        defaults = dict(min_replicas=1, max_replicas=3, cooldown_s=10.0,
                        up_consecutive=1, down_consecutive=2,
                        thrash_window_s=20.0, async_actions=False,
                        clock=lambda: self.t[0])
        defaults.update(kw)
        return ReplicaPool(self.router, factory, **defaults)

    def test_scale_up_warms_before_admission(self):
        pool = self._pool()
        try:
            self.router.on_scale_signal(_sig("up", 1))
            self.assertEqual(len(self.router.replicas), 2)
            self.assertEqual(self.made[0].warmed, 1)
            snap = pool.stats()
            self.assertEqual(snap["scale_ups"], 1)
            self.assertEqual(snap["warmup_compiles"], 3)
        finally:
            self.router.close()

    def test_cooldown_bounds_and_hysteresis(self):
        pool = self._pool()
        try:
            self.router.on_scale_signal(_sig("up", 1))
            self.router.on_scale_signal(_sig("up", 2))  # inside cooldown
            self.assertEqual(pool.stats()["deferred_cooldown"], 1)
            self.t[0] += 11
            self.router.on_scale_signal(_sig("up", 3))
            self.assertEqual(len(self.router.replicas), 3)
            self.t[0] += 11
            self.router.on_scale_signal(_sig("up", 4))  # at max
            self.assertEqual(pool.stats()["deferred_bounds"], 1)
            self.t[0] += 11
            self.router.on_scale_signal(_sig("down", 5))  # streak 1 < 2
            self.assertEqual(pool.stats()["deferred_streak"], 1)
            self.router.on_scale_signal(_sig("down", 6))
            self.assertEqual(len(self.router.replicas), 2)
            self.assertEqual(pool.stats()["scale_downs"], 1)
            # the pool retires its own engines and closes them
            self.assertTrue(self.made[-1].closed)
        finally:
            self.router.close()

    def test_stale_seq_discarded(self):
        pool = self._pool()
        try:
            self.router.on_scale_signal(_sig("up", 5))
            self.t[0] += 11
            self.router.on_scale_signal(_sig("up", 5))  # replayed
            self.router.on_scale_signal(_sig("up", 3))  # reordered
            self.assertEqual(pool.stats()["stale_signals"], 2)
            self.assertEqual(len(self.router.replicas), 2)
            # unsequenced signals (seq -1) are never treated as stale
            self.router.on_scale_signal(_sig("up", -1))
            self.assertEqual(len(self.router.replicas), 3)
        finally:
            self.router.close()

    def test_steady_resets_streaks(self):
        pool = self._pool(down_consecutive=2)
        try:
            self.t[0] += 11
            self.router.on_scale_signal(_sig("up", 1))
            self.t[0] += 11
            self.router.on_scale_signal(_sig("down", 2))
            self.router.on_scale_signal(_sig("steady", 3))
            self.router.on_scale_signal(_sig("down", 4))
            # streak was reset by steady: still only 1 consecutive down
            self.assertEqual(len(self.router.replicas), 2)
            self.assertEqual(pool.stats()["deferred_streak"], 2)
        finally:
            self.router.close()

    def test_thrash_detection_feeds_s605(self):
        was_warm = _retry_mod._warm
        _retry_mod.mark_warm()
        mon = RetraceMonitor().install()
        pool = self._pool(cooldown_s=0.0, down_consecutive=1,
                          thrash_window_s=1e9)
        try:
            self.router.on_scale_signal(_sig("up", 1))
            self.router.on_scale_signal(_sig("down", 2))  # reversal 1
            self.router.on_scale_signal(_sig("up", 3))    # reversal 2
            snap = pool.stats()
            self.assertEqual(snap["thrash_events"], 2)
            self.assertEqual(snap["thrash_events_after_warm"], 2)
            rules = [d.rule for d in mon.diagnostics()]
            self.assertIn("S605", rules)
            self.assertIn(pool.name, mon.pool_stats())
        finally:
            _retry_mod._warm = was_warm
            mon.uninstall()
            self.router.close()

    def test_no_s605_below_two_thrashes(self):
        was_warm = _retry_mod._warm
        _retry_mod.mark_warm()
        mon = RetraceMonitor().install()
        pool = self._pool(cooldown_s=0.0, down_consecutive=1,
                          thrash_window_s=1e9)
        try:
            self.router.on_scale_signal(_sig("up", 1))
            self.router.on_scale_signal(_sig("down", 2))  # one reversal
            self.assertEqual(pool.stats()["thrash_events_after_warm"], 1)
            self.assertNotIn("S605",
                             [d.rule for d in mon.diagnostics()])
        finally:
            _retry_mod._warm = was_warm
            mon.uninstall()
            self.router.close()

    def test_drain_abort_keeps_replica(self):
        """A replica that cannot drain in time stays in the fleet."""
        t = [0.0]
        e0 = FakeEngine(manual=True)
        stuck = FakeEngine(manual=True)
        router = Router([e0, stuck], policy="least", name="pl-stuck")
        pool = ReplicaPool(router, FakeEngine, min_replicas=1,
                           max_replicas=3, cooldown_s=0.0,
                           up_consecutive=1, down_consecutive=1,
                           drain_timeout_s=0.1, async_actions=False,
                           clock=lambda: t[0])
        try:
            futs = [router.submit(_inputs()), router.submit(_inputs())]
            self.assertTrue(stuck.pending)
            router.on_scale_signal(_sig("down", 1))
            snap = pool.stats()
            self.assertEqual(snap["drain_aborts"], 1)
            self.assertEqual(snap["scale_downs"], 0)
            self.assertEqual(len(router.replicas), 2)
            e0.resolve_all()
            stuck.resolve_all()
            for f in futs:
                f.result(timeout=5)
        finally:
            router.close()

    def test_closed_pool_ignores_signals(self):
        pool = self._pool()
        try:
            pool.close()
            self.router.on_scale_signal(_sig("up", 1))
            self.assertEqual(len(self.router.replicas), 1)
            self.assertEqual(pool.stats()["scale_ups"], 0)
        finally:
            self.router.close()

    def test_pool_publishes_trace_events(self):
        seen = {}
        def listener(site, info):
            if site[0] == "pool":
                seen[site[1]] = info
        trace_events.register(listener)
        pool = self._pool()
        try:
            self.router.on_scale_signal(_sig("up", 1))
            self.assertIn(pool.name, seen)
            self.assertEqual(seen[pool.name]["scale_ups"], 1)
        finally:
            trace_events.unregister(listener)
            self.router.close()


class SloSequenceTest(unittest.TestCase):
    """Satellite: ScaleSignal.seq is stamped monotonically per tick."""

    def test_seq_monotonic_across_ticks(self):
        eng = SloEngine([Objective.latency("p99", threshold_ms=50.0,
                                           engine="nosuch")])
        sigs = []
        eng.on_scale(sigs.append)
        try:
            for _ in range(3):
                eng.tick()
            self.assertEqual([s.seq for s in sigs], [1, 2, 3])
        finally:
            eng.close()

    def test_default_seq_is_unsequenced(self):
        self.assertEqual(ScaleSignal("up", "r", "o", 1.0, 0.0).seq, -1)


class FakeTarget:
    """Instant-result submit target for runner accounting tests."""

    def __init__(self, max_len=64):
        self.max_len = max_len
        self.calls = 0

    def submit(self, prompt, max_new_tokens=32, deadline_ms=None, **kw):
        self.calls += 1
        if len(prompt) > self.max_len:
            raise InvalidArgumentError("prompt exceeds largest bucket")
        f = Future()
        f.set_result(np.arange(max_new_tokens, dtype=np.int32))
        return f


class ScenarioTest(unittest.TestCase):
    def test_generators_deterministic(self):
        for gen in (diurnal, flash_crowd, heavy_tail, poison):
            a = gen(duration_s=5.0, seed=7)
            b = gen(duration_s=5.0, seed=7)
            c = gen(duration_s=5.0, seed=8)
            self.assertEqual(a, b)
            self.assertNotEqual(a.events, c.events)
            self.assertTrue(all(x.t <= y.t for x, y in
                                zip(a.events, a.events[1:])))

    def test_runner_accounting_and_poison(self):
        scn = poison(duration_s=2.0, rps=8.0, poison_frac=0.4,
                     oversize_len=999, seed=3)
        tgt = FakeTarget(max_len=64)
        ticks = []
        rep = run_scenario(tgt, scn, time_scale=0.01, tick=ticks.append,
                           tick_s=0.5)
        n_poison = sum(1 for e in scn.events if e.poison)
        self.assertGreater(n_poison, 0)
        self.assertEqual(rep["rejected"], n_poison)
        self.assertEqual(rep["poison_accepted"], 0)
        self.assertEqual(rep["lost"], 0)
        self.assertEqual(rep["failed"], 0)
        self.assertEqual(rep["accepted"], len(scn.events) - n_poison)
        self.assertEqual(rep["completed"], rep["accepted"])
        self.assertEqual(len(rep["records"]), len(scn.events))
        self.assertEqual(ticks, [0.5, 1.0, 1.5, 2.0])

    def test_runner_prompts_reproducible(self):
        scn = diurnal(duration_s=2.0, seed=5)
        tgt = FakeTarget()
        r1 = run_scenario(tgt, scn, time_scale=0.0)
        r2 = run_scenario(tgt, scn, time_scale=0.0)
        self.assertEqual([r["tokens"] for r in r1["records"]],
                         [r["tokens"] for r in r2["records"]])


@pytest.mark.slow
class PoolEndToEndSlowTest(unittest.TestCase):
    """Real paged fleet: pool-grown replicas serve bit-identical tokens,
    and the prefill->decode hand-off survives a scenario sweep."""

    @classmethod
    def _model(cls):
        pt.seed(11)
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        m = GPTForCausalLM(GPTConfig(vocab_size=97, hidden_size=64,
                                     num_layers=2, num_heads=4,
                                     max_position=256, dropout=0.0))
        m.eval()
        return m

    def test_handoff_identity_through_disagg_server(self):
        model = self._model()

        def eng(role, name):
            return GenerationEngine(model, prompt_buckets=[8, 16],
                                    batch_size=2, kv_page_size=16,
                                    role=role, name=name)

        colo = eng("any", "e2e-colo")
        ds = DisaggServer(eng("prefill", "e2e-pre"),
                          eng("decode", "e2e-dec"), name="e2e-ds")
        colo.warmup()
        ds.warmup()
        try:
            rng = np.random.RandomState(0)
            for L, N in ((5, 6), (12, 4), (3, 1), (16, 8)):
                prompt = rng.randint(1, 97, size=(L,)).astype(np.int32)
                ref = colo.generate(prompt, N, timeout=60)
                got = ds.generate(prompt, max_new_tokens=N, timeout=60)
                np.testing.assert_array_equal(ref, got)
            self.assertEqual(ds.stats()["handoffs"], 4)
            h = ds.prefill.submit(np.arange(1, 5, dtype=np.int32), 4,
                                  handoff=True).result(60)
            self.assertIsInstance(h, KVHandoff)
        finally:
            colo.close()
            ds.close()

    def test_pool_grows_real_fleet_under_scenario(self):
        model = self._model()
        made = []

        def factory():
            e = GenerationEngine(model, prompt_buckets=[8, 16],
                                 batch_size=2, kv_page_size=16,
                                 name=f"e2e-g{len(made)}")
            made.append(e)
            return e

        router = Router([factory()], name="e2e-rt")
        pool = ReplicaPool(router, factory, min_replicas=1, max_replicas=2,
                           cooldown_s=0.5, up_consecutive=1,
                           down_consecutive=1, async_actions=False,
                           name="e2e-pool")
        router.warmup()
        try:
            seq = [0]

            def tick(_t):
                seq[0] += 1
                router.on_scale_signal(_sig("up", seq[0], at=time.time()))

            scn = diurnal(duration_s=3.0, base_rps=4.0, peak_rps=8.0,
                          prompt_len=(4, 12), max_new_tokens=(2, 4),
                          seed=17)
            rep = run_scenario(router, scn, tick=tick, tick_s=0.5,
                               result_timeout_s=120.0)
            self.assertEqual(rep["lost"], 0)
            self.assertEqual(rep["failed"], 0)
            self.assertEqual(pool.stats()["scale_ups"], 1)  # bounded at 2
            self.assertEqual(len(router.replicas), 2)
            # the pool-grown replica warmed off-path: compile set closed
            for e in made:
                self.assertEqual(e.compile_count, len([8, 16]) + 3)
        finally:
            pool.close()
            router.close(timeout=30)


if __name__ == "__main__":
    unittest.main()
