"""Multi-tenant serving (serving/tenancy.py + the engine integration).

Covers the TenantScheduler contract: stride-order weighted fairness
(2:1 weights admit 2:1 under contention), budget throttling/deferral,
budget preemption with bit-identical regeneration through the paged
engine, mixed-adapter serving on a CLOSED compile set, analysis rule
S607 (in-budget starvation / dead adapters) fire + silent, and the
tenant-label cardinality cap (a tenant-id flood lands in the
``__overflow__`` metric child, never an unbounded label set).
"""
import time
import unittest

import numpy as np

import paddle_tpu as pt
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.serving import GenerationEngine, TenantScheduler, TenantSpec


class TestTenantScheduler(unittest.TestCase):
    def test_stride_order_is_weighted_and_deterministic(self):
        ten = TenantScheduler([TenantSpec("a", weight=2.0),
                               TenantSpec("b", weight=1.0)])
        items = [("a", i) for i in range(3)] + [("b", i) for i in range(3)]
        admissible, deferred = ten.schedule(
            list(items), tenant_of=lambda it: it[0])
        self.assertEqual(deferred, [])
        # stride simulation: both passes start at 0, ties break by name;
        # weight-2 "a" advances half as fast so it lands 2 admissions
        # for every 1 of "b", per-tenant FIFO preserved
        self.assertEqual([t for t, _ in admissible],
                         ["a", "b", "a", "a", "b", "b"])
        self.assertEqual([i for t, i in admissible if t == "a"], [0, 1, 2])
        self.assertEqual([i for t, i in admissible if t == "b"], [0, 1, 2])

    def test_untagged_items_go_first_fcfs(self):
        ten = TenantScheduler([TenantSpec("a")])
        admissible, deferred = ten.schedule(
            [("a", 0), (None, 0), ("ghost", 1)],
            tenant_of=lambda it: it[0])
        self.assertEqual(deferred, [])
        # untagged and unknown-tenant items bypass the stride pick
        self.assertEqual(admissible, [(None, 0), ("ghost", 1), ("a", 0)])

    def test_budget_throttles_and_refills(self):
        ten = TenantScheduler([TenantSpec("flood", token_budget=2),
                               TenantSpec("ok")])
        self.assertFalse(ten.is_throttled("flood"))
        ten.charge("flood", 2)
        self.assertTrue(ten.is_throttled("flood"))
        self.assertEqual(ten.over_budget(), ["flood"])
        admissible, deferred = ten.schedule(
            [("flood", 0), ("ok", 0), ("flood", 1)],
            tenant_of=lambda it: it[0])
        self.assertEqual(admissible, [("ok", 0)])
        self.assertEqual(deferred, [("flood", 0), ("flood", 1)])
        # no refill_per_s: the bucket is a hard one-shot cap
        self.assertTrue(ten.is_throttled("flood"))
        snap = ten.snapshot()
        self.assertTrue(snap["flood"]["over_budget"])
        self.assertEqual(snap["flood"]["tokens"], 2)

    def test_validation(self):
        with self.assertRaises(InvalidArgumentError):
            TenantScheduler([TenantSpec("x", weight=0.0)])
        with self.assertRaises(InvalidArgumentError):
            TenantScheduler([TenantSpec("x", token_budget=0)])
        ten = TenantScheduler()
        with self.assertRaises(InvalidArgumentError):
            ten.spec("nobody")

    def test_slo_objectives(self):
        ten = TenantScheduler([TenantSpec("gold", slo_ms=250.0),
                               TenantSpec("free")])
        objs = ten.slo_objectives("eng#1")
        self.assertEqual(len(objs), 1)  # only the declared SLO
        self.assertIn("gold", objs[0].name)


class TestEngineTenancy(unittest.TestCase):
    @classmethod
    def setUpClass(cls):
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        pt.seed(4321)
        cls.cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                            num_heads=4, max_position=64, dropout=0.0,
                            lora_capacity=2, lora_rank=4)
        cls.model = GPTForCausalLM(cls.cfg)
        cls.model.eval()

    def _adapters(self):
        from paddle_tpu.lora import random_adapter
        return [random_adapter(self.model, f"t{i}", rank=4, seed=20 + i,
                               alpha=32.0, std=0.2) for i in range(2)]

    def test_mixed_adapters_bit_identical_to_serial_closed_compile_set(self):
        # three tenants (two adapters + base) interleaved on ONE engine:
        # every completion must be bitwise the per-tenant serial run,
        # and the mixed traffic must not reopen the compile set
        ten = TenantScheduler([
            TenantSpec("acme", weight=2.0, adapter_id=0),
            TenantSpec("globex", adapter_id=1),
            TenantSpec("base", adapter_id=-1)])
        prompts = [(np.arange(5) * 11 + 3) % 97, np.arange(4) % 97,
                   (np.arange(6) * 3 + 1) % 97]
        a0, a1 = self._adapters()

        def build(name, tenancy=None):
            eng = GenerationEngine(self.model, prompt_buckets=[8],
                                   batch_size=2, cache_len=48,
                                   kv_page_size=8, tenancy=tenancy,
                                   name=name)
            eng.install_adapter(0, a0)
            eng.install_adapter(1, a1)
            eng.warmup()
            return eng

        refs = {}
        with build("ten-serial") as ser:
            for tn, aid in (("acme", 0), ("globex", 1), ("base", -1)):
                refs[tn] = [ser.generate(p, 6, timeout=120,
                                         adapter_id=aid).tolist()
                            for p in prompts]
        with build("ten-mixed", tenancy=ten) as eng:
            n_tr = eng.compile_count
            futs = [(tn, i, eng.submit(p, 6, tenant=tn))
                    for i, p in enumerate(prompts)
                    for tn in ("acme", "globex", "base")]
            for tn, i, f in futs:
                self.assertEqual(f.result(120).tolist(), refs[tn][i],
                                 f"tenant {tn} prompt {i}")
            self.assertEqual(eng.compile_count, n_tr)
            st = eng.stats()
            self.assertEqual(st["completed"], 9)
        # adapters actually differentiate the tenants
        self.assertNotEqual(refs["acme"], refs["base"])
        self.assertNotEqual(refs["acme"], refs["globex"])

    def test_budget_preemption_regenerates_bit_identically(self):
        # drain the tenant's bucket mid-decode: the engine must preempt
        # its live slot (pages released), then re-admit after refill and
        # regenerate EXACTLY the greedy tokens of an uncontended run
        ten = TenantScheduler([
            TenantSpec("metered", token_budget=50, refill_per_s=500.0)])
        p = (np.arange(6) * 9 + 4) % 97
        with GenerationEngine(self.model, prompt_buckets=[8], batch_size=2,
                              cache_len=48, kv_page_size=8,
                              tenancy=ten, name="ten-preempt") as eng:
            eng.warmup()
            ref = eng.generate(p, 20, timeout=120).tolist()  # untagged
            base_steps = eng.stats()["decode_steps"]
            fut = eng.submit(p, 20, tenant="metered")
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:  # wait until mid-decode
                if eng.stats()["decode_steps"] > base_steps:
                    break
                time.sleep(0.002)
            ten.charge("metered", 200)  # empty the bucket -> preempt
            self.assertEqual(fut.result(120).tolist(), ref)
            st = eng.stats()
            self.assertGreaterEqual(st["tenant_preempted"], 1)
            self.assertGreaterEqual(ten.snapshot()["metered"]["preempted"],
                                    1)
            self.assertEqual(st["kv_pages_leaked"], 0)


class TestS607(unittest.TestCase):
    def test_fires_on_in_budget_starvation(self):
        from paddle_tpu.analysis import RetraceMonitor
        from paddle_tpu.framework import trace_events
        with RetraceMonitor(budget=8) as mon:
            trace_events.notify(("tenancy", "eng#t"), {
                "decode_steps_after_warm": 200, "adapters_installed": 0,
                "adapters_dead": 0,
                "tenants": {"victim": {
                    "weight": 1.0, "queued": 3, "admitted": 1,
                    "starved_after_warm": 40, "over_budget": False}}})
        self.assertEqual(mon.tenancy_stats("eng#t")["tenants"]["victim"]
                         ["starved_after_warm"], 40)
        diags = [d for d in mon.diagnostics() if d.rule == "S607"]
        self.assertEqual(len(diags), 1)
        self.assertIn("victim", diags[0].message)
        self.assertIn("weighted-fair", diags[0].message)

    def test_fires_on_dead_adapters(self):
        from paddle_tpu.analysis import RetraceMonitor
        from paddle_tpu.framework import trace_events
        with RetraceMonitor() as mon:
            trace_events.notify(("tenancy", "eng#d"), {
                "decode_steps_after_warm": 120, "adapters_installed": 3,
                "adapters_dead": 2, "tenants": {}})
        diags = [d for d in mon.diagnostics() if d.rule == "S607"]
        self.assertEqual(len(diags), 1)
        self.assertIn("never matched", diags[0].message)

    def test_silent_on_throttled_and_healthy(self):
        from paddle_tpu.analysis import RetraceMonitor
        from paddle_tpu.framework import trace_events
        with RetraceMonitor(budget=8) as mon:
            trace_events.notify(("tenancy", "eng#ok"), {
                "decode_steps_after_warm": 200, "adapters_installed": 2,
                "adapters_dead": 0,
                "tenants": {
                    # over-budget waiting = throttling by design
                    "flooder": {"weight": 1.0, "queued": 9, "admitted": 2,
                                "starved_after_warm": 90,
                                "over_budget": True},
                    # in-budget and promptly served
                    "gold": {"weight": 2.0, "queued": 0, "admitted": 5,
                             "starved_after_warm": 2,
                             "over_budget": False}}})
        self.assertEqual(
            [d for d in mon.diagnostics() if d.rule == "S607"], [])


class TestTenantLabelCap(unittest.TestCase):
    def test_tenant_flood_lands_in_overflow_child(self):
        # a malicious/buggy client inventing tenant ids must not blow up
        # the label space: past the cap every new tenant routes to the
        # __overflow__ child and the drop counter ticks
        import paddle_tpu.observability as obs
        from paddle_tpu.observability.metrics import (
            DROPPED_LABELS_COUNTER, MetricRegistry, set_default_registry)
        from paddle_tpu.serving.metrics import ServingMetrics
        reg = MetricRegistry(max_label_children=4)
        was_enabled = obs._enabled
        set_default_registry(reg)
        obs._enabled = True
        try:
            sm = ServingMetrics("ovf#0")
            for i in range(10):
                sm.observe_tenant(f"tenant-{i}", 5.0, 3)
            fam = reg.get("paddle_tpu_serving_tenant_latency_ms")
            self.assertIsNotNone(fam)
            kids = [values for values, _ in fam.children()]
            self.assertIn(("__overflow__",), kids)
            self.assertLessEqual(len(kids), 5)  # cap + overflow child
            dropped = reg.get(DROPPED_LABELS_COUNTER)
            self.assertIsNotNone(dropped)
            total = sum(v for _, _, v in dropped.expose())
            self.assertGreaterEqual(total, 6)
        finally:
            obs._enabled = was_enabled
            set_default_registry(None)


if __name__ == "__main__":
    unittest.main()
