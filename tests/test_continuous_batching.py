"""Slot-level continuous batching (serving/generation.py).

Covers the scheduler's contract: token identity with the legacy
run-batch-to-completion path AND uncached greedy under staggered
mid-decode admission; slot eviction/re-admission without KV
contamination; the closed compile set (``len(prompt_buckets) + 2``,
zero post-warmup recompiles); EOS; the ``FLAGS_continuous_batching``
legacy fallback; transient-failure restart; and analysis rule S603
(sustained slot starvation while the queue is non-empty).
"""
import time
import unittest

import jax
import numpy as np

import paddle_tpu as pt
from paddle_tpu.framework.errors import UnavailableError
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.serving import GenerationEngine

#: one more executable wherever several devices make up the global mesh
#: (the suite's eight): a step's outputs carry the mesh's sharding, so the
#: host-built fresh state of warm-up is another abstract input and the
#: step traces once more for it (GenerationEngine.warmup's docstring)
FRESH_TRACE = int(len(jax.devices()) > 1)


class TestContinuousBatching(unittest.TestCase):
    @classmethod
    def setUpClass(cls):
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        pt.seed(4321)
        cls.cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                            num_heads=4, max_position=64, dropout=0.0)
        cls.model = GPTForCausalLM(cls.cfg)
        cls.model.eval()

    def _ref_greedy(self, prompt, n, eos=None):
        import jax.numpy as jnp
        ids, outs = list(map(int, prompt)), []
        for _ in range(n):
            logits = np.asarray(self.model(jnp.asarray([ids], jnp.int32)))[0]
            nxt = int(np.argmax(logits[-1]))
            outs.append(nxt)
            ids.append(nxt)
            if eos is not None and nxt == eos:
                break
        return outs

    def test_token_identity_staggered_admission(self):
        # one long request pins a slot while shorts are admitted
        # mid-decode into the other slot as it recycles — every output
        # must match uncached greedy AND the legacy fixed-batch path
        prompts = [(np.arange(10) * 5 + 2) % 97, np.arange(3) % 97,
                   (np.arange(6) * 3) % 97, (np.arange(4) * 7 + 1) % 97,
                   (np.arange(5) * 11 + 3) % 97]
        budgets = [14, 3, 4, 5, 3]
        refs = [self._ref_greedy(p, b) for p, b in zip(prompts, budgets)]
        with GenerationEngine(self.model, prompt_buckets=[8, 16],
                              batch_size=2, continuous=True,
                              name="cb-stagger") as eng:
            # 2 admits + decode + evict + the fresh-state trace of decode
            # (on the suite's 8-device mesh a step's outputs carry the
            # mesh's sharding, so _init_state's host-built state is another
            # abstract input: FRESH_TRACE)
            self.assertEqual(eng.warmup(), 4 + FRESH_TRACE)
            futs = [eng.submit(prompts[0], budgets[0]),
                    eng.submit(prompts[1], budgets[1])]
            for p, b in zip(prompts[2:], budgets[2:]):
                time.sleep(0.02)  # long request is mid-decode by now
                futs.append(eng.submit(p, b))
            gens = [f.result(120) for f in futs]
            for g, ref in zip(gens, refs):
                self.assertEqual(g.tolist(), ref)
            # slot churn never reopened the compile set
            self.assertEqual(eng.compile_count, 4 + FRESH_TRACE)
        with GenerationEngine(self.model, prompt_buckets=[8, 16],
                              batch_size=2, continuous=False,
                              name="cb-legacy") as leg:
            for p, b, ref in zip(prompts, budgets, refs):
                self.assertEqual(
                    leg.generate(p, b, timeout=120).tolist(), ref)

    def test_slot_reuse_has_no_kv_contamination(self):
        # batch_size=1: every request reuses THE one slot; admission must
        # fully replace the previous occupant's cache row
        prompts = [(np.arange(7) * 13 + 5) % 97, np.arange(2) % 97,
                   (np.arange(8) * 3 + 1) % 97]
        with GenerationEngine(self.model, prompt_buckets=[8], batch_size=1,
                              continuous=True, name="cb-reuse") as eng:
            # 1 admit + decode + evict + the fresh-state trace of decode
            self.assertEqual(eng.warmup(), 3 + FRESH_TRACE)
            for p in prompts:
                self.assertEqual(eng.generate(p, 5, timeout=120).tolist(),
                                 self._ref_greedy(p, 5))
            self.assertEqual(eng.compile_count, 3 + FRESH_TRACE)
            st = eng.stats()
            self.assertEqual(st["admitted"], 3)
            self.assertGreater(st["decode_steps"], 0)
            self.assertIn("slot_occupancy", st)
            self.assertIn("queue_age_ms", st)

    def test_eos_stops_early(self):
        probe = self._ref_greedy(np.arange(4) % 97, 8)
        eos = probe[1]
        expect = probe[: probe.index(eos) + 1]
        self.assertLess(len(expect), 8)
        with GenerationEngine(self.model, prompt_buckets=[8], batch_size=2,
                              continuous=True, eos_token_id=eos,
                              name="cb-eos") as eng:
            gen = eng.generate(np.arange(4) % 97, max_new_tokens=8,
                               timeout=120)
            self.assertEqual(gen.tolist(), expect)
            self.assertEqual(gen[-1], eos)

    def test_flag_fallback_to_legacy(self):
        set_flags({"continuous_batching": False})
        try:
            eng = GenerationEngine(self.model, prompt_buckets=[8],
                                   batch_size=1, name="cb-flag")
            try:
                self.assertFalse(eng.stats()["continuous"])
                self.assertIsNone(eng._thread)
                p = np.arange(3) % 97
                self.assertEqual(eng.generate(p, 3, timeout=120).tolist(),
                                 self._ref_greedy(p, 3))
            finally:
                eng.close()
        finally:
            set_flags({"continuous_batching": True})

    def test_transient_failure_restarts_and_tokens_survive(self):
        from paddle_tpu.resilience.faults import FaultPlan
        with GenerationEngine(self.model, prompt_buckets=[8], batch_size=2,
                              continuous=True, circuit_breaker=False,
                              name="cb-restart") as eng:
            eng.warmup()
            p = (np.arange(5) * 9 + 4) % 97
            ref = self._ref_greedy(p, 6)
            self.assertEqual(eng.generate(p, 6, timeout=120).tolist(), ref)
            plan = FaultPlan.parse(
                "site=serving.decode,nth=1,error=TransientDeviceError")
            with plan:
                # admission trips the fault; greedy decode is
                # deterministic, so the restarted request regenerates the
                # exact same tokens
                self.assertEqual(
                    eng.generate(p, 6, timeout=120).tolist(), ref)
            self.assertEqual(plan.stats()["serving.decode"]["fired"], 1)
            self.assertGreaterEqual(eng.stats()["restarts"], 1)

    def test_s603_fires_on_starved_queue(self):
        from paddle_tpu.analysis import RetraceMonitor

        class _AlwaysOpen:  # deterministic stand-in for an open circuit
            def allow(self, key):
                return False

            def record_success(self, key):
                pass

            def record_failure(self, key):
                pass

        with RetraceMonitor(budget=8) as mon:
            eng = GenerationEngine(self.model, prompt_buckets=[8],
                                   batch_size=1, continuous=True,
                                   name="cb-starve")
            try:
                eng.warmup()
                eng.breaker = _AlwaysOpen()
                fut = eng.submit(np.arange(3) % 97, 4)
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    if eng.stats()["starved_steps_after_warm"] > 8:
                        break
                    time.sleep(0.02)
                self.assertGreater(
                    eng.stats()["starved_steps_after_warm"], 8)
                time.sleep(0.25)  # let a publish tick carry the gauges
                self.assertGreaterEqual(eng.stats()["queue_depth"], 1)
                diags = [d for d in mon.diagnostics() if d.rule == "S603"]
                self.assertTrue(diags, mon.diagnostics())
            finally:
                eng.close(drain=False, timeout=10)
            self.assertIsInstance(fut.exception(timeout=5),
                                  UnavailableError)


if __name__ == "__main__":
    unittest.main()
