"""paddle_tpu.serving — bucketed dynamic batching + KV-cache generation.

Covers the serving contract end to end: bucket routing/padding, the
CLOSED compile set under mixed live traffic (the whole point of the
subsystem), token-identical KV-cache decode vs the uncached forward,
robustness (deadlines, load shedding, graceful drain, runner-failure
isolation), hot weight-swap with zero recompiles, metrics on the
trace_events bus, and the S601 bucket-miss analysis rule.
"""
import os
import tempfile
import threading
import time
import unittest

import numpy as np

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.analysis import RetraceMonitor
from paddle_tpu.framework.errors import (
    ExecutionTimeoutError,
    InvalidArgumentError,
    UnavailableError,
)
from paddle_tpu.serving import (
    Bucket,
    BucketSet,
    GenerationEngine,
    InferenceEngine,
    MicroBatcher,
    as_bucket,
)


class TestBucketing(unittest.TestCase):
    def test_as_bucket_shorthand(self):
        self.assertEqual(as_bucket((64,)).shapes, ((64,),))
        self.assertEqual(as_bucket(((64, 8), (64,))).shapes, ((64, 8), (64,)))
        b = Bucket(((16,),), batch_size=32)
        self.assertIs(as_bucket(b), b)
        with self.assertRaises(InvalidArgumentError):
            as_bucket("nope")
        with self.assertRaises(InvalidArgumentError):
            Bucket(((0,),))

    def test_route_smallest_fit(self):
        bs = BucketSet([(64,), (16,), (256,)])
        self.assertEqual(bs.route(((10,),)), 1)   # 16 is the smallest fit
        self.assertEqual(bs.route(((16,),)), 1)
        self.assertEqual(bs.route(((17,),)), 0)   # next up: 64
        self.assertEqual(bs.route(((200,),)), 2)
        self.assertEqual(bs.route(((300,),)), -1)  # miss
        self.assertEqual(bs.route(((10, 2),)), -1)  # rank mismatch = miss

    def test_pad_request(self):
        bs = BucketSet([((8, 4),)], pad_value=7)
        out = bs.pad_request(0, [np.ones((3, 4), np.float32)])
        self.assertEqual(out[0].shape, (8, 4))
        np.testing.assert_array_equal(out[0][:3], 1.0)
        np.testing.assert_array_equal(out[0][3:], 7.0)


class TestMicroBatcher(unittest.TestCase):
    def _echo_batcher(self, **kw):
        # router: bucket by first-input length; runner: echo batch size
        return MicroBatcher(
            lambda ins: len(ins[0]),
            lambda bucket, reqs: [(bucket, len(reqs))] * len(reqs), **kw)

    def test_groups_same_bucket(self):
        with self._echo_batcher(max_batch_size=4,
                                max_queue_delay_ms=60.0) as mb:
            futs = [mb.submit(([0, 0],)) for _ in range(4)]
            self.assertEqual({f.result(10) for f in futs}, {(2, 4)})

    def test_delay_flushes_partial_batch(self):
        with self._echo_batcher(max_batch_size=64,
                                max_queue_delay_ms=10.0) as mb:
            self.assertEqual(mb.submit(([0],)).result(10), (1, 1))

    def test_deadline_expires_queued_request(self):
        release = threading.Event()

        def slow_runner(bucket, reqs):
            release.wait(10)
            return [None] * len(reqs)

        mb = MicroBatcher(lambda ins: 0, slow_runner,
                          max_batch_size=1, max_queue_delay_ms=0.0)
        try:
            blocker = mb.submit((np.zeros(1),))        # occupies the worker
            doomed = mb.submit((np.zeros(1),), deadline_ms=1.0)
            time.sleep(0.05)
            release.set()
            blocker.result(10)
            with self.assertRaises(ExecutionTimeoutError):
                doomed.result(10)
        finally:
            release.set()
            mb.close()

    def test_load_shedding(self):
        started, release = threading.Event(), threading.Event()

        def slow_runner(bucket, reqs):
            started.set()
            release.wait(10)
            return [None] * len(reqs)

        mb = MicroBatcher(lambda ins: 0, slow_runner,
                          max_batch_size=1, max_queue_delay_ms=0.0,
                          max_queue_depth=2)
        try:
            futs = [mb.submit((np.zeros(1),))]
            self.assertTrue(started.wait(10))  # worker is now busy
            futs += [mb.submit((np.zeros(1),)) for _ in range(2)]
            with self.assertRaises(UnavailableError):  # depth at limit
                mb.submit((np.zeros(1),))
            self.assertGreaterEqual(mb.metrics.snapshot()["shed"], 1)
            release.set()
            for f in futs:
                f.result(10)
        finally:
            release.set()
            mb.close()

    def test_runner_exception_fails_batch_not_worker(self):
        calls = []

        def runner(bucket, reqs):
            calls.append(bucket)
            if bucket == 13:
                raise RuntimeError("boom")
            return [bucket] * len(reqs)

        with MicroBatcher(lambda ins: len(ins[0]), runner,
                          max_batch_size=1, max_queue_delay_ms=0.0) as mb:
            bad = mb.submit(([0] * 13,))
            with self.assertRaises(RuntimeError):
                bad.result(10)
            self.assertEqual(mb.submit(([0],)).result(10), 1)  # still alive

    def test_graceful_drain_and_closed_submit(self):
        mb = self._echo_batcher(max_batch_size=2, max_queue_delay_ms=1.0)
        futs = [mb.submit(([0],)) for _ in range(5)]
        mb.close(drain=True, timeout=10)
        for f in futs:
            self.assertIsNotNone(f.result(0))  # all served before join
        with self.assertRaises(UnavailableError):
            mb.submit(([0],))


class _TinyNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(8, 4)

    def forward(self, x):
        return self.fc(x)


def _export_tiny(tmpdir, name="m", seed=None):
    if seed is not None:
        pt.seed(seed)
    net = _TinyNet()
    prefix = os.path.join(tmpdir, name)
    pt.inference.save_inference_model(
        prefix, net, [pt.static.InputSpec([None, None, 8], "float32")])
    return prefix, net


class TestInferenceEngine(unittest.TestCase):
    @classmethod
    def setUpClass(cls):
        cls.tmp = tempfile.TemporaryDirectory()
        cls.prefix, cls.net = _export_tiny(cls.tmp.name, seed=1234)

    @classmethod
    def tearDownClass(cls):
        cls.tmp.cleanup()

    def _engine(self, **kw):
        kw.setdefault("max_batch_size", 4)
        kw.setdefault("max_queue_delay_ms", 2.0)
        return InferenceEngine(
            self.prefix, [Bucket(((4, 8),)), Bucket(((16, 8),))], **kw)

    def test_closed_compile_set_under_mixed_traffic(self):
        with self._engine() as eng:
            self.assertEqual(eng.warmup(), 2)  # one executable per bucket
            futs = [eng.submit([np.random.randn(n, 8).astype("float32")])
                    for n in (1, 3, 4, 2, 9, 16, 3, 11)]
            for f in futs:
                f.result(60)
            # mixed request shapes never minted a third executable
            self.assertEqual(eng.compile_count, 2)
            st = eng.stats()
            self.assertEqual(st["completed"], 8)
            self.assertEqual(st["bucket_misses"], 0)

    def test_outputs_match_direct_predictor_and_unpad(self):
        with self._engine() as eng:
            x = np.random.randn(3, 8).astype("float32")
            got = eng.infer([x], timeout=60)[0]
            want = np.asarray(self.net(x[None]))[0]
            self.assertEqual(got.shape, (3, 4))  # padding sliced back off
            np.testing.assert_allclose(got, want, atol=1e-5)

    def test_bucket_miss_rejected_or_fallback(self):
        with self._engine() as eng:
            with self.assertRaises(InvalidArgumentError):
                eng.infer([np.zeros((20, 8), np.float32)], timeout=60)
            self.assertEqual(eng.stats()["bucket_misses"], 1)
        with self._engine(allow_bucket_fallback=True) as eng:
            x = np.random.randn(20, 8).astype("float32")
            got = eng.infer([x], timeout=60)[0]
            np.testing.assert_allclose(
                got, np.asarray(self.net(x[None]))[0], atol=1e-5)
            st = eng.stats()
            self.assertEqual(st["bucket_misses"], 1)
            self.assertEqual(st["fallback_runs"], 1)

    def test_hot_weight_swap_zero_recompiles(self):
        prefix2, net2 = _export_tiny(self.tmp.name, "m2", seed=5678)
        with self._engine() as eng:
            eng.warmup()
            x = np.random.randn(3, 8).astype("float32")
            before = eng.infer([x], timeout=60)[0]
            eng.swap_weights(prefix2 + ".pdiparams")
            after = eng.infer([x], timeout=60)[0]
            self.assertEqual(eng.compile_count, 2)  # swap compiled nothing
            np.testing.assert_allclose(
                after, np.asarray(net2(x[None]))[0], atol=1e-5)
            self.assertFalse(np.allclose(after, before, atol=1e-5))

    def test_swap_rejects_mismatched_state(self):
        bad = os.path.join(self.tmp.name, "bad.pdiparams")
        other = nn.Linear(3, 3)
        pt.save({"params": other.param_pytree(),
                 "buffers": other.buffer_pytree()}, bad)
        with self._engine() as eng:
            with self.assertRaises(InvalidArgumentError):
                eng.swap_weights(bad)

    def test_metrics_published_on_bus(self):
        with RetraceMonitor(budget=8) as mon, self._engine() as eng:
            eng.infer([np.zeros((2, 8), np.float32)], timeout=60)
            stats = mon.serving_stats(eng.name)
            self.assertEqual(stats["completed"], 1)
            self.assertGreater(stats["p50_ms"], 0.0)
            self.assertIn("batch_occupancy", stats)

    def test_s601_bucket_miss_churn(self):
        with RetraceMonitor(budget=2) as mon, self._engine() as eng:
            for _ in range(4):  # 4 misses > budget 2
                with self.assertRaises(InvalidArgumentError):
                    eng.infer([np.zeros((99, 8), np.float32)], timeout=60)
            diags = mon.diagnostics()
        s601 = [d for d in diags if d.rule == "S601"]
        self.assertEqual(len(s601), 1)
        self.assertIn("4 bucket misses", s601[0].message)
        # under budget: silent
        with RetraceMonitor(budget=8) as mon, self._engine() as eng:
            with self.assertRaises(InvalidArgumentError):
                eng.infer([np.zeros((99, 8), np.float32)], timeout=60)
            self.assertEqual([d for d in mon.diagnostics()
                              if d.rule == "S601"], [])


class TestGenerationEngine(unittest.TestCase):
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

    def test_token_identical_and_closed_compile_set(self):
        # more requests than slots, all submitted at once; the scheduler
        # has its own suite (test_continuous_batching.py, which also holds
        # the EOS cases)
        import jax
        with GenerationEngine(self.model, prompt_buckets=[8, 16],
                              batch_size=2, max_queue_delay_ms=2.0) as eng:
            # 2 admission buckets + the verify step + its [B, 1] fast
            # trace + the page copy, and on a mesh of several devices the
            # fresh-pool trace of the step (warmup's docstring)
            closed = 5 + int(len(jax.devices()) > 1)
            self.assertEqual(eng.warmup(), closed)
            prompts = [np.arange(5) % 97, (np.arange(7) * 3) % 97,
                       (np.arange(11) * 5 + 2) % 97]
            futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
            gens = [f.result(120) for f in futs]
            for p, g in zip(prompts, gens):
                self.assertEqual(g.tolist(), self._ref_greedy(p, 5))
            # ragged prompts + many decode steps never reopened the set
            self.assertEqual(eng.compile_count, closed)
            st = eng.stats()
            self.assertEqual(st["tokens"], 15)
            self.assertGreater(st["tokens_per_s"], 0.0)

    def test_prompt_over_largest_bucket_is_a_miss(self):
        with GenerationEngine(self.model, prompt_buckets=[8],
                              batch_size=1) as eng:
            with self.assertRaises(InvalidArgumentError):
                eng.submit(np.zeros(9, np.int32))
            self.assertEqual(eng.stats()["bucket_misses"], 1)


if __name__ == "__main__":
    unittest.main()
