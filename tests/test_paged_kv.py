"""Paged KV cache + copy-on-write prefix sharing + speculative decoding
(serving/paging.py, serving/generation.py, models/gpt.py
``forward_paged``/``init_paged_cache``/``copy_pages``).

Covers the page pool's side of the scheduler's contract (staggered
admission and restart are in tests/test_continuous_batching.py): the
closed compile set (``len(prompt_buckets) + 3`` with speculation on —
the extra trace is the ``[B, 1]`` no-draft fast step — zero post-warmup
retraces); CoW isolation (a sibling's divergent write never perturbs a
shared prefix page); speculative accept/reject bit-identity vs plain
greedy and vs the uncached forward under a sliding-window mask
(including past the wrap point where drafting disables); pool-exhaustion
preemption; ``PagePool`` accounting invariants; the keywords and flags
of the removed schedulers; and analysis rule S604 (admission starved by
a page leak).
"""
import time
import unittest

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework.errors import (InvalidArgumentError, NotFoundError,
                                         UnavailableError)
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.serving import GenerationEngine, PagePool

#: one more executable wherever several devices make up the global mesh
#: (the suite's eight): a step's outputs carry the mesh's sharding, so the
#: host-built fresh state of warm-up is another abstract input and the
#: step traces once more for it (GenerationEngine.warmup's docstring)
FRESH_TRACE = int(len(jax.devices()) > 1)


class TestPagePool(unittest.TestCase):
    def test_alloc_release_refcounts(self):
        pool = PagePool(num_slots=2, num_pages=8, page_size=4, max_len=16)
        self.assertEqual(pool.free_pages, 8)
        prompt = np.arange(6, dtype=np.int32)  # 2 pages
        pairs, shared = pool.admit(0, prompt)
        self.assertEqual((pairs, shared), ([], 0))
        self.assertEqual(pool.free_pages, 6)
        self.assertEqual(pool.pos_map[0, 5], 5)
        self.assertEqual(pool.pos_map[0, 6], -1)
        pool.release(0)
        self.assertEqual(pool.free_pages, 8)
        self.assertTrue((pool.table[0] == -1).all())
        self.assertEqual(pool.leaked_pages(), 0)

    def test_prefix_sharing_and_cow(self):
        pool = PagePool(num_slots=3, num_pages=12, page_size=4, max_len=16)
        prompt = np.arange(10, dtype=np.int32)  # pages 0-1 full, page 2 part
        pool.admit(0, prompt)
        pool.register_prefix("sys", 0, prompt)
        base = pool.free_pages
        # sibling shares 2 full pages, CoWs the partial boundary page
        sib = np.concatenate([prompt, [50, 51]]).astype(np.int32)
        pairs, shared = pool.admit(1, sib, prefix_key="sys")
        self.assertEqual(shared, 10)
        self.assertEqual(len(pairs), 1)  # the boundary-page copy
        self.assertEqual(pool.pages_needed(sib, "sys"), 1)
        self.assertEqual(pool.free_pages, base - 1)
        self.assertGreaterEqual(pool.shared_pages, 2)
        # full shared pages are mapped, not copied
        self.assertEqual(pool.table[1, 0], pool.table[0, 0])
        self.assertEqual(pool.table[1, 1], pool.table[0, 1])
        self.assertNotEqual(pool.table[1, 2], pool.table[0, 2])
        # divergent-token prompt must NOT share, even with the key
        other = np.arange(10, dtype=np.int32)[::-1].copy()
        pairs, shared = pool.admit(2, other, prefix_key="sys")
        self.assertEqual((pairs, shared), ([], 0))
        # the registry holds a ref on the boundary page, so the donor's
        # own next write CoWs it — registered prefix data stays pristine
        # for siblings admitted later
        old = int(pool.table[0, 2])
        pr = pool.ensure_writable(0, 10)
        self.assertIsNotNone(pr)
        self.assertEqual(pr[0], old)
        self.assertNotEqual(int(pool.table[0, 2]), old)
        # but a write into a FULL shared page (ring wrap) does CoW
        pr = pool.ensure_writable(1, 16)  # wraps to slot 0, page 0 shared
        self.assertIsNotNone(pr)
        self.assertEqual(pr[0], pool.table[0, 0])
        self.assertNotEqual(pool.table[1, 0], pool.table[0, 0])
        # registry pins pages past every holder's release
        pool.release(0), pool.release(1), pool.release(2)
        self.assertEqual(pool.leaked_pages(), 0)
        self.assertLess(pool.free_pages, 12)
        pool.drop_prefix("sys")
        self.assertEqual(pool.free_pages, 12)

    def test_exhaustion_raises_and_rolls_back(self):
        pool = PagePool(num_slots=2, num_pages=4, page_size=4, max_len=16)
        pool.admit(0, np.arange(12, dtype=np.int32))  # 3 pages
        with self.assertRaises(MemoryError):
            pool.admit(1, np.arange(8, dtype=np.int32))  # needs 2, 1 free
        # failed admission rolled back completely
        self.assertTrue((pool.table[1] == -1).all())
        self.assertEqual(pool.free_pages, 1)
        self.assertEqual(pool.leaked_pages(), 0)

    def test_geometry_validation(self):
        with self.assertRaises(ValueError):
            PagePool(num_slots=1, num_pages=8, page_size=5, max_len=16)
        with self.assertRaises(ValueError):  # pool can't hold one slot
            PagePool(num_slots=1, num_pages=2, page_size=4, max_len=16)


class TestPagedGeneration(unittest.TestCase):
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

    def test_pool_is_stored_as_token_rows_of_all_heads(self):
        # ONE stored order: a layer's K (and V) is [P+1, page, H*hd], a
        # token's heads side by side in one row; scale planes of a
        # quantized pool are indexed the same way.  A direct forward
        # writes token t of slot 0 at (page, offset) as the row the
        # fused projection produced, before any head split
        import jax.numpy as jnp
        gpt, cfg = self.model.gpt, self.cfg
        pool = gpt.init_paged_cache(6, 8)
        for l in pool["layers"]:
            self.assertEqual(sorted(l), ["k", "v"])
            self.assertEqual(l["k"].shape, (7, 8, cfg.hidden_size))
            self.assertEqual(l["v"].shape, (7, 8, cfg.hidden_size))
        qpool = gpt.init_paged_cache(6, 8, dtype=jnp.int8)
        self.assertEqual(qpool["layers"][0]["k"].shape,
                         (7, 8, cfg.hidden_size))
        self.assertEqual(qpool["layers"][1]["v_scale"].shape,
                         (7, 8, cfg.num_heads))
        ids = np.asarray([[3, 14, 15, 9, 2, 6, 5, 35, 8, 0]], np.int32)
        T = ids.shape[1]
        pos = np.arange(T, dtype=np.int32)[None]
        pos_map = np.full((1, 16), -1, np.int32)
        pos_map[0, :T] = np.arange(T)
        table = np.asarray([[4, 1]], np.int32)  # logical page 0 -> 4, 1 -> 1
        _, new = gpt.forward_paged(ids, pos, pos_map, table, pool)
        blk = gpt.blocks[0]
        x = gpt.wte(jnp.asarray(ids)) + gpt.wpe(jnp.asarray(pos))
        qkv = np.asarray(blk.attn.qkv(blk.ln1(x)))[0]  # [T, 3D]
        D = cfg.hidden_size
        k0, v0 = np.asarray(new["layers"][0]["k"]), np.asarray(
            new["layers"][0]["v"])
        np.testing.assert_array_equal(k0[4], qkv[:8, D:2 * D])
        np.testing.assert_array_equal(k0[1, :2], qkv[8:, D:2 * D])
        np.testing.assert_array_equal(v0[4], qkv[:8, 2 * D:])
        untouched = [p for p in range(7) if p not in (4, 1)]
        self.assertFalse(k0[untouched].any() or k0[1, 2:].any())

    def test_handoff_payload_rides_in_the_stored_order(self):
        # a hand-off exported by one engine and adopted by another: the
        # payload is [L, 2, K, page, H*hd], pages exactly as the donor's
        # pool stores them (every row a token's K or V for all heads),
        # and the adopter reproduces the donor's tokens
        from paddle_tpu.serving import KVHandoff
        cfg = self.cfg
        prompts = [(np.arange(11) * 7 + 3) % 97, (np.arange(5) * 3 + 1) % 97]
        budgets = [6, 4]
        refs = [self._ref_greedy(p, b) for p, b in zip(prompts, budgets)]

        def eng(role, name):
            return GenerationEngine(self.model, prompt_buckets=[8, 16],
                                    batch_size=2, kv_page_size=8,
                                    speculative_k=0,
                                    role=role, name=name)

        with eng("prefill", "ho-pre") as pre, eng("decode", "ho-dec") as dec:
            pre.warmup()
            dec.warmup()
            for p, b, ref in zip(prompts, budgets, refs):
                p = p.astype(np.int32)
                h = pre.submit(p, b, handoff=True).result(120)
                self.assertIsInstance(h, KVHandoff)
                kv = np.asarray(h.kv)
                # widest bucket 16 / page 8 = 2 pages whatever the prompt
                self.assertEqual(kv.shape, (cfg.num_layers, 2, 2, 8,
                                            cfg.hidden_size))
                self.assertEqual((h.length, h.first_token),
                                 (len(p), ref[0]))
                rows = kv.reshape(cfg.num_layers, 2, 16, cfg.hidden_size)
                self.assertTrue(rows[:, :, :len(p)].any(axis=-1).all())
                # layer 0's K rows ARE the projection's rows, token-major
                gpt = self.model.gpt
                import jax.numpy as jnp
                x = (gpt.wte(jnp.asarray(p[None]))
                     + gpt.wpe(jnp.arange(len(p))[None]))
                qkv = np.asarray(gpt.blocks[0].attn.qkv(
                    gpt.blocks[0].ln1(x)))[0]
                D = cfg.hidden_size
                np.testing.assert_allclose(rows[0, 0, :len(p)],
                                           qkv[:, D:2 * D], rtol=1e-5,
                                           atol=1e-6)
                got = dec.submit(p, b, handoff=h).result(120)
                self.assertEqual(np.asarray(got).tolist(), ref)
            self.assertEqual(dec.metrics.snapshot()["handoffs_in"], 2)
            self.assertEqual(pre.metrics.snapshot()["handoffs_out"], 2)

    def test_cow_prefix_sharing_isolation(self):
        # four requests share a system prompt under one prefix_key; the
        # prefix prefills once, siblings CoW the boundary page, and
        # every completion must still match uncached greedy computed
        # WITHOUT any sharing — divergent writes never reach a shared
        # page
        sys_p = (np.arange(11) * 7 + 3) % 97
        prompts = [np.concatenate([sys_p, e]).astype(np.int32)
                   for e in ([5, 9, 2], [5, 9, 2, 44], [61], [30, 8])]
        budgets = [6, 5, 8, 7]
        refs = [self._ref_greedy(p, b) for p, b in zip(prompts, budgets)]
        with GenerationEngine(self.model, prompt_buckets=[16],
                              batch_size=2, cache_len=64,
                              kv_page_size=8, speculative_k=2,
                              name="pg-cow") as eng:
            eng.warmup()
            outs = []
            for p, b in zip(prompts, budgets):
                outs.append(eng.submit(p, b, prefix_key="sys",
                                       prefix_len=len(sys_p)))
            for o, ref in zip(outs, refs):
                self.assertEqual(o.result(120).tolist(), ref)
            st = eng.stats()
            # the boundary page was CoW'd for at least one sibling and
            # full prefix pages were actually mapped shared
            self.assertGreater(st["cow_copies"], 0)
            self.assertGreater(st["prefix_hits"], 0)
            self.assertEqual(st["kv_pages_leaked"], 0)
            # 1 admit + step + fast step + cow + the fresh-pool trace
            self.assertEqual(eng.compile_count, 4 + FRESH_TRACE)

    def _next_tokens(self, seq, window=None):
        """The uncached forward's argmax after every prefix of ``seq``,
        in one teacher-forced pass; with ``window``, under a causal mask
        banded to it: a query at position q sees keys q-window+1..q in
        every layer — sliding-window attention written without a cache.
        Greedy output ``out`` of ``prompt`` is exact iff it equals
        ``_next_tokens(prompt + out)[len(prompt) - 1:-1]``."""
        import jax.numpy as jnp
        S = len(seq)
        band = None
        if window is not None:
            q, k = np.arange(S)[:, None], np.arange(S)[None, :]
            band = jnp.asarray(np.where(
                k > q - window, 0.0,
                np.finfo(np.float32).min).astype(np.float32))
        logits = np.asarray(self.model(
            jnp.asarray([list(map(int, seq))], jnp.int32), band))[0]
        return np.argmax(logits, axis=-1).tolist()

    def test_speculative_bit_identity_and_ring_wrap(self):
        # repetitive continuations make the n-gram proposer hit; accepted
        # AND rejected drafts must leave tokens bit-identical to plain
        # greedy on the same engine AND to the uncached forward under the
        # sliding window — including past position C where drafting
        # disables and the window slides
        p = ((np.arange(6) * 9 + 4) % 97).tolist()
        C, n = 32, 45

        def eng(k, name):
            return GenerationEngine(self.model, prompt_buckets=[8],
                                    batch_size=2, cache_len=C,
                                    kv_page_size=8, speculative_k=k,
                                    name=name)

        with eng(3, "pg-spec") as spec, eng(0, "pg-spec-k0") as plain:
            spec.warmup()
            plain.warmup()
            out = spec.generate(p, n, timeout=120).tolist()
            self.assertEqual(plain.generate(p, n, timeout=120).tolist(), out)
            self.assertEqual(
                self._next_tokens(p + out, window=C)[len(p) - 1:-1], out)
            # the window only matters past C: the plain causal forward
            # gives the same tokens up to there and other ones after
            full = self._next_tokens(p + out)[len(p) - 1:-1]
            self.assertEqual(full[:C - len(p) + 1], out[:C - len(p) + 1])
            self.assertNotEqual(full, out)
            st = spec.stats()
            self.assertGreater(st["spec_drafted"], 0)
            self.assertGreaterEqual(st["spec_drafted"], st["spec_accepted"])
            # speculation paid off: fewer steps than tokens decoded
            self.assertLess(st["decode_steps"], n)
            self.assertEqual(plain.stats()["spec_drafted"], 0)

    def test_pool_exhaustion_preempts_and_recovers(self):
        # a pool too small for both requests' full decode: the newest
        # slot is preempted mid-flight, requeued, and regenerated —
        # outputs still exact
        pa = (np.arange(4) * 13 + 1) % 97
        pb = (np.arange(4) * 5 + 2) % 97
        refs = [self._ref_greedy(pa, 26), self._ref_greedy(pb, 26)]
        with GenerationEngine(self.model, prompt_buckets=[8], batch_size=2,
                              cache_len=32, kv_page_size=4,
                              kv_pages=9, speculative_k=0,
                              circuit_breaker=False,
                              name="pg-preempt") as eng:
            eng.warmup()
            fa = eng.submit(pa, 26)
            fb = eng.submit(pb, 26)
            self.assertEqual(fa.result(120).tolist(), refs[0])
            self.assertEqual(fb.result(120).tolist(), refs[1])
            st = eng.stats()
            self.assertGreaterEqual(st["preempted"], 1)
            self.assertEqual(st["kv_pages_leaked"], 0)
            self.assertEqual(st["kv_pages_free"], 9)

    def test_flag_and_mode_validation(self):
        # nothing selects a scheduler: the default-constructed engine runs
        # the paged loop, the vestigial keywords accept None and True
        p = np.arange(3) % 97
        for kw in ({}, {"paged": True, "continuous": True}):
            with GenerationEngine(self.model, prompt_buckets=[8],
                                  batch_size=1, name="pg-flag", **kw) as eng:
                self.assertEqual(eng._thread._target, eng._paged_loop)
                st = eng.stats()
                self.assertTrue(st["paged"] and st["continuous"])
                self.assertIn("kv_pages_free", st)
                self.assertEqual(eng.generate(p, 3, timeout=120).tolist(),
                                 self._ref_greedy(p, 3))
        with self.assertRaises(InvalidArgumentError):
            GenerationEngine(self.model, prompt_buckets=[8], batch_size=1,
                             paged=False, continuous=False, name="pg-bad")

    def test_s604_fires_on_page_leak(self):
        from paddle_tpu.analysis import RetraceMonitor
        with RetraceMonitor(budget=8) as mon:
            eng = GenerationEngine(self.model, prompt_buckets=[8],
                                   batch_size=1, cache_len=32,
                                   kv_page_size=8, name="pg-leak")
            try:
                eng.warmup()
                # inject a page leak: drain the free list with refcounts
                # held by no slot table and no prefix registry — exactly
                # the state a release/decref pairing bug produces
                pool = eng._pool
                while pool.alloc() is not None:
                    pass
                self.assertEqual(pool.free_pages, 0)
                self.assertGreater(pool.leaked_pages(), 0)
                fut = eng.submit(np.arange(3) % 97, 4)
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    if eng.stats()["starved_steps_after_warm"] > 8:
                        break
                    time.sleep(0.02)
                self.assertGreater(
                    eng.stats()["starved_steps_after_warm"], 8)
                time.sleep(0.25)  # let a publish tick carry the gauges
                diags = [d for d in mon.diagnostics() if d.rule == "S604"]
                self.assertTrue(diags, mon.diagnostics())
                self.assertIn("page leak", diags[0].message)
            finally:
                eng.close(drain=False, timeout=10)
            self.assertIsInstance(fut.exception(timeout=5),
                                  UnavailableError)


@pytest.mark.parametrize("engine_kw,extra", [
    ({"speculative_k": 0}, 2), ({"speculative_k": 2}, 3),
    ({"speculative_k": 0, "role": "prefill"}, 3)],
    ids=["k0", "k2", "k0_prefill_role"])
def test_warmup_returns_the_count_its_docstring_states(engine_kw, extra):
    # len(prompt_buckets) + 2 (per-bucket admission, the step, the page
    # copy), + 1 with speculation (the [B, 1] fast trace), + 1 for a
    # hand-off role, + 1 on a mesh of several devices (FRESH_TRACE)
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    pt.seed(4321)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=1, num_heads=4,
        max_position=32, dropout=0.0))
    buckets = [8, 16, 24]
    with GenerationEngine(model, prompt_buckets=buckets, batch_size=2,
                          kv_page_size=8, name="pg-count",
                          **engine_kw) as eng:
        assert eng.warmup() == len(buckets) + extra + FRESH_TRACE
        assert eng.compile_count == len(buckets) + extra + FRESH_TRACE
        assert eng.stats()["compile_count"] == eng.compile_count


@pytest.mark.parametrize("keyword", ["paged", "continuous"])
def test_removed_scheduler_keyword_is_refused(keyword):
    # the dense ring (paged=False) and run-to-completion
    # (continuous=False) schedulers are gone; asking for one says so
    # before the model is touched
    with pytest.raises(InvalidArgumentError, match="removed in PR 29"):
        GenerationEngine(None, prompt_buckets=[8], **{keyword: False})


@pytest.mark.parametrize("name", ["paged_kv", "continuous_batching"])
def test_removed_scheduler_flag_is_unknown(name):
    with pytest.raises(NotFoundError):
        set_flags({name: True})


if __name__ == "__main__":
    unittest.main()
