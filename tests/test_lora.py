"""Batched multi-LoRA (paddle_tpu/lora/).

Covers the adapter-math contract: the batched ragged gather path must
match a dense-merged single-adapter reference (allclose — ``x@(W+AB)``
vs ``x@W + (x@A)@B`` associate differently); slot id ``-1`` must be
BITWISE the no-adapter model; export/load round-trips through the
sha256-manifested ``.pdlora`` artifact and rejects tampered bytes; and
adapter hot add/remove on a live engine edits only host-side buffer
leaves — zero recompiles.
"""
import os
import tempfile
import unittest

import numpy as np

import paddle_tpu as pt
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.lora import (LoraAdapter, export_adapter, load_adapter,
                             merge_adapter, random_adapter)
from paddle_tpu.lora.batched import (adapter_capacity, clear_slot,
                                     write_adapter)
from paddle_tpu.lora.runtime import adapter_scope
from paddle_tpu.nn.layer_base import functional_call
from paddle_tpu.serving import GenerationEngine


def _install(model, slot, adapter):
    """Write an adapter into the EAGER model's buffer boxes (the engine
    does the same edit on its snapshotted flat tree)."""
    import jax.numpy as jnp
    new = write_adapter(model.buffer_pytree(), slot, adapter)
    for name, box in model.named_buffers():
        if name in new:
            box.value = jnp.asarray(new[name])


def _tiny_model(capacity=2, rank=4):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    pt.seed(4321)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_position=64, dropout=0.0,
                    lora_capacity=capacity, lora_rank=rank)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestAdapterMath(unittest.TestCase):
    def test_batched_gather_matches_dense_merged_reference(self):
        # one adapter in slot 0; a [B=2] batch scoping ids [0, 0] must
        # match the SAME model with W + AB*scale folded in densely
        model = _tiny_model()
        adp = random_adapter(model, "a0", rank=3, alpha=6.0, seed=7)
        _install(model, 0, adp)
        ids = np.array([[3, 9, 27, 5], [11, 2, 40, 8]], np.int32)
        import jax.numpy as jnp
        with adapter_scope(np.array([0, 0], np.int32)):
            got = np.asarray(model(jnp.asarray(ids)))
        merged = merge_adapter(model, adp)
        ref = np.asarray(functional_call(model, merged, jnp.asarray(ids)))
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
        # and the adapter actually moved the logits
        base = np.asarray(model(jnp.asarray(ids)))
        self.assertGreater(float(np.abs(got - base).max()), 1e-6)

    def test_slot_minus_one_is_bitwise_base(self):
        # with a NONZERO adapter installed, a -1 row must be bitwise the
        # unscoped model's output — the where-combine selects base rows,
        # never recomputes them
        model = _tiny_model()
        adp = random_adapter(model, "a0", rank=4, seed=3)
        _install(model, 1, adp)
        ids = np.array([[3, 9, 27, 5], [11, 2, 40, 8]], np.int32)
        import jax.numpy as jnp
        base = np.asarray(model(jnp.asarray(ids)))
        with adapter_scope(np.array([-1, -1], np.int32)):
            dead = np.asarray(model(jnp.asarray(ids)))
        self.assertTrue(np.array_equal(base, dead))
        # mixed batch: row 0 adapted, row 1 base — row 1 stays bitwise
        with adapter_scope(np.array([1, -1], np.int32)):
            mixed = np.asarray(model(jnp.asarray(ids)))
        self.assertTrue(np.array_equal(base[1], mixed[1]))
        self.assertGreater(float(np.abs(mixed[0] - base[0]).max()), 1e-6)

    def test_write_adapter_validation(self):
        model = _tiny_model(capacity=2, rank=4)
        bufs = model.buffer_pytree()
        self.assertEqual(adapter_capacity(bufs), 2)
        # rank above the table rank is rejected
        big = random_adapter(model, "big", rank=8, seed=1)
        with self.assertRaises(InvalidArgumentError):
            write_adapter(bufs, 0, big)
        # slot out of range
        ok = random_adapter(model, "ok", rank=2, seed=1)
        with self.assertRaises(InvalidArgumentError):
            write_adapter(bufs, 5, ok)
        # unknown site
        bad = LoraAdapter("bad", 2, 2.0, {
            "gpt.nowhere.qkv": (np.zeros((32, 2), np.float32),
                                np.zeros((2, 96), np.float32))})
        with self.assertRaises(InvalidArgumentError):
            write_adapter(bufs, 0, bad)
        # sub-rank adapters zero-pad: delta equals the unpadded math
        new = write_adapter(bufs, 0, ok)
        site = next(iter(ok.sites))
        a_tab = np.asarray(new[site + ".lora_A"])
        self.assertEqual(a_tab.shape[2], 4)
        self.assertTrue(np.all(a_tab[0, :, 2:] == 0))
        # and the original tree was not mutated
        self.assertTrue(np.all(np.asarray(bufs[site + ".lora_A"]) == 0))
        cleared = clear_slot(new, 0)
        self.assertTrue(np.all(np.asarray(cleared[site + ".lora_A"]) == 0))


class TestAdapterArtifact(unittest.TestCase):
    def test_export_load_roundtrip(self):
        model = _tiny_model()
        adp = random_adapter(model, "ship-me", rank=3, alpha=5.0, seed=11)
        with tempfile.TemporaryDirectory() as d:
            path = export_adapter(adp, os.path.join(d, "adp"))
            self.assertTrue(path.endswith(".pdlora"))
            self.assertTrue(os.path.exists(path + ".manifest.json"))
            back = load_adapter(path)
        self.assertEqual(back.name, "ship-me")
        self.assertEqual(back.rank, 3)
        self.assertEqual(back.alpha, 5.0)
        self.assertEqual(set(back.sites), set(adp.sites))
        for s, (a, b) in adp.sites.items():
            self.assertTrue(np.array_equal(a, back.sites[s][0]))
            self.assertTrue(np.array_equal(b, back.sites[s][1]))

    def test_load_rejects_tampered_and_unmanifested(self):
        model = _tiny_model()
        adp = random_adapter(model, "tamper", rank=2, seed=5)
        with tempfile.TemporaryDirectory() as d:
            path = export_adapter(adp, os.path.join(d, "adp"))
            with open(path, "r+b") as f:
                f.seek(-1, os.SEEK_END)
                last = f.read(1)
                f.seek(-1, os.SEEK_END)
                f.write(bytes([last[0] ^ 0xFF]))
            with self.assertRaises(InvalidArgumentError):
                load_adapter(path)  # sha256 mismatch
            os.remove(path + ".manifest.json")
            with self.assertRaises(InvalidArgumentError):
                load_adapter(path)  # no manifest = unverifiable


class TestHotSwap(unittest.TestCase):
    def test_hot_add_remove_zero_recompile(self):
        # install/remove adapters on a LIVE paged engine between
        # generations: outputs change, the compile set does not
        model = _tiny_model(capacity=2, rank=4)
        p = (np.arange(6) * 9 + 4) % 97
        with GenerationEngine(model, prompt_buckets=[8], batch_size=2,
                              cache_len=48, kv_page_size=8,
                              name="lora-hot") as eng:
            n_tr = eng.warmup()
            base = eng.generate(p, 8, timeout=120).tolist()
            adp = random_adapter(model, "hot", rank=4, seed=9,
                                 alpha=32.0, std=0.2)
            eng.install_adapter(0, adp)
            self.assertEqual(eng.adapters, {0: "hot"})
            tuned = eng.generate(p, 8, timeout=120,
                                 adapter_id=0).tolist()
            # explicit -1 still serves the base model alongside
            self.assertEqual(
                eng.generate(p, 8, timeout=120, adapter_id=-1).tolist(),
                base)
            eng.remove_adapter(0)
            self.assertEqual(eng.adapters, {})
            # a cleared slot computes a zero delta -> base tokens
            self.assertEqual(
                eng.generate(p, 8, timeout=120, adapter_id=0).tolist(),
                base)
            self.assertEqual(eng.compile_count, n_tr)  # zero recompiles
            st = eng.stats()
            self.assertEqual(st["adapter_installs"], 1)
            self.assertEqual(st["adapter_removals"], 1)
        # the random adapter is strong enough to change greedy tokens at
        # least somewhere in the budget (seeded, deterministic)
        self.assertNotEqual(tuned, base)

    def test_submit_validates_adapter_id(self):
        model = _tiny_model(capacity=2)
        with GenerationEngine(model, prompt_buckets=[8], batch_size=2,
                              cache_len=48, kv_page_size=8,
                              name="lora-val") as eng:
            eng.warmup()
            with self.assertRaises(InvalidArgumentError):
                eng.submit(np.arange(4) % 97, 4, adapter_id=7)


if __name__ == "__main__":
    unittest.main()
