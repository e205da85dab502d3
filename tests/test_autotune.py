"""What ``ops/autotune.py`` holds since PR 48 (Mosaic's constants, the
block clamp, no search and no registry) and the contract the kernels'
explicit ``block_*=`` keywords keep: every block computes the values of the
lax reference, a call without blocks is a call at the rule's blocks, and an
explicit block wins over the rule.

All on CPU (interpret mode).  The rules themselves are held to the cells'
shapes in ``tests/test_tile_rules.py``; the measured-search engine that the
plan and serving spaces still use is ``tests/test_tuning.py``'s.
"""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops import autotune
from paddle_tpu.ops.flash_attention import (
    _naive_reference,
    flash_attention,
    flash_blocks,
    flash_bwd_blocks,
)
from paddle_tpu.ops.fused_layernorm import layernorm_residual, ln_block
from paddle_tpu.ops.fused_softmax_xent import softmax_cross_entropy


def _arr(*shape, dtype=np.float32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


class TestModule:
    def test_vmem_fits(self):
        assert autotune.vmem_fits(1024)
        assert not autotune.vmem_fits(autotune.VMEM_BYTES)

    def test_clamp_tile(self):
        # a short dim never pays a full-width padded tile: 48 rows take one
        # block of round_up(48, 8); 300 columns cap at round_up(300, 128)
        assert autotune.clamp_tile(512, 48) == 48
        assert autotune.clamp_tile(512, 300, autotune.LANE) == 384
        assert autotune.clamp_tile(100, 4096) == 104       # whole sublanes
        assert autotune.clamp_tile(128, 3, 32) == 32       # at least a tile

    def test_no_registry_and_no_search(self):
        # PR 48: nothing is registered, raced or timed for a tile (the
        # checks that `paged_decode`, the delta-rule kernels and
        # `latent_decode` were not among the registered are this one now)
        for gone in ("_REGISTRY", "registered_kernels", "TunedKernel",
                     "autotune", "tile_candidates", "measure_ms"):
            assert not hasattr(autotune, gone), gone
        # what benchmarks/run.py reads still answers: the engine's own
        from paddle_tpu.tuning import engine
        assert autotune.cache_path is engine.cache_path
        assert autotune.get_counters is engine.get_counters

    def test_kernel_autotune_flag_is_gone(self):
        from paddle_tpu.framework.errors import NotFoundError
        with pytest.raises(NotFoundError, match="kernel_autotune"):
            set_flags({"kernel_autotune": "off"})


class TestBlockEquivalence:
    """Every block the explicit keywords admit must compute the same
    values as the lax reference — the chip tool behind a rule's table
    (``tools/tile_table_chip.py``) times them against each other, and a
    fast block that changed numerics would be a bug the table could pick."""

    def test_conv1x1_bn_stats_all_blocks(self):
        x, w = _arr(100, 24), _arr(24, 40, seed=1)
        ref_y = np.asarray(x) @ np.asarray(w)
        from paddle_tpu.ops.fused_conv1x1_bn import conv1x1_bn_stats
        for bm, bn in ((8, 128), (64, 128), (104, 128), (512, 256)):
            y, s, q = conv1x1_bn_stats(x, w, block_m=bm, block_n=bn)
            np.testing.assert_allclose(np.asarray(y), ref_y,
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(s), ref_y.sum(0),
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(np.asarray(q), (ref_y ** 2).sum(0),
                                       rtol=1e-4, atol=1e-4)

    def test_layernorm_residual_all_blocks(self):
        from paddle_tpu.nn import functional as F
        x, r = _arr(52, 48), _arr(52, 48, seed=1)
        g = _arr(48, seed=2)
        b = _arr(48, seed=3)
        ref_s = np.asarray(x + r)
        ref_y = np.asarray(F.layer_norm(x + r, (48,), g, b, 1e-5))
        for bm in (8, 16, 56, 512):
            s, y = layernorm_residual(x, r, g, b, block_m=bm)
            np.testing.assert_allclose(np.asarray(s), ref_s,
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(np.asarray(y), ref_y,
                                       rtol=1e-5, atol=1e-5)

    def test_softmax_xent_all_blocks(self):
        logits = _arr(36, 200)
        labels = jnp.asarray(
            np.random.RandomState(1).randint(0, 200, 36), jnp.int32)
        ref = -np.take_along_axis(
            np.asarray(jax.nn.log_softmax(logits, -1)),
            np.asarray(labels)[:, None], 1)[:, 0]
        for bm, bv in ((8, 128), (16, 256), (40, 256), (256, 2048)):
            loss = softmax_cross_entropy(logits, labels, block_m=bm,
                                         block_v=bv)
            np.testing.assert_allclose(np.asarray(loss), ref,
                                       rtol=1e-5, atol=1e-5)

    def test_softmax_xent_grad_matches_reference(self):
        logits = _arr(20, 130)
        labels = jnp.asarray(
            np.random.RandomState(1).randint(0, 130, 20), jnp.int32)

        def fused(lg):
            return softmax_cross_entropy(lg, labels, block_m=8,
                                         block_v=128).mean()

        def ref(lg):
            lp = jax.nn.log_softmax(lg, -1)
            return -jnp.take_along_axis(lp, labels[:, None], 1).mean()

        np.testing.assert_allclose(np.asarray(jax.grad(fused)(logits)),
                                   np.asarray(jax.grad(ref)(logits)),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_fwd_all_blocks(self, causal):
        B, H, S, D = 1, 2, 136, 16
        q, k, v = (_arr(B, H, S, D, seed=i) for i in range(3))
        scale = 1.0 / math.sqrt(D)
        ref = np.asarray(_naive_reference(q, k, v, causal, scale))
        # square blocks stay on the causal triangle grid; the rectangles
        # take the rectangular one
        for bq, bk in ((128, 128), (136, 136), (512, 512), (64, 128),
                       (136, 128)):
            out = flash_attention(q, k, v, causal=causal, block_q=bq,
                                  block_k=bk)
            np.testing.assert_allclose(np.asarray(out), ref,
                                       rtol=2e-5, atol=2e-5)

    def test_flash_grad_with_explicit_blocks(self):
        B, H, S, D = 1, 2, 64, 16
        q, k, v = (_arr(B, H, S, D, seed=i) for i in range(3))
        scale = 1.0 / math.sqrt(D)

        def fused(q, k, v):
            return (flash_attention(q, k, v, causal=True, block_q=48,
                                    block_k=48) ** 2).sum()

        def ref(q, k, v):
            return (_naive_reference(q, k, v, True, scale) ** 2).sum()

        for gf, gr in zip(jax.grad(fused, (0, 1, 2))(q, k, v),
                          jax.grad(ref, (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       rtol=2e-4, atol=2e-4)

    def test_call_without_blocks_equals_call_at_the_rules_blocks(self):
        """Forward and gradients: no ``block_*=`` is bit-identical to the
        rule's blocks passed explicitly (at 96 positions the forward's and
        the backward kernels' rules agree: one block)."""
        B, H, S, D = 1, 2, 96, 16
        q, k, v = (_arr(B, H, S, D, seed=i) for i in range(3))
        bq, bk = flash_blocks(S, S, D * 4)
        assert (bq, bk) == flash_bwd_blocks(S, S) == (96, 96)

        def loss(q, k, v, **blocks):
            return (flash_attention(q, k, v, causal=True, **blocks)
                    ** 2).sum()

        by_rule = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
        explicit = jax.value_and_grad(
            lambda *a: loss(*a, block_q=bq, block_k=bk), (0, 1, 2))(q, k, v)
        for a, b in zip(jax.tree_util.tree_leaves(by_rule),
                        jax.tree_util.tree_leaves(explicit)):
            assert (np.asarray(a) == np.asarray(b)).all()


def test_explicit_block_wins_over_the_rule(monkeypatch):
    mod = importlib.import_module("paddle_tpu.ops.fused_layernorm")
    ran, real = [], mod._ln_res_pallas
    monkeypatch.setattr(
        mod, "_ln_res_pallas",
        lambda x, r, g, b, eps, block_m: ran.append(block_m) or real(
            x, r, g, b, eps, block_m))
    x, g = _arr(72, 48), _arr(48, seed=1)
    layernorm_residual(x, x, g, g, block_m=16)
    layernorm_residual(x, x, g, g)
    assert ran == [16, ln_block(72)] and ln_block(72) != 16
