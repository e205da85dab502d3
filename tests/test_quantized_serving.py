"""Quantized serving path: KV page numerics, CoW on quantized pools,
hot-swap without recompiles, and the serving-space quantization dial.

The expensive end-to-end properties (margin-accounted token agreement,
equal-HBM resident slots, rolling swap across a router) live in
tools/quant_smoke.py; these are the cheap unit contracts underneath.
"""
import os
import tempfile

import numpy as np
import pytest

import jax
import jax.monitoring
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import slim
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM, _quantize_kv
from paddle_tpu.serving import GenerationEngine

CACHE, PAGE = 32, 8

_XLA_COMPILES = [0]
jax.monitoring.register_event_listener(
    lambda name, **kw: _XLA_COMPILES.__setitem__(0, _XLA_COMPILES[0] + 1)
    if name == "/jax/compilation_cache/compile_requests_use_cache" else None)


def _model(seed=3):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=53, hidden_size=32, num_layers=2,
                    num_heads=4, max_position=CACHE, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestQuantizedKVPages:
    def test_quantize_kv_roundtrip_bounds(self):
        rng = np.random.RandomState(0)
        t = jnp.asarray(rng.randn(6, 4, 8).astype(np.float32))
        amax = np.max(np.abs(np.asarray(t)), axis=-1)  # [N, H]
        for qdt, tol in ((jnp.int8, amax / 127 / 2 + 1e-6),
                         (jnp.float8_e4m3fn, amax * 0.0625)):
            q, s = _quantize_kv(t, qdt)
            assert q.dtype == jnp.dtype(qdt)
            assert s.shape == (6, 4) and s.dtype == jnp.float32
            recon = np.asarray(q, np.float32) * np.asarray(s)[..., None]
            err = np.max(np.abs(recon - np.asarray(t)), axis=-1)
            assert (err <= tol).all()

    def test_fp8_overflow_clips_not_nan(self):
        # e4m3fn has no inf: an unclipped cast of the abs-max element
        # would round up past 448 and land on NaN
        q, s = _quantize_kv(jnp.full((1, 1, 4), 1e4, jnp.float32),
                            jnp.float8_e4m3fn)
        assert np.isfinite(np.asarray(q, np.float32)).all()

    def test_pool_gather_scatter_preserves_bits(self):
        # hand-off contract: quantized pages move pool→pool without a
        # float round-trip — the adopting pool stores the same bits
        gpt = _model().gpt
        rng = np.random.RandomState(1)
        pool_a = gpt.init_paged_cache(4, PAGE, dtype=jnp.int8)
        kv = jnp.asarray(rng.randn(PAGE, 4, 8).astype(np.float32))
        q, s = _quantize_kv(kv, jnp.int8)
        # the stored order: a page is PAGE token rows of H*hd values,
        # its scale plane [PAGE, H] is indexed the same way
        assert pool_a["layers"][0]["k"].shape == (5, PAGE, 4 * 8)
        assert pool_a["layers"][0]["k_scale"].shape == (5, PAGE, 4)
        rows = q.reshape(PAGE, 4 * 8)
        layers = []
        for l in pool_a["layers"]:
            layers.append({
                "k": l["k"].at[1].set(rows),
                "v": l["v"].at[1].set(rows),
                "k_scale": l["k_scale"].at[1].set(s),
                "v_scale": l["v_scale"].at[1].set(s),
            })
        pool_a = {"layers": layers}
        exported = gpt.gather_pages(pool_a, jnp.asarray([1], jnp.int32))
        assert isinstance(exported, tuple)  # (pages, scales) pair
        pages, scales = exported
        assert pages.dtype == jnp.int8 and scales.dtype == jnp.float32
        assert pages.shape == (2, 2, 1, PAGE, 4 * 8)
        assert scales.shape == (2, 2, 1, PAGE, 4)
        pool_b = gpt.init_paged_cache(4, PAGE, dtype=jnp.int8)
        pool_b = gpt.scatter_pages(pool_b, exported,
                                   jnp.asarray([2], jnp.int32))
        re_pages, re_scales = gpt.gather_pages(
            pool_b, jnp.asarray([2], jnp.int32))
        np.testing.assert_array_equal(np.asarray(re_pages),
                                      np.asarray(pages))
        np.testing.assert_array_equal(np.asarray(re_scales),
                                      np.asarray(scales))

    def test_scatter_quantized_pool_requires_scales(self):
        gpt = _model().gpt
        pool = gpt.init_paged_cache(4, PAGE, dtype=jnp.int8)
        bare = jnp.zeros((2, 2, 1, PAGE, 4 * 8), jnp.int8)
        with pytest.raises(ValueError):
            gpt.scatter_pages(pool, bare, jnp.asarray([0], jnp.int32))

    def test_copy_pages_covers_scale_planes(self):
        # CoW on a quantized pool: the page copy must move k/v AND their
        # scale planes, or the copied page dequantizes with zero scales
        gpt = _model().gpt
        pool = gpt.init_paged_cache(4, PAGE, dtype=jnp.int8)
        l0 = pool["layers"][0]
        l0 = dict(l0, k=l0["k"].at[0].set(7),
                  k_scale=l0["k_scale"].at[0].set(0.5))
        pool = {"layers": [l0] + pool["layers"][1:]}
        out = gpt.copy_pages(pool, jnp.asarray([0], jnp.int32),
                             jnp.asarray([3], jnp.int32))
        ol0 = out["layers"][0]
        np.testing.assert_array_equal(np.asarray(ol0["k"][3]),
                                      np.asarray(l0["k"][0]))
        np.testing.assert_array_equal(np.asarray(ol0["k_scale"][3]),
                                      np.asarray(l0["k_scale"][0]))


class TestQuantizedEngine:
    def test_bad_mode_rejected(self):
        from paddle_tpu.framework.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError):
            GenerationEngine(_model(), prompt_buckets=[16], batch_size=2,
                             cache_len=CACHE, quantized="int4")

    def test_serving_space_has_quantization_dial(self):
        from paddle_tpu.tuning.serving_space import DIAL_SWEEPS
        assert DIAL_SWEEPS["quantization"] == ("none", "int8", "fp8")

    def test_hot_swap_zero_recompile(self, tmp_path):
        # swap_weights with an export_quantized artifact: outputs change,
        # XLA compiles nothing (same tree, same per-leaf shape/dtype)
        donor = _model(seed=11)
        artifact = slim.export_quantized(
            donor, os.path.join(str(tmp_path), "donor"), mode="int8")
        prompt = np.arange(1, 9, dtype=np.int32)
        with GenerationEngine(_model(), prompt_buckets=[16], batch_size=2,
                              cache_len=CACHE,
                              speculative_k=0, quantized="int8",
                              name="tq-swap") as eng:
            eng.warmup()
            before = eng.submit(prompt, 4).result(60).tolist()
            x0 = _XLA_COMPILES[0]
            eng.swap_weights(artifact)
            after = eng.submit(prompt, 4).result(60).tolist()
            assert _XLA_COMPILES[0] - x0 == 0
            assert before != after  # donor weights actually serving
            assert eng.stats()["quantization"] == "int8"

    def test_swap_rejects_mode_mismatch(self, tmp_path):
        from paddle_tpu.framework.errors import InvalidArgumentError
        donor = _model(seed=11)
        artifact = slim.export_quantized(
            donor, os.path.join(str(tmp_path), "donor8"), mode="fp8")
        with GenerationEngine(_model(), prompt_buckets=[16], batch_size=2,
                              cache_len=CACHE,
                              speculative_k=0, quantized="int8",
                              name="tq-mismatch") as eng:
            with pytest.raises(InvalidArgumentError):
                eng.swap_weights(artifact)


class TestQuantizedMatmulKernel:
    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    def test_all_candidates_match_dequant_reference(self, mode):
        # acceptance gate: every tile computes the same answer as
        # dequantize-then-matmul (fwd; inference path)
        from paddle_tpu.ops.quantized_matmul import (_qmm_pallas,
                                                     quantize_activations)
        from paddle_tpu.slim.quantization import _quantize_weight

        M, K, N = 256, 64, 256
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(M, K).astype(np.float32))
        w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1)
        bias = jnp.asarray(rng.randn(N).astype(np.float32) * 0.01)
        xq, x_scale = quantize_activations(x, mode)
        wq, w_scale = _quantize_weight(w, mode)
        scale = (x_scale * w_scale).astype(jnp.float32)  # folded epilogue
        ref = (np.asarray(xq, np.float32) @ np.asarray(wq, np.float32)
               ) * np.asarray(scale) + np.asarray(bias)

        for cfg in ((bm, bn) for bm in (64, 128, 256) for bn in (128, 256)):
            out = np.asarray(_qmm_pallas(xq, wq, scale, bias, *cfg))
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5,
                                       err_msg=str(cfg))
