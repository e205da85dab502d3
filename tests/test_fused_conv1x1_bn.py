"""Fused 1x1-conv + BN-stats Pallas kernel (ops/fused_conv1x1_bn.py).

Numerics vs the unfused XLA reference on the CPU interpreter-backed
pallas path; the performance question (does removing one pass over Y pay
on the bandwidth-bound 1x1 layers?) is answered on the real chip by
tools/resnet_epilogue_probe.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.fused_conv1x1_bn import (bn_apply_relu, conv1x1_bn_relu,
                                             conv1x1_bn_stats)


def _ref_stats(x, w):
    y = x.astype(np.float32) @ w.astype(np.float32)
    return y, y.sum(0), (y * y).sum(0)


class TestConv1x1BnStats:
    @pytest.mark.parametrize("M,K,N", [(512, 256, 64), (1000, 64, 256),
                                       (256, 2048, 512), (77, 128, 100)])
    def test_matches_reference(self, M, K, N):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(M, K).astype(np.float32))
        w = jnp.asarray(rng.randn(K, N).astype(np.float32))
        y, s, q = conv1x1_bn_stats(x, w)
        ry, rs, rq = _ref_stats(np.asarray(x), np.asarray(w))
        # f32 accumulation-order differences grow with K (the dot and the
        # scratch accumulate in different orders than numpy)
        np.testing.assert_allclose(np.asarray(y), ry, rtol=1e-5,
                                   atol=1e-3 * np.sqrt(K / 64))
        np.testing.assert_allclose(np.asarray(s), rs, rtol=1e-5,
                                   atol=0.05 * np.sqrt(M * K / 1e4))
        np.testing.assert_allclose(np.asarray(q), rq, rtol=1e-5,
                                   atol=1.0 * M * K / 1e4)

    def test_bf16_inputs_f32_stats(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(384, 128), jnp.bfloat16)
        w = jnp.asarray(rng.randn(128, 256), jnp.bfloat16)
        y, s, q = conv1x1_bn_stats(x, w)
        assert y.dtype == jnp.bfloat16
        assert s.dtype == jnp.float32 and q.dtype == jnp.float32
        ry = np.asarray(x, np.float32) @ np.asarray(w, np.float32)
        np.testing.assert_allclose(np.asarray(y, np.float32), ry,
                                   rtol=2e-2, atol=2e-1)
        # stats accumulate the bf16-rounded MXU output in f32
        np.testing.assert_allclose(np.asarray(s),
                                   np.asarray(y, np.float32).sum(0),
                                   rtol=1e-3, atol=2.0)


class TestConv1x1BnRelu:
    def test_matches_unfused_train_bn(self):
        rng = np.random.RandomState(2)
        M, K, N = 512, 64, 128
        x = jnp.asarray(rng.randn(M, K).astype(np.float32))
        w = jnp.asarray(rng.randn(K, N).astype(np.float32))
        gamma = jnp.asarray(rng.rand(N).astype(np.float32) + 0.5)
        beta = jnp.asarray(rng.randn(N).astype(np.float32))
        res = jnp.asarray(rng.randn(M, N).astype(np.float32))
        rm = jnp.zeros((N,), jnp.float32)
        rv = jnp.ones((N,), jnp.float32)

        out, nrm, nrv = conv1x1_bn_relu(x, w, gamma, beta, residual=res,
                                        running_mean=rm, running_var=rv)

        y = np.asarray(x) @ np.asarray(w)
        mean, var = y.mean(0), y.var(0)
        want = (np.asarray(gamma) * (y - mean) / np.sqrt(var + 1e-5)
                + np.asarray(beta)) + np.asarray(res)
        want = np.maximum(want, 0.0)
        np.testing.assert_allclose(np.asarray(out), want,
                                   rtol=1e-4, atol=1e-4)
        unbiased = var * M / (M - 1)
        np.testing.assert_allclose(np.asarray(nrm), 0.1 * mean, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(nrv),
                                   0.9 * 1.0 + 0.1 * unbiased, rtol=1e-4)

    def test_padding_rows_do_not_skew_stats(self):
        # M=77 pads to a block multiple; padded zero rows must not enter
        # mean/var (they contribute zero to Σ and Σ² and M uses the true
        # row count)
        rng = np.random.RandomState(3)
        M, K, N = 77, 32, 48
        x = jnp.asarray(rng.randn(M, K).astype(np.float32))
        w = jnp.asarray(rng.randn(K, N).astype(np.float32))
        g = jnp.ones((N,), jnp.float32)
        b = jnp.zeros((N,), jnp.float32)
        out, _, _ = conv1x1_bn_relu(x, w, g, b)
        y = np.asarray(x) @ np.asarray(w)
        want = np.maximum((y - y.mean(0)) / np.sqrt(y.var(0) + 1e-5), 0.0)
        np.testing.assert_allclose(np.asarray(out), want,
                                   rtol=1e-4, atol=1e-4)


class TestBnApplyRelu:
    def test_all_candidates_match_unfused_tail(self):
        rng = np.random.RandomState(4)
        M, N = 200, 256
        y = jnp.asarray(rng.randn(M, N).astype(np.float32))
        scale = jnp.asarray(rng.rand(N).astype(np.float32) + 0.5)
        shift = jnp.asarray(rng.randn(N).astype(np.float32))
        res = jnp.asarray(rng.randn(M, N).astype(np.float32))
        want = np.maximum(np.asarray(y) * np.asarray(scale)
                          + np.asarray(shift) + np.asarray(res), 0.0)
        for bm, bn in ((64, 128), (200, 128), (200, 256), (512, 256)):
            out = bn_apply_relu(y, scale, shift, res, block_m=bm,
                                block_n=bn)
            np.testing.assert_allclose(np.asarray(out), want,
                                       rtol=1e-5, atol=1e-5)
        # no-residual leg
        out = bn_apply_relu(y, scale, shift)
        np.testing.assert_allclose(
            np.asarray(out),
            np.maximum(np.asarray(y) * np.asarray(scale)
                       + np.asarray(shift), 0.0),
            rtol=1e-5, atol=1e-5)

    def test_fused_epilogue_flag_is_value_preserving(self):
        rng = np.random.RandomState(5)
        M, K, N = 77, 32, 128  # ragged M exercises the padding path
        x = jnp.asarray(rng.randn(M, K).astype(np.float32))
        w = jnp.asarray(rng.randn(K, N).astype(np.float32))
        g = jnp.asarray(rng.rand(N).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(N).astype(np.float32))
        res = jnp.asarray(rng.randn(M, N).astype(np.float32))
        base, _, _ = conv1x1_bn_relu(x, w, g, b, residual=res)
        fused, _, _ = conv1x1_bn_relu(x, w, g, b, residual=res,
                                      fused_epilogue=True)
        np.testing.assert_allclose(np.asarray(base), np.asarray(fused),
                                   rtol=1e-5, atol=1e-5)

    def test_resnet_bottleneck_fused_tail_wiring(self):
        # the gate is TPU-only in production; forcing it open checks the
        # weight-layout/stat-update plumbing against the plain tail
        import paddle_tpu.nn as nn
        import paddle_tpu.ops.autotune as at
        from paddle_tpu.vision.models.resnet import BottleneckBlock

        blk = BottleneckBlock(
            256, 64, data_format="NHWC",
            norm_layer=lambda c: nn.BatchNorm2D(c, data_format="NHWC"))
        x = jnp.asarray(np.random.RandomState(6)
                        .randn(2, 8, 8, 256).astype(np.float32))
        assert blk._fused_tail(x, x) is None  # CPU: gate closed
        ref = blk(x)
        rm_ref = np.asarray(blk.bn3._mean.value)
        blk.bn3._mean.value = jnp.zeros_like(blk.bn3._mean.value)
        blk.bn3._variance.value = jnp.ones_like(blk.bn3._variance.value)
        orig = at.fused_epilogues_eligible
        at.fused_epilogues_eligible = lambda feature_dim=None: True
        try:
            fused = blk(x)
        finally:
            at.fused_epilogues_eligible = orig
        np.testing.assert_allclose(np.asarray(ref), np.asarray(fused),
                                   rtol=3e-5, atol=3e-5)
        # the fused tail updated bn3's running stats like the plain one
        np.testing.assert_allclose(np.asarray(blk.bn3._mean.value),
                                   rm_ref, rtol=1e-4, atol=1e-6)


def test_fused_tail_keeps_the_buffers_dtype():
    # net.astype("bfloat16") makes bn3's running stats bf16; the kernel's
    # stats are f32.  Handing them back as f32 changed the carry type of a
    # scan-chained train step (Executor.run_steps) — the ResNet-50 program
    # did not even trace on the chip.  conv1x1_bn_relu returns running
    # stats in the dtype they came in.
    import paddle_tpu.nn as nn
    import paddle_tpu.ops.autotune as at
    from paddle_tpu.vision.models.resnet import BottleneckBlock

    blk = BottleneckBlock(
        256, 64, data_format="NHWC",
        norm_layer=lambda c: nn.BatchNorm2D(c, data_format="NHWC"),
    ).astype("bfloat16")
    x = jnp.ones((2, 8, 8, 256), jnp.bfloat16)
    orig = at.fused_epilogues_eligible
    at.fused_epilogues_eligible = lambda feature_dim=None: True
    try:
        assert blk._fused_tail(x[..., :64], x) is not None
    finally:
        at.fused_epilogues_eligible = orig
    assert blk.bn3._mean.value.dtype == jnp.bfloat16
    assert blk.bn3._variance.value.dtype == jnp.bfloat16


class TestGradients:
    """The ResNet training step differentiates through both kernels;
    ``pallas_call`` has no transpose rule, so each carries a closed-form
    VJP — checked here against autodiff of the plain jnp formulation."""

    @pytest.mark.parametrize("fused_epilogue", [False, True])
    def test_grads_match_unfused_reference(self, fused_epilogue):
        rng = np.random.RandomState(7)
        M, K, N = 77, 32, 128  # ragged M: padded rows must not leak grads
        x = jnp.asarray(rng.randn(M, K).astype(np.float32))
        w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.2)
        g = jnp.asarray(rng.rand(N).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(N).astype(np.float32))
        res = jnp.asarray(rng.randn(M, N).astype(np.float32))
        cot = jnp.asarray(rng.randn(M, N).astype(np.float32))

        def fused(x, w, g, b, res):
            out, _, _ = conv1x1_bn_relu(x, w, g, b, residual=res,
                                        fused_epilogue=fused_epilogue)
            return (out * cot).sum()

        def plain(x, w, g, b, res):
            y = x @ w
            mean = y.mean(0)
            var = ((y - mean) ** 2).mean(0)
            out = g * (y - mean) * jax.lax.rsqrt(var + 1e-5) + b + res
            return (jnp.maximum(out, 0.0) * cot).sum()

        got = jax.grad(fused, argnums=(0, 1, 2, 3, 4))(x, w, g, b, res)
        want = jax.grad(plain, argnums=(0, 1, 2, 3, 4))(x, w, g, b, res)
        for name, a, e in zip("x w gamma beta residual".split(), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       rtol=2e-3, atol=2e-3, err_msg=name)

    def test_bn_apply_without_residual_is_differentiable(self):
        rng = np.random.RandomState(8)
        y = jnp.asarray(rng.randn(40, 128).astype(np.float32))
        sc = jnp.asarray(rng.rand(128).astype(np.float32) + 0.5)
        sh = jnp.asarray(rng.randn(128).astype(np.float32))
        got = jax.grad(lambda *a: bn_apply_relu(*a).sum(),
                       argnums=(0, 1, 2))(y, sc, sh)
        want = jax.grad(lambda y, a, b: jnp.maximum(y * a + b, 0.0).sum(),
                        argnums=(0, 1, 2))(y, sc, sh)
        for a, e in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       rtol=1e-5, atol=1e-5)
