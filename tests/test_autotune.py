"""Kernel autotuner (ops/autotune.py): keys, caches, counters, and
per-candidate numerical equivalence of every registered kernel.

All on CPU — measured searches are forced with FLAGS_kernel_autotune=
"force" (interpret-mode timing is meaningless as a measurement but
exercises the full search/cache machinery); the off-TPU default path
must resolve to the untimed heuristic.
"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import trace_events
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops import autotune
from paddle_tpu.ops.flash_attention import (
    _fwd_tuned,
    _naive_reference,
    flash_attention,
)
from paddle_tpu.ops.fused_conv1x1_bn import _conv1x1_bn_stats
from paddle_tpu.ops.fused_layernorm import _ln_res_measured, layernorm_residual
from paddle_tpu.ops.fused_softmax_xent import (
    _sxent_measured,
    softmax_cross_entropy,
)


@pytest.fixture(autouse=True)
def _clean_tuner_state():
    """Each test starts cold (memory caches, counters, warm flag) and
    leaves the flags at their defaults."""
    autotune.clear_cache()
    autotune.reset_counters()
    autotune.reset_warm()
    yield
    set_flags({"kernel_autotune": "on", "kernel_tuning_cache": ""})
    autotune.clear_cache()
    autotune.reset_counters()
    autotune.reset_warm()


# one tiny registered kernel so cache/counter tests don't depend on the
# real kernels' spaces
_probe = autotune.autotune(
    "test_probe", params=("block",),
    space=lambda x: [{"block": 8}, {"block": 16}],
    heuristic=lambda x: {"block": 8},
)(lambda x, *, block: x * 2)


def _arr(*shape, dtype=np.float32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


class TestSpaceHelpers:
    def test_tile_candidates_clamped_to_padded_length(self):
        # dim 48: every base clamps to round_up(48, 8) = 48
        assert autotune.tile_candidates(48, base=(128, 256, 512)) == [48]
        # dim 300 with lane multiple: caps at round_up(300, 128) = 384
        cands = autotune.tile_candidates(300, multiple=128,
                                         base=(128, 256, 512, 1024))
        assert cands == [128, 256, 384]
        assert all(c % 128 == 0 for c in cands)

    def test_tile_candidates_rejects_bad_dim(self):
        from paddle_tpu.framework.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError):
            autotune.tile_candidates(0)

    def test_vmem_fits(self):
        assert autotune.vmem_fits(1024)
        assert not autotune.vmem_fits(autotune.VMEM_BYTES)


class TestCacheKey:
    def test_key_stable_and_shape_bucketed(self):
        a = _arr(100, 60)
        b = _arr(120, 64, seed=1)  # same pow2 buckets: (128, 64)
        assert _probe.cache_key(a) == _probe.cache_key(a)
        assert _probe.cache_key(a) == _probe.cache_key(b)
        c = _arr(200, 60)          # bucket (256, 64): distinct entry
        assert _probe.cache_key(a) != _probe.cache_key(c)

    def test_key_varies_with_dtype_and_kwargs(self):
        a32 = _arr(64, 64)
        a16 = _arr(64, 64).astype(jnp.bfloat16)
        assert _probe.cache_key(a32) != _probe.cache_key(a16)
        k1 = _fwd_tuned.cache_key(a32, a32, a32, causal=True, q_offset=0)
        k2 = _fwd_tuned.cache_key(a32, a32, a32, causal=False, q_offset=0)
        assert k1 != k2  # key_kwargs land in the key


class TestResolution:
    def test_off_tpu_defaults_to_heuristic_without_timing(self):
        assert jax.default_backend() != "tpu"
        cfg = _probe.config(_arr(32, 32))
        assert cfg == {"block": 8}
        c = autotune.get_counters("test_probe")
        assert c["heuristic"] == 1 and c["searches"] == 0
        assert c["configs_timed"] == 0
        # second resolution: heuristic-cache hit, still no timing
        _probe.config(_arr(32, 32))
        assert autotune.get_counters("test_probe")["hits"] == 1

    def test_force_mode_searches_and_memoizes(self):
        set_flags({"kernel_autotune": "force", "kernel_tuning_cache": "off"})
        x = _arr(32, 32)
        cfg = _probe.config(x)
        assert cfg in ({"block": 8}, {"block": 16})
        c = autotune.get_counters("test_probe")
        assert c["searches"] == 1 and c["configs_timed"] == 2
        _probe.config(x)
        assert autotune.get_counters("test_probe")["hits"] == 1

    def test_search_inside_a_jit_trace_measures_real_executions(
            self, monkeypatch):
        # models resolve configs while THEIR step is being traced; the
        # candidates must still compile and run for real, not be staged
        # into the outer trace (where "timing" reads tracing time)
        set_flags({"kernel_autotune": "force", "kernel_tuning_cache": "off"})
        seen = []
        real = autotune.measure_ms

        def spy(fn, args=(), repeats=3):
            seen.append(any(isinstance(a, jax.core.Tracer) for a in args))
            return real(fn, args, repeats)

        monkeypatch.setattr(autotune, "measure_ms", spy)

        @jax.jit
        def step(x):
            return _probe(x)

        np.testing.assert_allclose(np.asarray(step(_arr(32, 32))),
                                   np.asarray(_arr(32, 32) * 2))
        assert seen == [False, False]  # both candidates, concrete inputs

    def test_every_candidate_failing_raises_the_first_error(self):
        # an all-failed search is a broken kernel, not a tuning outcome:
        # it must not quietly hand back the heuristic
        set_flags({"kernel_autotune": "force", "kernel_tuning_cache": "off"})

        def boom(x, *, block):
            raise RuntimeError(f"refused block={block}")

        broken = autotune.TunedKernel(
            boom, "test_broken", ("block",),
            lambda x: [{"block": 8}, {"block": 16}],
            lambda x: {"block": 8})
        try:
            with pytest.raises(RuntimeError, match="refused block=8"):
                broken.config(_arr(32, 32))
            c = autotune.get_counters("test_broken")
            assert c["search_failures"] == 2 and c["searches"] == 0
        finally:
            autotune._REGISTRY.pop("test_broken", None)

    def test_off_mode_never_searches(self):
        set_flags({"kernel_autotune": "off"})
        assert _probe.config(_arr(32, 32)) == {"block": 8}
        assert autotune.get_counters("test_probe")["searches"] == 0

    def test_explicit_override_bypasses_resolution(self):
        out = _probe(_arr(8, 8), block=16)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_arr(8, 8) * 2))
        assert autotune.get_counters("test_probe") == {
            k: 0 for k in autotune._COUNTER_KEYS}

    def test_disk_round_trip(self, tmp_path):
        path = str(tmp_path / "tuning.json")
        set_flags({"kernel_autotune": "force", "kernel_tuning_cache": path})
        x = _arr(64, 16)
        won = _probe.config(x)
        assert autotune.get_counters("test_probe")["searches"] == 1
        data = json.load(open(path))
        assert len(data["entries"]) == 1
        (entry,) = data["entries"].values()
        assert entry["kernel"] == "test_probe" and entry["config"] == won
        # a "restarted process": memory gone, disk stays
        autotune.clear_cache(memory=True, disk=False)
        autotune.reset_counters()
        assert _probe.config(x) == won
        c = autotune.get_counters("test_probe")
        assert c["disk_hits"] == 1 and c["searches"] == 0

    def test_cache_path_flag_forms(self, tmp_path):
        set_flags({"kernel_tuning_cache": "off"})
        assert autotune.cache_path() is None
        set_flags({"kernel_tuning_cache": str(tmp_path / "t.json")})
        assert autotune.cache_path() == str(tmp_path / "t.json")
        set_flags({"kernel_tuning_cache": ""})
        from paddle_tpu import sysconfig
        assert autotune.cache_path() == os.path.join(
            sysconfig.cache_root(), "kernel_tuning.json")
        assert sysconfig.kernel_tuning_cache_path() == autotune.cache_path()

    def test_events_published(self):
        seen = []
        cb = lambda site, info: seen.append((tuple(site), dict(info)))  # noqa: E731
        trace_events.register(cb)
        try:
            set_flags({"kernel_autotune": "force",
                       "kernel_tuning_cache": "off"})
            _probe.config(_arr(16, 16))
            _probe.config(_arr(16, 16))
        finally:
            trace_events.unregister(cb)
        kinds = [info["event"] for site, info in seen
                 if site == ("autotune", "test_probe")]
        assert kinds == ["search", "hit"]
        search_info = seen[0][1]
        assert search_info["n_timed"] == 2
        assert search_info["counters"]["searches"] == 1


class TestCandidateEquivalence:
    """Every candidate the space generates must compute the same values
    as the lax reference — a fast winner that changes numerics is a bug
    the tuner must never be able to pick."""

    def test_conv1x1_bn_stats_all_candidates(self):
        x, w = _arr(100, 24), _arr(24, 40, seed=1)
        ref_y = np.asarray(x) @ np.asarray(w)
        from paddle_tpu.ops.fused_conv1x1_bn import conv1x1_bn_stats
        cands = _conv1x1_bn_stats.candidates(x, w)
        assert len(cands) >= 2
        for cfg in cands:
            y, s, q = conv1x1_bn_stats(x, w, **cfg)
            np.testing.assert_allclose(np.asarray(y), ref_y,
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(s), ref_y.sum(0),
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(np.asarray(q), (ref_y ** 2).sum(0),
                                       rtol=1e-4, atol=1e-4)

    def test_layernorm_residual_all_candidates(self):
        from paddle_tpu.nn import functional as F
        x, r = _arr(52, 48), _arr(52, 48, seed=1)
        g = _arr(48, seed=2)
        b = _arr(48, seed=3)
        ref_s = np.asarray(x + r)
        ref_y = np.asarray(F.layer_norm(x + r, (48,), g, b, 1e-5))
        for cfg in _ln_res_measured.candidates(x, r, g, b, epsilon=1e-5):
            s, y = layernorm_residual(x, r, g, b, **cfg)
            np.testing.assert_allclose(np.asarray(s), ref_s,
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(np.asarray(y), ref_y,
                                       rtol=1e-5, atol=1e-5)

    def test_softmax_xent_all_candidates(self):
        logits = _arr(36, 200)
        labels = jnp.asarray(
            np.random.RandomState(1).randint(0, 200, 36), jnp.int32)
        ref = -np.take_along_axis(
            np.asarray(jax.nn.log_softmax(logits, -1)),
            np.asarray(labels)[:, None], 1)[:, 0]
        cands = _sxent_measured.candidates(logits, labels)
        assert len(cands) >= 2
        for cfg in cands:
            loss = softmax_cross_entropy(logits, labels, **cfg)
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
    def test_flash_fwd_all_candidates(self, causal):
        B, H, S, D = 1, 2, 136, 16
        q, k, v = (_arr(B, H, S, D, seed=i) for i in range(3))
        scale = 1.0 / math.sqrt(D)
        ref = np.asarray(_naive_reference(q, k, v, causal, scale))
        cands = _fwd_tuned.candidates(q, k, v, causal=causal,
                                      sm_scale=scale, q_offset=0, kv_len=S)
        assert len(cands) >= 2
        for cfg in cands:
            out = flash_attention(q, k, v, causal=causal, **cfg)
            np.testing.assert_allclose(np.asarray(out), ref,
                                       rtol=2e-5, atol=2e-5)

    def test_flash_grad_with_candidate_blocks(self):
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

    def test_tuned_call_equals_explicit_default(self):
        """The no-argument (tuned) call path must be bit-identical to the
        explicit pre-autotuner defaults on CPU (heuristic == old behavior)."""
        B, H, S, D = 1, 2, 96, 16
        q, k, v = (_arr(B, H, S, D, seed=i) for i in range(3))
        tuned = flash_attention(q, k, v, causal=True)
        explicit = flash_attention(q, k, v, causal=True,
                                   block_q=512, block_k=512)
        assert (np.asarray(tuned) == np.asarray(explicit)).all()


class TestServingHotPath:
    def test_k701_after_warmup_search(self):
        from paddle_tpu.analysis import RetraceMonitor
        set_flags({"kernel_autotune": "force", "kernel_tuning_cache": "off"})
        with RetraceMonitor() as mon:
            autotune.mark_warm()
            _probe.config(_arr(16, 48))  # cold key -> hot-path search
        stats = mon.autotune_stats("test_probe")
        assert stats["counters"]["searches_after_warm"] == 1
        assert stats["warm"] is True
        diags = mon.diagnostics()
        k701 = [d for d in diags if d.rule == "K701"]
        assert len(k701) == 1
        assert "test_probe" in k701[0].message

    def test_no_k701_before_warmup(self):
        from paddle_tpu.analysis import RetraceMonitor
        set_flags({"kernel_autotune": "force", "kernel_tuning_cache": "off"})
        with RetraceMonitor() as mon:
            _probe.config(_arr(16, 48))
        assert not [d for d in mon.diagnostics() if d.rule == "K701"]


class TestProfilerSection:
    def test_summary_section_renders_and_resets(self):
        from paddle_tpu import profiler
        profiler.reset_profiler()
        _probe.config(_arr(24, 24))  # heuristic resolution on CPU
        s = profiler.summary()
        assert "Measured search" in s and "test_probe" in s
        profiler.reset_profiler()
        assert profiler.summary() == ""  # deltas cleared with the rest
