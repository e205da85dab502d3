"""Paged flash-decode kernel (ops/paged_attention.py).

Per-candidate numerical equivalence against the gather-then-attend
reference (the serving path's bit-identical CPU fallback) across float,
int8 and fp8-e4m3 pools, drop-page masking, ragged page counts and the
speculative ``1+k`` verify width — all on the CPU interpreter.  The
performance question lives on the real chip (``benchmarks/run.py``, the
``gpt2_small`` cells).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops.paged_attention import (_head_blocks, _paged_decode,
                                            paged_flash_decode,
                                            paged_flash_eligible)


def _ref_attend(q, k_pool, v_pool, tables, mask, k_scale=None, v_scale=None):
    """Gather-then-attend oracle: materialize each slot's logical cache
    from the pool (dequantizing in full, as the fallback path does), then
    plain masked softmax attention.  Fully-masked rows emit softmax over
    a uniform -1e30 row — garbage by construction — so callers compare
    valid rows only."""
    B, H, T, hd = q.shape
    page = k_pool.shape[1]
    tab = np.maximum(np.asarray(tables), 0)
    B_, G = tab.shape
    # the pool's stored order: [P+1, page, H*hd], a token's heads side by
    # side in one row -> [B, G, page, H, hd]
    k = np.asarray(k_pool, np.float32)[tab].reshape(B, G, page, H, hd)
    v = np.asarray(v_pool, np.float32)[tab].reshape(B, G, page, H, hd)
    if k_scale is not None:  # [P+1, page, H], indexed like the values
        k = k * np.asarray(k_scale, np.float32)[tab][..., None]
        v = v * np.asarray(v_scale, np.float32)[tab][..., None]
    k = k.transpose(0, 3, 1, 2, 4).reshape(B, H, G * page, hd)
    v = v.transpose(0, 3, 1, 2, 4).reshape(B, H, G * page, hd)
    s = np.einsum("bhtd,bhcd->bhtc", np.asarray(q, np.float32),
                  k) / np.sqrt(hd)
    s = np.where(np.asarray(mask)[:, None], s, -1e30)
    m = s.max(-1, keepdims=True)
    p = np.exp(s - m)
    return np.einsum("bhtc,bhcd->bhtd",
                     p / np.maximum(p.sum(-1, keepdims=True), 1e-30), v)


def _geometry(rng, B=3, H=4, hd=64, page=16, G=4, T=1, dtype=np.float32):
    """A ragged paged layout: slot b holds ``lengths[b]`` tokens across
    its first ceil(len/page) table entries; the rest are unmapped (-1).
    Heads of 64 (GPT-2's, half a lane tile): a head block is 2 or 4."""
    P = B * G  # enough physical pages for a 1:1 mapping + 1 drop page
    k_pool = rng.randn(P + 1, page, H * hd).astype(dtype)
    v_pool = rng.randn(P + 1, page, H * hd).astype(dtype)
    lengths = [G * page - 1 - 3 * b for b in range(B)]  # ragged, >= T
    tables = np.full((B, G), -1, np.int32)
    nxt = 0
    for b in range(B):
        for g in range(-(-lengths[b] // page)):
            tables[b, g] = nxt
            nxt += 1
    q = rng.randn(B, H, T, hd).astype(np.float32)
    kp = np.arange(G * page)
    mask = np.zeros((B, T, G * page), bool)
    for b in range(B):
        mapped = np.repeat(tables[b] >= 0, page)
        for t in range(T):
            mask[b, t] = mapped & (kp <= lengths[b] - T + t)
    return q, k_pool, v_pool, tables, mask


def _quantize(pool, dtype, H=4):
    """Per-(page entry, head) abs-max quantization, the serving layout:
    scale [P+1, page, H] f32 applied over each head's hd lanes."""
    flat = pool.shape
    pool = pool.reshape(flat[0], flat[1], H, flat[2] // H)
    amax = np.abs(pool).max(-1)
    if dtype == "int8":
        scale = amax / 127.0
        qp = np.clip(np.round(pool / np.maximum(scale, 1e-30)[..., None]),
                     -127, 127).astype(np.int8)
        qp = jnp.asarray(qp.reshape(flat))
    else:  # fp8-e4m3
        scale = amax / 448.0
        qp = jnp.asarray((pool / np.maximum(scale, 1e-30)[..., None]
                          ).reshape(flat)).astype(jnp.float8_e4m3fn)
    return qp, jnp.asarray(scale.astype(np.float32))


def _clipped(tables):
    return jnp.maximum(jnp.asarray(tables), 0)


class TestEquivalence:
    def test_float_all_candidates(self):
        rng = np.random.RandomState(0)
        q, kp, vp, tab, mask = _geometry(rng)
        cands = _paged_decode.candidates(q, kp, vp, tab, mask, None, None)
        # H=4 heads of 64: a block of 4 (the whole row) or 2 (one lane
        # tile); one head alone is half a tile and is not offered
        assert sorted(c["block_h"] for c in cands) == [2, 4]
        want = _ref_attend(q, kp, vp, tab, mask)
        for cfg in cands:
            out = paged_flash_decode(jnp.asarray(q), jnp.asarray(kp),
                                     jnp.asarray(vp), _clipped(tab),
                                     jnp.asarray(mask), **cfg)
            np.testing.assert_allclose(np.asarray(out), want,
                                       rtol=2e-4, atol=2e-5)

    def test_decode_width_sweeps_the_heads_as_one_block_diagonal_head(self):
        # q [B, H, 1, hd] over float pages with no block asked for: the
        # heads become the rows of one head as wide as a pool row; same
        # products, so the per-head form agrees to rounding, fully
        # masked slots (free slots of a decode step) emit zeros, and the
        # other heads' lanes never leak into a head's context
        rng = np.random.RandomState(9)
        q, kp, vp, tab, mask = _geometry(rng, H=12)  # GPT-2's 12 x 64
        mask[2] = False
        args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                _clipped(tab), jnp.asarray(mask))
        jaxpr = str(jax.make_jaxpr(paged_flash_decode)(*args))
        assert "f32[3,1,16,768]" in jaxpr  # 12 heads -> 16 rows of 768
        out = np.asarray(paged_flash_decode(*args))
        assert out.shape == q.shape
        np.testing.assert_array_equal(out[2], 0.0)
        want = _ref_attend(q, kp, vp, tab, mask)
        np.testing.assert_allclose(out[:2], want[:2], rtol=2e-4, atol=2e-5)
        per_head = np.asarray(paged_flash_decode(*args, block_h=12))
        np.testing.assert_allclose(out, per_head, rtol=1e-5, atol=1e-6)
        # poison one head's lanes of every page: only that head moves
        kp2, vp2 = kp.copy(), vp.copy()
        kp2[:, :, 3 * 64:4 * 64] += 5.0
        vp2[:, :, 3 * 64:4 * 64] -= 7.0
        out2 = np.asarray(paged_flash_decode(
            args[0], jnp.asarray(kp2), jnp.asarray(vp2), *args[3:]))
        others = [h for h in range(12) if h != 3]
        np.testing.assert_array_equal(out2[:, others], out[:, others])
        assert np.abs(out2[:2, 3] - out[:2, 3]).max() > 1.0

    @pytest.mark.parametrize("qdtype", ["int8", "fp8"])
    def test_quantized_all_candidates(self, qdtype):
        rng = np.random.RandomState(1)
        q, kp, vp, tab, mask = _geometry(rng)
        kq, ks = _quantize(kp, qdtype)
        vq, vs = _quantize(vp, qdtype)
        # the oracle attends over the SAME dequantized values, so the
        # comparison isolates the kernel, not the quantizer
        want = _ref_attend(q, kq, vq, tab, mask, ks, vs)
        cands = _paged_decode.candidates(q, kq, vq, tab, mask, ks, vs)
        for cfg in cands:
            out = paged_flash_decode(jnp.asarray(q), kq, vq, _clipped(tab),
                                     jnp.asarray(mask), ks, vs, **cfg)
            np.testing.assert_allclose(np.asarray(out), want,
                                       rtol=2e-4, atol=2e-4)

    def test_speculative_verify_width(self):
        # T = 1+k (k=4) pads to the sublane tile inside the kernel; all
        # T rows are valid queries at staggered causal positions
        rng = np.random.RandomState(2)
        q, kp, vp, tab, mask = _geometry(rng, T=5)
        assert mask.all(-1).sum() == 0  # staggered causality is live
        want = _ref_attend(q, kp, vp, tab, mask)
        out = paged_flash_decode(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), _clipped(tab),
                                 jnp.asarray(mask))
        np.testing.assert_allclose(np.asarray(out), want,
                                   rtol=2e-4, atol=2e-5)

    def test_drop_page_and_unmapped_pages_never_contribute(self):
        rng = np.random.RandomState(3)
        q, kp, vp, tab, mask = _geometry(rng)
        out0 = paged_flash_decode(jnp.asarray(q), jnp.asarray(kp),
                                  jnp.asarray(vp), _clipped(tab),
                                  jnp.asarray(mask))
        # poison the write-drop page (last) AND every unmapped page: the
        # mask (not the data) must be what excludes them
        kp2, vp2 = kp.copy(), vp.copy()
        kp2[-1] = vp2[-1] = 1e4
        used = set(tab[tab >= 0].ravel())
        for p in range(kp.shape[0] - 1):
            if p not in used:
                kp2[p] = vp2[p] = -1e4
        out1 = paged_flash_decode(jnp.asarray(q), jnp.asarray(kp2),
                                  jnp.asarray(vp2), _clipped(tab),
                                  jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))

    def test_fully_masked_row_emits_zeros(self):
        rng = np.random.RandomState(4)
        q, kp, vp, tab, mask = _geometry(rng, T=2)
        mask[1, 0, :] = False  # e.g. a slot mid-admission: no valid kv yet
        out = paged_flash_decode(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), _clipped(tab),
                                 jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(out)[1, :, 0], 0.0)
        want = _ref_attend(q, kp, vp, tab, mask)
        vb, vt = np.nonzero(np.asarray(mask).any(-1))  # valid rows only
        np.testing.assert_allclose(np.asarray(out)[vb, :, vt],
                                   want[vb, :, vt], rtol=2e-4, atol=2e-5)

    def test_bf16_query_pool(self):
        rng = np.random.RandomState(5)
        q, kp, vp, tab, mask = _geometry(rng)
        qb = jnp.asarray(q, jnp.bfloat16)
        kb = jnp.asarray(kp, jnp.bfloat16)
        vb = jnp.asarray(vp, jnp.bfloat16)
        out = paged_flash_decode(qb, kb, vb, _clipped(tab),
                                 jnp.asarray(mask))
        assert out.dtype == jnp.bfloat16
        want = _ref_attend(np.asarray(qb, np.float32),
                           np.asarray(kb, np.float32),
                           np.asarray(vb, np.float32), tab, mask)
        np.testing.assert_allclose(np.asarray(out, np.float32), want,
                                   rtol=2e-2, atol=2e-2)

    def test_head_blocks_are_whole_lane_tiles_or_the_row(self):
        assert _head_blocks(12, 64) == [12, 6, 4, 2]   # GPT-2-small
        assert _head_blocks(20, 64) == [20, 10, 4, 2]  # GPT-2-large
        assert _head_blocks(8, 128) == [8, 4, 2, 1]
        assert _head_blocks(4, 8) == [4]  # tiny models: the row whole
        # a block the row cannot be cut into runs as the whole row
        rng = np.random.RandomState(7)
        q, kp, vp, tab, mask = _geometry(rng)
        args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                _clipped(tab), jnp.asarray(mask))
        np.testing.assert_array_equal(
            np.asarray(paged_flash_decode(*args, block_h=1)),
            np.asarray(paged_flash_decode(*args, block_h=4)))

    def test_small_heads_and_wide_pages(self):
        # hd=16 (a row of 64 lanes, one block) and pages of 128
        rng = np.random.RandomState(8)
        for kw in (dict(hd=16), dict(hd=16, page=128, G=2)):
            q, kp, vp, tab, mask = _geometry(rng, **kw)
            out = paged_flash_decode(jnp.asarray(q), jnp.asarray(kp),
                                     jnp.asarray(vp), _clipped(tab),
                                     jnp.asarray(mask))
            np.testing.assert_allclose(np.asarray(out),
                                       _ref_attend(q, kp, vp, tab, mask),
                                       rtol=2e-4, atol=2e-5)

    def test_scale_pair_enforced(self):
        rng = np.random.RandomState(6)
        q, kp, vp, tab, mask = _geometry(rng)
        kq, ks = _quantize(kp, "int8")
        with pytest.raises(InvalidArgumentError):
            paged_flash_decode(jnp.asarray(q), kq, kq, _clipped(tab),
                               jnp.asarray(mask), k_scale=ks)


class TestEligibility:
    def test_cpu_backend_falls_back(self):
        # the gather path is the CPU reference; interpret-mode pallas
        # must never be the production dispatch
        assert jax.default_backend() != "tpu"
        assert not paged_flash_eligible(head_dim=64, page_size=16)

    def test_tpu_override_would_dispatch(self, use_mesh):
        use_mesh(jax.devices()[:1])
        assert paged_flash_eligible(head_dim=64, page_size=16,
                                    backend="tpu")

    def test_multi_device_mesh_refuses(self, use_mesh):
        # JAX will not lower a Mosaic kernel inside a jit that spans more
        # than one device, whichever axis is sharded — here data=2
        use_mesh(jax.devices()[:2])
        assert not paged_flash_eligible(head_dim=64, page_size=16,
                                        backend="tpu")

    def test_alignment_and_flag_gate(self, use_mesh):
        use_mesh(jax.devices()[:1])
        assert not paged_flash_eligible(head_dim=12, backend="tpu")
        assert not paged_flash_eligible(page_size=12, backend="tpu")
        set_flags({"paged_flash": False})
        try:
            assert not paged_flash_eligible(head_dim=64, backend="tpu")
        finally:
            set_flags({"paged_flash": True})
