"""Paged flash-decode kernel (ops/paged_attention.py).

Per-candidate numerical equivalence against the gather-then-attend
reference (the serving path's bit-identical CPU fallback) across float,
int8 and fp8-e4m3 pools, drop-page masking, ragged page counts, sweep
bounds and the speculative ``1+k`` verify width — all on the CPU
interpreter.  The performance question lives on the real chip
(``benchmarks/run.py``, the ``gpt2_small`` cells).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops.paged_attention import (QUERY_TILE, _head_blocks,
                                            _heads_a_step, _sweep,
                                            block_pages, key_visible,
                                            paged_flash_decode,
                                            paged_flash_eligible,
                                            query_tile, sweep_bound)


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


def _geometry(rng, B=3, H=4, hd=64, page=16, G=4, T=1, dtype=np.float32,
              lengths=None):
    """A ragged paged layout: slot b holds ``lengths[b]`` tokens across
    its first ceil(len/page) table entries; the rest are unmapped (-1).
    The cache metadata is the serving loop's: ``pos_map`` [B, C] (the
    position each mapped entry holds, -1 elsewhere) and the query rows'
    ``positions`` [B, T], the last T of the slot.  Heads of 64 (GPT-2's,
    half a lane tile): a head block is 2 or 4."""
    P = B * G  # enough physical pages for a 1:1 mapping + 1 drop page
    k_pool = rng.randn(P + 1, page, H * hd).astype(dtype)
    v_pool = rng.randn(P + 1, page, H * hd).astype(dtype)
    if lengths is None:
        lengths = [G * page - 1 - 3 * b for b in range(B)]  # ragged, >= T
    tables = np.full((B, G), -1, np.int32)
    nxt = 0
    for b in range(B):
        for g in range(-(-lengths[b] // page)):
            tables[b, g] = nxt
            nxt += 1
    q = rng.randn(B, H, T, hd).astype(np.float32)
    kp = np.arange(G * page, dtype=np.int32)
    pos_map = np.where(np.repeat(tables >= 0, page, axis=1), kp[None], -1)
    positions = np.stack([np.arange(n - T, n) if n else np.full(T, -1)
                          for n in lengths]).astype(np.int32)
    return q, k_pool, v_pool, tables, pos_map.astype(np.int32), positions


def _mask(pos_map, positions):
    """The model's validity mask, as ``forward_paged`` builds it."""
    return key_visible(pos_map[:, None, :], positions[:, :, None],
                       pos_map.shape[1])


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


def _args(q, kp, vp, tab, pm, pos):
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), _clipped(tab),
            jnp.asarray(pm), jnp.asarray(pos))


class TestEquivalence:
    def test_float_all_head_blocks(self):
        rng = np.random.RandomState(0)
        q, kp, vp, tab, pm, pos = _geometry(rng)
        # H=4 heads of 64: a block of 4 (the whole row) or 2 (one lane
        # tile); one head alone is half a tile and is not offered.  The
        # rule takes the whole row: nothing is searched
        assert _head_blocks(4, 64) == [4, 2]
        assert _heads_a_step(q, kp, tab, False) == 4
        want = _ref_attend(q, kp, vp, tab, _mask(pm, pos))
        for bh in (None, 4, 2):
            out = paged_flash_decode(*_args(q, kp, vp, tab, pm, pos),
                                     block_h=bh)
            np.testing.assert_allclose(np.asarray(out), want,
                                       rtol=2e-4, atol=2e-5)

    def test_decode_width_sweeps_the_heads_as_one_block_diagonal_head(self):
        # q [B, H, 1, hd] over float pages with no block asked for: the
        # heads become the rows of one head as wide as a pool row; same
        # products, so the per-head form agrees to rounding, fully
        # masked slots (free slots of a decode step) emit zeros, and the
        # other heads' lanes never leak into a head's context
        rng = np.random.RandomState(9)
        q, kp, vp, tab, pm, pos = _geometry(rng, H=12)  # GPT-2's 12 x 64
        pos[2] = -1
        args = _args(q, kp, vp, tab, pm, pos)
        jaxpr = str(jax.make_jaxpr(paged_flash_decode)(*args))
        assert "f32[3,1,16,768]" in jaxpr  # 12 heads -> 16 rows of 768
        out = np.asarray(paged_flash_decode(*args))
        assert out.shape == q.shape
        np.testing.assert_array_equal(out[2], 0.0)
        want = _ref_attend(q, kp, vp, tab, _mask(pm, pos))
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
    def test_quantized_all_head_blocks(self, qdtype):
        rng = np.random.RandomState(1)
        q, kp, vp, tab, pm, pos = _geometry(rng)
        kq, ks = _quantize(kp, qdtype)
        vq, vs = _quantize(vp, qdtype)
        # the oracle attends over the SAME dequantized values, so the
        # comparison isolates the kernel, not the quantizer
        want = _ref_attend(q, kq, vq, tab, _mask(pm, pos), ks, vs)
        assert _heads_a_step(q, kq, tab, True) == 4
        for bh in (None, 4, 2):
            out = paged_flash_decode(*_args(q, kq, vq, tab, pm, pos),
                                     k_scale=ks, v_scale=vs, block_h=bh)
            np.testing.assert_allclose(np.asarray(out), want,
                                       rtol=2e-4, atol=2e-4)

    def test_speculative_verify_width(self):
        # T = 1+k (k=4) pads to the sublane tile inside the kernel; all
        # T rows are valid queries at staggered causal positions
        rng = np.random.RandomState(2)
        q, kp, vp, tab, pm, pos = _geometry(rng, T=5)
        mask = _mask(pm, pos)
        assert mask.all(-1).sum() == 0  # staggered causality is live
        assert len({int(r.sum()) for r in mask[0]}) == 5
        want = _ref_attend(q, kp, vp, tab, mask)
        out = paged_flash_decode(*_args(q, kp, vp, tab, pm, pos))
        np.testing.assert_allclose(np.asarray(out), want,
                                   rtol=2e-4, atol=2e-5)

    def test_drop_page_and_unmapped_pages_never_contribute(self):
        rng = np.random.RandomState(3)
        q, kp, vp, tab, pm, pos = _geometry(rng)
        out0 = paged_flash_decode(*_args(q, kp, vp, tab, pm, pos))
        # poison the write-drop page (last) AND every unmapped page: the
        # rule (not the data) must be what excludes them
        kp2, vp2 = kp.copy(), vp.copy()
        kp2[-1] = vp2[-1] = 1e4
        used = set(tab[tab >= 0].ravel())
        for p in range(kp.shape[0] - 1):
            if p not in used:
                kp2[p] = vp2[p] = -1e4
        out1 = paged_flash_decode(*_args(q, kp2, vp2, tab, pm, pos))
        np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))

    def test_fully_masked_row_emits_zeros(self):
        rng = np.random.RandomState(4)
        q, kp, vp, tab, pm, pos = _geometry(rng, T=2)
        pos[1, 0] = -1  # e.g. a slot mid-admission: no valid kv yet
        out = paged_flash_decode(*_args(q, kp, vp, tab, pm, pos))
        np.testing.assert_array_equal(np.asarray(out)[1, :, 0], 0.0)
        mask = _mask(pm, pos)
        want = _ref_attend(q, kp, vp, tab, mask)
        vb, vt = np.nonzero(mask.any(-1))  # valid rows only
        assert len(vb) == 5
        np.testing.assert_allclose(np.asarray(out)[vb, :, vt],
                                   want[vb, :, vt], rtol=2e-4, atol=2e-5)

    def test_bf16_query_pool(self):
        rng = np.random.RandomState(5)
        q, kp, vp, tab, pm, pos = _geometry(rng)
        qb = jnp.asarray(q, jnp.bfloat16)
        kb = jnp.asarray(kp, jnp.bfloat16)
        vb = jnp.asarray(vp, jnp.bfloat16)
        out = paged_flash_decode(qb, kb, vb, _clipped(tab),
                                 jnp.asarray(pm), jnp.asarray(pos))
        assert out.dtype == jnp.bfloat16
        want = _ref_attend(np.asarray(qb, np.float32),
                           np.asarray(kb, np.float32),
                           np.asarray(vb, np.float32), tab, _mask(pm, pos))
        np.testing.assert_allclose(np.asarray(out, np.float32), want,
                                   rtol=2e-2, atol=2e-2)

    def test_head_blocks_are_whole_lane_tiles_or_the_row(self):
        assert _head_blocks(12, 64) == [12, 6, 4, 2]   # GPT-2-small
        assert _head_blocks(20, 64) == [20, 10, 4, 2]  # GPT-2-large
        assert _head_blocks(8, 128) == [8, 4, 2, 1]
        assert _head_blocks(4, 8) == [4]  # tiny models: the row whole
        # a block the row cannot be cut into runs as the whole row
        rng = np.random.RandomState(7)
        args = _args(*_geometry(rng))
        np.testing.assert_array_equal(
            np.asarray(paged_flash_decode(*args, block_h=1)),
            np.asarray(paged_flash_decode(*args, block_h=4)))

    def test_small_heads_and_wide_pages(self):
        # hd=16 (a row of 64 lanes, one block) and pages of 128
        rng = np.random.RandomState(8)
        for kw in (dict(hd=16), dict(hd=16, page=128, G=2)):
            q, kp, vp, tab, pm, pos = _geometry(rng, **kw)
            out = paged_flash_decode(*_args(q, kp, vp, tab, pm, pos))
            np.testing.assert_allclose(
                np.asarray(out),
                _ref_attend(q, kp, vp, tab, _mask(pm, pos)),
                rtol=2e-4, atol=2e-5)

    def test_scale_pair_enforced(self):
        rng = np.random.RandomState(6)
        q, kp, vp, tab, pm, pos = _geometry(rng)
        kq, ks = _quantize(kp, "int8")
        with pytest.raises(InvalidArgumentError):
            paged_flash_decode(*_args(q, kq, kq, tab, pm, pos), k_scale=ks)


def _wrapped(pm, pos, b, at):
    """Slot ``b`` (fully mapped) has decoded past its window: its newest
    token sits at ``at`` >= C, entry c holds the newest position that is
    c modulo C, and the entries of its FIRST pages, not yet re-written
    this lap, are blank — its live pages are not a prefix of its table."""
    C, T = pm.shape[1], pos.shape[1]
    c = np.arange(C)
    lap = at - at % C + c
    pm[b] = np.where(c <= at % C, lap, lap - C)
    pm[b, :24] = -1
    pos[b] = np.arange(at - T + 1, at + 1)


#: one case a line: geometry, then what the case does to it
_BOUNDED = {
    # bounds of 2, 1, 0 blocks of 8 pages: slot 1 ends inside its first
    # block (a live length that is no multiple of the block, nor of the
    # page), slot 2 is free: zero iterations, zeros out
    "ragged_bounds_one_slot_empty": dict(G=16, lengths=[255, 77, 0],
                                         bounds=[16, 8, 0]),
    "verify_width": dict(G=16, T=5, lengths=[200, 133, 5],
                         bounds=[16, 16, 8]),
    # an admission chunk of two rows, the second a padding row
    "admission_width_one_padding_row": dict(B=2, G=16, T=40,
                                            lengths=[40, 0], bounds=[8, 0]),
    "wrapped_slot_gets_the_whole_window": dict(G=16, lengths=[256, 100, 9],
                                               wrap=(0, 256 + 70),
                                               bounds=[16, 8, 8]),
    "int8_pool": dict(G=16, T=5, lengths=[255, 77, 0], quant="int8",
                      bounds=[16, 8, 0]),
    "fp8_pool": dict(G=16, lengths=[255, 77, 0], quant="fp8",
                     bounds=[16, 8, 0]),
    # one page a block
    "page_128": dict(G=3, page=128, T=5, lengths=[384, 129, 0],
                     bounds=[3, 2, 0]),
    "decode_width_page_128": dict(G=3, page=128, lengths=[300, 128, 0],
                                  bounds=[3, 1, 0]),
}


@pytest.mark.parametrize("case", sorted(_BOUNDED))
def test_bounded_sweep_matches_the_gather_path(case):
    """The kernel given the model's sweep bound against the gather
    reference over the whole window: no visible key is skipped, whatever
    the bound cuts off."""
    kw = dict(_BOUNDED[case])
    bounds, quant, wrap = kw.pop("bounds"), kw.pop("quant", None), kw.pop(
        "wrap", None)
    rng = np.random.RandomState(sorted(_BOUNDED).index(case))
    q, kp, vp, tab, pm, pos = _geometry(rng, **kw)
    if wrap:
        _wrapped(pm, pos, *wrap)
    mask = _mask(pm, pos)
    page = kp.shape[1]
    bound = sweep_bound(mask, page)
    assert bound.dtype == np.int32 and bound.tolist() == bounds
    np.testing.assert_array_equal(  # numpy and jax: one rule
        np.asarray(sweep_bound(jnp.asarray(mask), page)), bound)
    scales = {}
    if quant:
        (kp, ks), (vp, vs) = _quantize(kp, quant), _quantize(vp, quant)
        scales = dict(k_scale=ks, v_scale=vs)
    want = _ref_attend(q, kp, vp, tab, mask, *scales.values())
    args = _args(q, kp, vp, tab, pm, pos)
    out = np.asarray(paged_flash_decode(*args, jnp.asarray(bound), **scales))
    seen = mask.any(-1)  # [B, T]: rows with a visible key
    np.testing.assert_array_equal(out[~seen[:, None].repeat(q.shape[1], 1)],
                                  0.0)
    vb, vt = np.nonzero(seen)
    np.testing.assert_allclose(out[vb, :, vt], want[vb, :, vt],
                               rtol=2e-4, atol=2e-4 if quant else 2e-5)
    # the bound is an upper bound only: the whole window gives the same
    whole = np.asarray(paged_flash_decode(*args, **scales))
    np.testing.assert_allclose(whole, out, rtol=1e-6, atol=1e-6)


def test_the_bound_is_what_the_kernel_walks():
    # a bound one block short really stops the sweep there: the answer is
    # the reference's over the first 128 keys alone (so a bound of 0 costs
    # a slot nothing, and a wrong bound would be seen)
    rng = np.random.RandomState(11)
    q, kp, vp, tab, pm, pos = _geometry(rng, G=16, T=5,
                                        lengths=[255, 250, 240])
    assert block_pages(16) == 8 and block_pages(128) == 1
    mask = _mask(pm, pos)
    assert sweep_bound(mask, 16).tolist() == [16, 16, 16]
    # a window that ends inside a block cuts the bound: 4 pages, not 8
    small = _geometry(rng)
    assert sweep_bound(_mask(*small[4:]), 16).tolist() == [4, 4, 4]
    short = jnp.asarray([8, 16, 0], jnp.int32)
    out = np.asarray(paged_flash_decode(*_args(q, kp, vp, tab, pm, pos),
                                        short))
    cut = mask.copy()
    cut[0, :, 128:] = False
    want = _ref_attend(q, kp, vp, tab, cut)
    np.testing.assert_allclose(out[:2], want[:2], rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(out[2], 0.0)
    full = _ref_attend(q, kp, vp, tab, mask)
    assert np.abs(out[0] - full[0]).max() > 1e-2


class _Shape:
    def __init__(self, shape, dtype):
        self.shape, self.dtype = shape, np.dtype(dtype)


#: the fixed rule at the shapes the search used to race (and the width the
#: one-tile form could not hold): rows of the call, tile (None: the
#: rule's), heads, head size, pool dtype -> rows a tile, heads a grid step.
#: A tile's VMEM, so every GPT-2 bucket takes all 12 heads where the
#: bucket whole took 2 or 4
_RULE = {
    "gpt2_admit_512": ((512, None, 12, 64, "f4"), (256, 12)),
    "gpt2_admit_640": ((640, None, 12, 64, "f4"), (320, 12)),
    "gpt2_admit_768": ((768, None, 12, 64, "f4"), (256, 12)),
    "gpt2_admit_1024": ((1024, None, 12, 64, "f4"), (256, 12)),
    "gpt2_admit_256_is_one_tile": ((256, None, 12, 64, "f4"), (256, 6)),
    "gpt2_admit_768_tiles_of_128": ((768, 128, 12, 64, "f4"), (128, 12)),
    "gpt2_admit_768_one_tile": ((768, 768, 12, 64, "f4"), (768, 2)),
    "gpt2_admit_640_one_tile": ((640, 640, 12, 64, "f4"), (640, 4)),
    "gpt2_admit_512_one_tile": ((512, 512, 12, 64, "f4"), (512, 4)),
    "gpt2_verify_width": ((5, None, 12, 64, "f4"), (8, 12)),
    "gpt2_int8_pool": ((768, None, 12, 64, "i1"), (256, 12)),
    "gpt2_large": ((768, None, 20, 64, "f4"), (256, 20)),
}


@pytest.mark.parametrize("case", sorted(_RULE))
def test_tile_and_heads_a_step_are_rules_of_the_shape_not_a_search(case):
    (T, tile, H, hd, kv), (rows, heads) = _RULE[case]
    assert QUERY_TILE == 320
    assert (tile or query_tile(T)) == rows
    got = _heads_a_step(_Shape((2, H, T, hd), "f4"),
                        _Shape((9, 16, H * hd), kv),
                        _Shape((2, 64), "i4"), kv == "i1", tile)
    assert got == heads and got in _head_blocks(H, hd)


def _brute_bound(mask, page, tile):
    """A tile's bound read off the mask one (slot, tile) at a time."""
    B, T, C = mask.shape
    G, ppb = C // page, block_pages(page)
    nq = -(-T // tile)
    out = np.zeros((B, nq), np.int32)
    for b in range(B):
        for t in range(nq):
            keys = np.nonzero(mask[b, t * tile:(t + 1) * tile].any(0))[0]
            if len(keys):
                pages = keys.max() // page + 1
                out[b, t] = min(-(-pages // ppb) * ppb, G)
    return out


def _admission(rng, case):
    """An admission call ``[B, T]`` over 32 pages of 16 (four key blocks)
    a slot, two heads of 64: ``(q, k, v, tables, pos_map, positions)`` and
    the per-tile bounds expected, in pages."""
    B, H, hd, page, G = 2, 2, 64, 16, 32
    kw = dict(B=B, H=H, hd=hd, page=page, G=G)
    if case == "two_ragged_rows":
        # [2, 768] in three tiles of 256 over six key blocks: 400 tokens
        # end in the second tile, 704 in the third
        q, kp, vp, tab, pm, pos = _geometry(rng, T=768, lengths=[768, 768],
                                            **{**kw, "G": 48})
        for b, n in enumerate((400, 704)):
            pm[b, n:] = pos[b, n:] = -1
        return (q, kp, vp, tab, pm, pos), [[16, 32, 0], [16, 32, 48]]
    if case == "behind_a_shared_prefix":
        # 300 rows at positions 160 .. 459 of a slot whose first 160
        # entries another request wrote, in two tiles of 192: the first
        # tile already reads three key blocks; row 1 starts cold, 200 tokens
        q, kp, vp, tab, pm, pos = _geometry(rng, T=384, lengths=[460, 200],
                                            **kw)
        pos[:] = -1
        pos[0, :300] = np.arange(160, 460)
        pos[1, :200] = np.arange(200)
        return (q, kp, vp, tab, pm, pos), [[24, 32], [16, 16]]
    if case == "padding_row":
        q, kp, vp, tab, pm, pos = _geometry(rng, T=512, lengths=[512, 0],
                                            **kw)
        return (q, kp, vp, tab, pm, pos), [[16, 32], [0, 0]]
    if case == "ring_wrapped_slot":
        # the slot's newest 384 positions lie past its window's end: live
        # pages are no prefix of its table, every tile gets the window
        q, kp, vp, tab, pm, pos = _geometry(rng, T=384, lengths=[512, 300],
                                            **kw)
        _wrapped(pm, pos, 0, 512 + 200)
        pos[1] = -1
        pos[1, :256] = np.arange(44, 300)
        return (q, kp, vp, tab, pm, pos), [[32, 32], [16, 24]]
    if case == "rows_not_whole_tiles":
        # 396 rows: two tiles of 200, four rows of padding
        q, kp, vp, tab, pm, pos = _geometry(rng, T=396, lengths=[396, 131],
                                            **kw)
        pos[1] = -1
        pos[1, :131] = np.arange(131)
        return (q, kp, vp, tab, pm, pos), [[16, 32], [16, 0]]
    raise KeyError(case)


_ADMISSIONS = ("two_ragged_rows", "behind_a_shared_prefix", "padding_row",
               "ring_wrapped_slot", "rows_not_whole_tiles")


@pytest.mark.parametrize("quant", [None, "int8", "fp8"],
                         ids=["float32", "int8", "fp8"])
@pytest.mark.parametrize("case", _ADMISSIONS)
def test_tiled_sweep_equals_the_one_tile_sweep_bit_for_bit(case, quant):
    """An admission width in query tiles, each swept to its own bound,
    against the same call as ONE tile swept to the slot's bound (the form
    the kernel had): skipping a block no row of a tile can see leaves the
    running max, sum and accumulator as they were, so every bit agrees."""
    rng = np.random.RandomState(_ADMISSIONS.index(case))
    (q, kp, vp, tab, pm, pos), want_bound = _admission(rng, case)
    B, H, T, hd = q.shape
    page = kp.shape[1]
    mask = _mask(pm, pos)
    bound = sweep_bound(mask, page)
    assert bound.dtype == np.int32 and bound.tolist() == want_bound
    np.testing.assert_array_equal(bound, _brute_bound(mask, page,
                                                      query_tile(T)))
    # tiles of any other size: their own bounds, the same bits (below)
    fine = sweep_bound(mask, page, tile=128)
    np.testing.assert_array_equal(fine, _brute_bound(mask, page, 128))
    np.testing.assert_array_equal(  # numpy and jax: one rule
        np.asarray(sweep_bound(jnp.asarray(mask), page)), bound)
    # a slot's bound is the largest of its tiles'
    slot = sweep_bound(mask, page, tile=T)
    np.testing.assert_array_equal(slot, bound.max(axis=1))
    scales = (None, None)
    if quant:
        (kp, ks), (vp, vs) = _quantize(kp, quant, H), _quantize(vp, quant, H)
        scales = (ks, vs)
    args = _args(q, kp, vp, tab, pm, pos)
    tiled = np.asarray(paged_flash_decode(*args, jnp.asarray(bound),
                                          *scales))
    one = np.asarray(_sweep(*args, jnp.asarray(slot), *scales, block_h=H,
                            sm_scale=hd ** -0.5, tile=T + 7))
    np.testing.assert_array_equal(tiled, one)
    # and it is the attention the gather path computes
    ref = _ref_attend(q, kp, vp, tab, mask, *scales)
    seen = mask.any(-1)
    np.testing.assert_array_equal(
        tiled[~seen[:, None].repeat(H, 1)], 0.0)
    vb, vt = np.nonzero(seen)
    np.testing.assert_allclose(tiled[vb, :, vt], ref[vb, :, vt], rtol=2e-4,
                               atol=2e-4 if quant else 2e-5)
    # a slot's bound given for a tiled call holds for each of its tiles
    np.testing.assert_array_equal(
        np.asarray(paged_flash_decode(*args, jnp.asarray(slot), *scales)),
        tiled)
    if not quant:  # and tiles of 128 rows walk less to the same bits
        np.testing.assert_array_equal(np.asarray(_sweep(
            *args, jnp.asarray(fine), *scales, block_h=H,
            sm_scale=hd ** -0.5, tile=128)), tiled)


def test_grouped_head_fold_gives_a_row_the_bound_of_its_own_positions():
    # 8 query heads over 2 K/V heads: the 4 query heads of a K/V head are
    # 4 x T query rows of it, positions tiled; T = 400 is two tiles of 200
    # and the fold's 1600 rows five of 320, so a tile of the fold straddles
    # tiles and copies of the rows and takes the largest of their bounds.
    # Bit for bit the one-tile sweep of the same folded rows
    rng = np.random.RandomState(21)
    B, H, Hkv, hd, page, G, T = 2, 8, 2, 64, 16, 32, 400
    rep = H // Hkv
    q, kp, vp, tab, pm, pos = _geometry(rng, B=B, H=Hkv, hd=hd, page=page,
                                        G=G, T=T, lengths=[400, 70])
    pos[1] = -1
    pos[1, :70] = np.arange(70)
    q = rng.randn(B, H, T, hd).astype(np.float32)
    mask = _mask(pm, pos)
    bound = sweep_bound(mask, page)
    assert bound.tolist() == [[16, 32], [8, 0]]
    args = _args(q, kp, vp, tab, pm, pos)
    got = np.asarray(paged_flash_decode(*args, jnp.asarray(bound)))
    folded = jnp.asarray(q).reshape(B, Hkv, rep * T, hd)
    fpos = jnp.tile(jnp.asarray(pos), (1, rep))
    # the fold's bounds hold those its own mask gives (a tile of the fold
    # takes the bound of every tile of the call it touches)
    from paddle_tpu.ops.paged_attention import _fold_bound
    fb = np.asarray(_fold_bound(jnp.asarray(bound), T, rep))
    own = sweep_bound(_mask(pm, np.asarray(fpos)), page)
    assert fb.shape == own.shape == (B, 5) and (fb >= own).all()
    assert fb.tolist() == [[32, 32, 32, 32, 32], [8, 8, 8, 8, 8]]
    one = np.asarray(_sweep(
        folded, *args[1:5], fpos, jnp.asarray(sweep_bound(mask, page, T)),
        None, None, block_h=Hkv, sm_scale=hd ** -0.5,
        tile=rep * T)).reshape(B, H, T, hd)
    np.testing.assert_array_equal(got, one)
    kf = np.repeat(kp.reshape(-1, page, Hkv, hd), rep, 2).reshape(
        -1, page, H * hd)
    vf = np.repeat(vp.reshape(-1, page, Hkv, hd), rep, 2).reshape(
        -1, page, H * hd)
    ref = _ref_attend(q, kf, vf, tab, mask)
    vb, vt = np.nonzero(mask.any(-1))
    np.testing.assert_allclose(got[vb, :, vt], ref[vb, :, vt], rtol=2e-4,
                               atol=2e-5)


def test_a_bound_of_another_tiling_is_refused():
    rng = np.random.RandomState(22)
    args = _args(*_geometry(rng, B=2, H=2, G=32, T=384, lengths=[384, 9]))
    with pytest.raises(InvalidArgumentError):
        paged_flash_decode(*args, jnp.zeros((2, 3), jnp.int32))
    one_tile = _args(*_geometry(rng, B=2, H=2, G=32, T=8, lengths=[80, 9]))
    with pytest.raises(InvalidArgumentError):
        paged_flash_decode(*one_tile, jnp.zeros((2, 1), jnp.int32))


@pytest.mark.parametrize("pool_dtype", [None, "int8"], ids=["float", "int8"])
def test_forward_paged_gives_the_kernel_its_bound(monkeypatch, pool_dtype):
    """``GPTModel.forward_paged`` with the kernel's gate open (interpret
    mode here; the chip's branch, which no CPU run takes otherwise)
    against the gather path: an admission call with a padding row, then a
    verify-width step with a free slot, over a pool of shuffled pages.
    Hidden states agree, the pools written are identical, and every layer
    was handed the one bound computed from the call's mask."""
    from paddle_tpu import nn  # noqa: F401  (registers layers)
    from paddle_tpu.models import gpt as G
    from paddle_tpu.ops import paged_attention as PA

    cfg = G.gpt_tiny(hidden_size=64, max_position=512)  # 4 heads of 16
    model = G.GPTModel(cfg)
    page, Gp, B = 16, 16, 3
    C = page * Gp  # two key blocks of 128
    dt = jnp.int8 if pool_dtype else None
    rng = np.random.RandomState(5)
    table = np.full((B, Gp), -1, np.int32)
    free = list(rng.permutation(B * Gp))
    lengths = [150, 0, 37]
    for b, n in enumerate(lengths):
        for g in range(-(-(n + 5) // page)):
            table[b, g] = free.pop()
    pos_map = np.full((B, C), -1, np.int32)
    Tb = 352  # two query tiles of 176 rows
    ids = rng.randint(0, cfg.vocab_size, (B, Tb)).astype(np.int32)
    pos = np.full((B, Tb), -1, np.int32)
    for b, n in enumerate(lengths):
        pos[b, :n] = pos_map[b, :n] = np.arange(n)
    step_ids = rng.randint(0, cfg.vocab_size, (B, 5)).astype(np.int32)
    step_pos = np.full((B, 5), -1, np.int32)
    step_map = pos_map.copy()
    for b, n in enumerate(lengths):
        if n:
            step_pos[b] = step_map[b, n:n + 5] = np.arange(n, n + 5)

    given = []
    real = PA.paged_flash_decode

    def spy(q, k, v, tab, pm, pp, bound, *scales, **kw):
        given.append(np.asarray(bound))
        return real(q, k, v, tab, pm, pp, bound, *scales, **kw)

    def run(kernel):
        monkeypatch.setattr(G, "_paged_flash", lambda hd, pg: kernel)
        monkeypatch.setattr(PA, "paged_flash_decode", spy)
        pool = model.init_paged_cache(B * Gp, page, dtype=dt)
        h0, pool = model.forward_paged(ids, pos, pos_map, table, pool)
        h1, pool = model.forward_paged(step_ids, step_pos, step_map, table,
                                       pool)
        return np.asarray(h0), np.asarray(h1), pool

    want0, want1, wpool = run(False)
    assert not given
    got0, got1, gpool = run(True)
    assert len(given) == 2 * cfg.num_layers
    for got_b, (pm, pp) in zip(given[::cfg.num_layers],
                               ((pos_map, pos), (step_map, step_pos))):
        mask = key_visible(pm[:, None, :], pp[:, :, None], C)
        np.testing.assert_array_equal(got_b, sweep_bound(mask, page))
    # the admission's 352 rows are two query tiles, each with its own
    # bound: the second holds padding rows alone and walks nothing; the
    # step's five rows are one tile
    assert given[0].tolist() == [[16, 0], [0, 0], [8, 0]]
    assert given[-1].tolist() == [16, 0, 8]
    for a, b in zip(given[:cfg.num_layers], given[1:cfg.num_layers]):
        np.testing.assert_array_equal(a, b)  # one bound, every layer
    live0, live1 = pos >= 0, step_pos >= 0
    tol = dict(rtol=2e-2, atol=2e-2) if pool_dtype else dict(rtol=2e-4,
                                                              atol=2e-4)
    np.testing.assert_allclose(got0[live0], want0[live0], **tol)
    np.testing.assert_allclose(got1[live1], want1[live1], **tol)
    if not pool_dtype:  # the scatter is the same on both paths
        for lw, lg in zip(wpool["layers"], gpool["layers"]):
            used = table[table >= 0]
            np.testing.assert_allclose(np.asarray(lg["k"])[used],
                                       np.asarray(lw["k"])[used],
                                       rtol=2e-4, atol=2e-4)


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
