"""The hybrid stack under the ``k_exaone`` family's options (three
``sliding_attention`` layers to one ``full_attention`` layer, the window
layers rotated and cached in per-slot rings, the global layers without
positions and cached in pages, grouped K/V heads with per-head QK-norm, a
dense first layer and expert layers that HOLD A SHARE of their experts
behind a sigmoid router, an ungated shared expert) against the ONE plain
reference, ``benchmarks/reference/k_exaone.py``: tiny widths, seeded
weights, CPU.

Tolerances.  With float32 parameters the program (rings, pages, fused
projections, sorted expert rows) and the reference (one causal forward pass
under a mask, a loop over the held experts) compute the same float32
function by two routes: logits of magnitude up to 0.68 agreed to 6.3e-7 over
these shapes and three seeds, so 3e-5.  With bfloat16 parameters the program
rounds every projection to bfloat16 where the reference keeps float32:
0.0033 was the widest MEAN logit gap over three seeds, so 0.01 (3 x); the
widest single gap is a routing flip's (a sigmoid top-4 of 16 whose fourth
and fifth scores round apart: 0.095, 0.136, 0.198 at the three seeds), so
0.6 (3 x) and the mean is the limit that tells.  Each planted fault moves
the MEAN logit by more than that bfloat16 mean tolerance in float32 (the
smallest seen: 0.016, global layers rotated), so the comparison fails it
whichever type the parameters have.  ``rms_norm_eps`` is the preset's 1e-12
(``families/olmo_hybrid.py:TINY`` says why), so no norm hides a layer.
"""
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import loader  # noqa: E402

from paddle_tpu.models import hybrid  # noqa: E402
from paddle_tpu.moe import DroplessMoE  # noqa: E402
from paddle_tpu.ops import paged_attention as pa  # noqa: E402
from paddle_tpu.ops.flash_attention import flash_attention  # noqa: E402
from paddle_tpu.serving import GenerationEngine  # noqa: E402

# ``paddle_tpu.ops.grouped_matmul`` the attribute is the function of that name
gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")
fam = loader.load_module("families", "k_exaone")
ref = loader.load_module("reference", "k_exaone")
with open(os.path.join(REPO, "benchmarks", "configs",
                       "k_exaone_serve.json")) as _f:
    PUBLISHED = json.load(_f)

F32_TOL = 3e-5
BF16_MAX, BF16_MEAN = 0.6, 0.01
W = fam.TINY["sliding_window"]


def tiny_cfg(dtype="float32", cache_len=64, **over):
    cfg = {**PUBLISHED, **fam.TINY, "param_dtype": dtype,
           "serve": {"cache_len": cache_len}}
    cfg.update(over)
    return cfg


def build(cfg, seed=5):
    w = fam.make_weights(cfg, seed)
    m = fam.build_model(cfg, w)
    m.eval()
    return m, w


def ref_logits(w, ids, cfg):
    return np.asarray(ref.logits(w, jnp.asarray(ids, jnp.int32),
                                 cfg_items=ref.static_items(cfg)))


def ids_of(shape=(2, 40), seed=1):
    return np.random.default_rng(seed).integers(1, 512, shape).astype(
        np.int32)


@pytest.fixture(scope="module")
def tiny():
    return build(tiny_cfg(cache_len=128))


# -- the configuration -----------------------------------------------------------
def test_the_configuration_is_the_published_one_cut_as_it_says():
    c = PUBLISHED
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                            "num_nextn_predict_layers"]
    assert set(c["reduced"]) == set(c["published"]) == set(c["reduced_why"])
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["sliding_window"],
            c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts_per_tok"]) == (6144, 64, 8, 128, 128, 18432, 2048,
                                          8)
    assert len(c["layer_types"]) == len(c["mlp_layer_types"]) == 48
    assert fam.layer_types(c) == ("sliding_attention",) * 3 + (
        "full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)
    assert fam.ffn_types(c) == ("dense",) + ("moe",) * 7
    assert fam.held(c) == (0, 16) and fam.router_width(c) == 128
    params = sum(int(np.prod(s)) for s, _, _ in fam.param_spec(c).values())
    assert 11.9e9 < 2 * params < 12.0e9     # bfloat16 bytes
    mc = fam.model_config(c)
    assert mc.rope_kinds == ("sliding_attention",) and mc.block_norm == "post"
    assert mc.moe["held"] == (0, 16) and mc.moe["num_experts"] == 128


def test_config_refuses_a_window_without_its_layers_and_the_reverse():
    base = dict(vocab_size=32, hidden_size=16, num_heads=2,
                intermediate_size=32)
    with pytest.raises(Exception, match="sliding_window"):
        hybrid.HybridConfig(layer_types=["sliding_attention"], **base)
    with pytest.raises(Exception, match="sliding_window"):
        hybrid.HybridConfig(layer_types=["full_attention"], sliding_window=4,
                            **base)
    with pytest.raises(Exception, match="linear head sizes"):
        hybrid.HybridConfig(layer_types=["linear_attention"], **base)
    with pytest.raises(Exception, match="rope_kinds"):
        hybrid.HybridConfig(layer_types=["full_attention"],
                            rope_kinds=("linear_attention",), **base)


def test_ring_positions_is_the_last_position_of_each_residue():
    got = np.asarray(hybrid.ring_positions(
        jnp.asarray([0, 5, 8, 21, -1], jnp.int32), 8))
    for qp, row in zip((0, 5, 8, 21), got):
        want = [max((p for p in range(qp + 1) if p % 8 == r), default=-1)
                for r in range(8)]
        assert row.tolist() == want
    assert (got[4] == -1).all()
    # every position a window of 8 can see is in the ring, nothing newer
    assert sorted(got[3].tolist()) == list(range(14, 22))


# -- the model against the reference -----------------------------------------
@pytest.mark.parametrize("dtype,widest,mean", [
    ("float32", F32_TOL, F32_TOL), ("bfloat16", BF16_MAX, BF16_MEAN)])
def test_full_forward_logits_match_the_reference(dtype, widest, mean):
    cfg = tiny_cfg(dtype)
    m, w = build(cfg)
    ids = ids_of()                              # 40 tokens: five windows
    got, want = np.asarray(m(ids)), ref_logits(w, ids, cfg)
    assert got.dtype == np.float32 and np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < widest
    assert np.abs(got - want).mean() < mean


def test_a_score_bias_enters_the_choice_and_never_the_weights():
    """The family's seeded bias is zero (the configuration's ``assumed``
    says why); one that is not must move the choice as the reference has
    it."""
    cfg = tiny_cfg()
    w = fam.make_weights(cfg, 5)
    names = [n for n in w if n.endswith("mlp.score_bias")]
    assert len(names) == 3 and all(not np.asarray(w[n]).any() for n in names)
    biased = dict(w)
    for i, n in enumerate(names):
        biased[n] = 0.3 * jax.random.normal(jax.random.PRNGKey(i),
                                            w[n].shape, jnp.float32)
    ids = ids_of()
    plain = ref_logits(w, ids, cfg)
    want = ref_logits(biased, ids, cfg)
    assert np.abs(want - plain).mean() > BF16_MEAN     # other experts chosen
    m = fam.build_model(cfg, biased)
    m.eval()
    assert np.abs(np.asarray(m(ids)) - want).max() < F32_TOL


def paged_logits(m, ids, plen, C, page, bucket, cache=None):
    """Admit ``plen`` tokens of each row in one ``[B, bucket]`` call, then
    decode the rest one token a call (teacher-forced) through a shuffled
    page table and the slots' rings.  Returns ({position: logits [B, V]
    that predict position + 1}, the cache)."""
    B, total = ids.shape
    G = C // page
    if cache is None:
        cache = m.init_paged_cache(B * G, page, slots=B)
    table = np.random.default_rng(2).permutation(B * G).reshape(B, G).astype(
        np.int32)
    pos_map = np.full((B, C), -1, np.int32)
    pin = np.zeros((B, bucket), np.int32)
    pp = np.full((B, bucket), -1, np.int32)
    pin[:, :plen], pp[:, :plen] = ids[:, :plen], np.arange(plen)
    pos_map[:, :plen] = np.arange(plen)
    lg, cache = m.forward_paged(
        pin, pp, pos_map, table, cache,
        gather_last=np.full((B,), plen, np.int32),
        slots=np.arange(B, dtype=np.int32))
    got = {plen - 1: np.asarray(lg)}
    for p in range(plen, total):
        pos_map[:, p % C] = p
        lg, cache = m.forward_paged(ids[:, p:p + 1],
                                    np.full((B, 1), p, np.int32), pos_map,
                                    table, cache)
        got[p] = np.asarray(lg[:, 0])
    return got, cache


@pytest.mark.parametrize("plen,total", [
    (5, 14),     # shorter than the window; the decode fills and wraps it
    (13, 20),    # between one and two windows
    (27, 40),    # past three: the admission itself wraps the ring
    (8, 9), (16, 18)])   # whole windows exactly
def test_prefill_then_decode_through_rings_and_pages_matches_full_forward(
        tiny, plen, total):
    m, w = tiny
    cfg = tiny_cfg(cache_len=128)
    ids = ids_of((3, total), seed=plen)
    got, cache = paged_logits(m, ids, plen, 64, 8, 32)
    want = ref_logits(w, ids, cfg)
    for p, lg in got.items():
        assert np.abs(lg - want[:, p]).max() < F32_TOL, p
    ring = cache["layers"][0]["ring_k"]
    assert ring.shape == (3 + 1, W, 2 * 16)    # [slots + 1, W, H_kv * hd]
    assert cache["layers"][3]["k"].shape[1:] == (8, 2 * 16)
    assert "ring_k" not in cache["layers"][3]


@pytest.mark.parametrize("kinds", [
    ("sliding_attention", "sliding_attention"),     # no page pool at all
    ("full_attention", "full_attention"),           # no per-slot tensor
    ("full_attention", "sliding_attention")])
def test_any_subset_of_the_layer_kinds_serves_through_its_own_caches(kinds):
    cfg = tiny_cfg(num_hidden_layers=2,
                   layer_types=list(kinds) + PUBLISHED["layer_types"][2:],
                   sliding_window=W if "sliding_attention" in kinds else None)
    m, _ = build(cfg)
    ids = ids_of((2, 26), seed=3)
    got, cache = paged_logits(m, ids, 11, 64, 8, 16)
    want = np.asarray(m(ids))
    for p, lg in got.items():
        assert np.abs(lg - want[:, p]).max() < F32_TOL, p
    assert [sorted(kv) for kv in cache["layers"]] == [
        ["k", "v"] if k == "full_attention" else ["ring_k", "ring_v"]
        for k in kinds]


def test_a_short_prompt_into_a_slot_a_long_one_left_needs_no_reset(tiny):
    """The long tenant fills every ring row and the pages' drop row is not
    in play: the short one that follows writes 3 rows and must see 3."""
    m, w = tiny
    cfg = tiny_cfg(cache_len=128)
    long_ids = ids_of((2, 30), seed=8)
    _, cache = paged_logits(m, long_ids, 27, 64, 8, 32)
    left = np.asarray(cache["layers"][0]["ring_k"][:2])
    assert (np.abs(left).max(axis=(0, 2)) > 0).all()   # every row written
    short = ids_of((2, 12), seed=9)
    got, _ = paged_logits(m, short, 3, 64, 8, 32, cache=cache)
    want = ref_logits(w, short, cfg)
    for p, lg in got.items():
        assert np.abs(lg - want[:, p]).max() < F32_TOL, p


def test_an_inert_row_and_a_free_slot_write_no_ring_row(tiny):
    m, _ = tiny
    B, page, G = 4, 8, 8
    cache = m.init_paged_cache(32, page, slots=B)
    before = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(1), a.shape,
                                    jnp.float32).astype(a.dtype), cache)
    ids = ids_of((2, 16), seed=4)
    pos = np.full((2, 16), -1, np.int32)
    pos[0, :11] = np.arange(11)            # row 1 is inert: slot -1
    pm = np.full((2, G * page), -1, np.int32)
    pm[0, :11] = np.arange(11)
    tab = np.full((2, G), -1, np.int32)
    tab[0, :2] = (5, 9)
    _, after = m.forward_paged(ids, pos, pm, tab, before,
                               slots=np.array([2, -1], np.int32))
    for old, new in zip(before["layers"][:3], after["layers"][:3]):
        for name in ("ring_k", "ring_v"):
            assert not np.array_equal(new[name][2], old[name][2])
            for other in (0, 1, 3):
                assert np.array_equal(new[name][other], old[name][other])
    # a decode step: slots 1 and 3 are free
    ids1 = np.array([[7], [0], [9], [0]], np.int32)
    pos1 = np.array([[3], [-1], [5], [-1]], np.int32)
    pm = np.full((B, G * page), -1, np.int32)
    tab = np.full((B, G), -1, np.int32)
    for b, n in ((0, 4), (2, 6)):
        pm[b, :n], tab[b, 0] = np.arange(n), b
    _, after = m.forward_paged(ids1, pos1, pm, tab, before)
    for old, new in zip(before["layers"][:3], after["layers"][:3]):
        for name in ("ring_k", "ring_v"):
            for free in (1, 3):
                assert np.array_equal(new[name][free], old[name][free])
            for live, row in ((0, 3), (2, 5)):
                changed = np.any(np.asarray(new[name][live])
                                 != np.asarray(old[name][live]), axis=1)
                assert changed.tolist() == [r == row for r in range(W)]


def test_through_the_engine_rings_pages_and_experts_in_one_loop(tiny):
    """Admission ([R, bucket] prompts, some shorter than the window, some
    several windows long), then one token a step; 10 requests on 4 slots,
    so slots are reused.  Every served token is the reference's own argmax
    of a full causal forward pass over its history, to the float32
    tolerance."""
    m, w = tiny
    cfg = tiny_cfg(cache_len=128)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, size=n).astype(np.int32)
               for n in (5, 16, 20, 31, 9, 12, 2, 1, 27, 3)]
    eng = GenerationEngine(m, batch_size=4, prompt_buckets=[16, 32],
                           kv_page_size=8, speculative_k=0,
                           eos_token_id=None, name="kex")
    try:
        warm = eng.warmup()
        futures = [eng.submit(p, 12) for p in prompts]
        outs = [np.asarray(f.result(timeout=300)).tolist() for f in futures]
        assert eng.compile_count == warm
        st = eng.stats()
        assert st["admitted"] == 10
        # 3 sliding layers: K and V rings of 8 rows of 2 x 16, float32 here
        assert m.slot_state_bytes() == 3 * 2 * 4 * W * 32
        assert st["state_bytes_steps"] == (
            st["decode_steps"] * 2 * 4 * m.slot_state_bytes())
        # every decode step routes 4 slots x 4 choices in each of 3 expert
        # layers; the 4 held of the router's 16 experts see about a quarter
        assert st["moe_pairs_routed"] == st["moe_layer_steps"] * 4 * 4 > 0
        assert st["moe_pairs_local"] == st["moe_routed_tokens"]
        assert 0.1 < st["moe_pairs_local"] / st["moe_pairs_routed"] < 0.45
        assert eng.expert_counts().shape == (4,) == (m.moe_experts,)
        texts = eng.compiled_programs()
        assert set(texts) == {"step", "admit[16]", "admit[32]"}
        assert all("/win/" in t and "/attn/" in t and "/moe/" in t
                   for t in texts.values())
    finally:
        eng.close()
    assert all(len(o) == 12 for o in outs)
    gaps = ref.served_token_gaps(w, cfg, prompts, outs)
    assert max(g["gap"].max() for g in gaps) < F32_TOL


def test_slot_state_refuses_speculation_for_a_window_model(tiny):
    m, _ = tiny
    with pytest.raises(Exception, match="slot"):
        GenerationEngine(m, batch_size=2, prompt_buckets=[16],
                         kv_page_size=8, speculative_k=2, eos_token_id=None,
                         name="kex-spec")


# -- the shares of one expert layer -----------------------------------------------
def test_eight_shares_of_the_experts_sum_to_the_uncut_reference_layer():
    """``held=(16 i, 16)`` for i in 0..7 of one 128-expert layer, the
    shared expert counted once, against the reference's layer holding all
    128."""
    D, F, E, k = 32, 16, 128, 8
    kw = dict(hidden_size=D, expert_width=F, num_experts=E, top_k=k,
              shared_experts=1, routed_scale=2.5, norm_topk=True,
              router="sigmoid")
    whole = DroplessMoE(**kw)
    rng = np.random.default_rng(3)
    for p in whole.parameters():
        p.value = jnp.asarray(0.3 * rng.standard_normal(p.shape),
                              jnp.float32)
    x = jnp.asarray(rng.standard_normal((24, D)), jnp.float32)
    names = ("expert_gate", "expert_up", "expert_down")
    w = {"mlp." + n: p.value for n, p in whole.named_parameters()}
    cfg = {"num_experts_per_tok": k, "routed_scaling_factor": 2.5,
           "norm_topk_prob": 1, "expert_offset": 0}
    want = np.asarray(ref.moe(x, w, cfg, "f32"))
    zero = {"mlp.shared_" + n: jnp.zeros_like(w["mlp.shared_" + n])
            for n in ("gate", "up", "down")}
    shared = np.asarray(ref.moe(x, {**w, **zero, **{
        "mlp." + n: w["mlp." + n][:1] * 0 for n in names}}, cfg, "f32"))
    assert np.abs(shared).max() == 0.0
    shared = want - np.asarray(ref.moe(x, {**w, **zero}, cfg, "f32"))
    total = np.zeros_like(want)
    for i in range(8):
        part = DroplessMoE(held=(16 * i, 16), **kw)
        for n, p in part.named_parameters():
            full = dict(whole.named_parameters())[n].value
            p.value = full[16 * i:16 * (i + 1)] if n in names else full
        total += np.asarray(part(x)) - shared
        # the reference's own share agrees with the program's
        ref_part = np.asarray(ref.moe(
            x, {**w, **{"mlp." + n: w["mlp." + n][16 * i:16 * (i + 1)]
                        for n in names}},
            {**cfg, "expert_offset": 16 * i}, "f32"))
        assert np.abs(np.asarray(part(x)) - ref_part).max() < F32_TOL
    assert np.abs(total + shared - want).max() < F32_TOL
    assert np.abs(want).max() > 0.5 and np.abs(shared).max() > 0.05


# -- the two kernels against their XLA paths ---------------------------------------
def _window_reference(q, k, v, window):
    rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
    t = jnp.arange(q.shape[2])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(pa.key_visible(t[None, :], t[:, None], window), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("T,window,block", [
    (256, 128, 128), (256, 128, 256), (512, 128, 512),   # the candidates
    (200, 128, 128),      # T no multiple of the block
    (72, 128, 128),       # T < W
    (300, 100, 128),      # W no multiple of the block
    (300, 48, 64), (384, 200, 128), (200, 128, None)])
def test_windowed_flash_forward_matches_masked_attention(T, window, block):
    ks = jax.random.split(jax.random.PRNGKey(T + window), 3)
    q = jax.random.normal(ks[0], (2, 4, T, 32), jnp.float32)
    k, v = (jax.random.normal(key, (2, 2, T, 32), jnp.float32)
            for key in ks[1:])
    got = flash_attention(q, k, v, causal=True, window=window, block_q=block)
    assert np.abs(np.asarray(got - _window_reference(q, k, v, window))
                  ).max() < 2e-6


def test_windowed_flash_visits_only_the_band_and_refuses_other_shapes():
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    qi, ki, first = fa._band_table(32, 128, 128)     # 4096 tokens, W 128
    assert len(qi) == 2 * 32 - 1                     # not 32 x 33 / 2
    assert (ki <= qi).all() and (qi - ki <= 1).all()
    assert first.sum() == 32
    qi, ki, _ = fa._band_table(8, 512, 128)
    assert len(qi) == 2 * 8 - 1
    q = jnp.zeros((1, 2, 16, 8))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q[:, :, :8], q[:, :, :8], causal=True, window=4)


@pytest.mark.parametrize("tm,pairs", [(16, 40), (128, 700)])
@pytest.mark.parametrize("block_f", [128, 256])
def test_width_tiled_expert_kernel_matches_the_ragged_dot_path(tm, pairs,
                                                               block_f):
    E, D, F = 4, 128, 512
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    ids = jax.random.randint(ks[0], (pairs,), 0, E + 2) - 1   # some absent
    lay = gm.ragged_layout(ids, E, tm, partial=True)
    xs = jax.random.normal(ks[1], (lay["tiles"] * tm, D), jnp.float32)
    wg, wu = (0.1 * jax.random.normal(key, (E, D, F), jnp.float32)
              for key in ks[2:4])
    wd = 0.1 * jax.random.normal(ks[4], (E, F, D), jnp.float32)
    used = int(lay["used"][0]) * tm
    want = gm.ragged_gated_mlp(xs, wg, wu, wd, lay, kernel=False)
    got = gm._gated_mlp_wide(xs, wg, wu, wd, lay, block_f)
    assert used > 0 and np.abs(np.asarray(want[:used])).max() > 1.0
    assert np.abs(np.asarray(got[:used] - want[:used])).max() < 2e-5


def test_an_expert_that_fits_takes_the_whole_expert_kernel(monkeypatch):
    """The gate between the two kernels is the call's VMEM need against the
    cap: the two expert shapes the benchmark had fit, this one does not."""
    item = 2
    for D, F, fits in ((2048, 768, True), (2048, 512, True),
                       (6144, 2048, False)):
        for tm in (16, 128):
            need = gm._gated_mlp_vmem(tm, D, F, item)
            assert (need * 5 // 4 <= gm._VMEM_CAP) == fits
    assert gm._wide_blocks(16, 6144, 2048, item) == [1024, 512, 256]
    assert gm._wide_blocks(128, 6144, 2048, item) == [512, 256]
    calls = []
    monkeypatch.setattr(gm, "_gated_mlp_wide",
                        lambda *a: calls.append(a[-1]) or a[0])
    lay = gm.ragged_layout(jnp.zeros((16,), jnp.int32), 2, 16)
    xs = jnp.zeros((lay["tiles"] * 16, 128), jnp.float32)
    w = jnp.zeros((2, 128, 256), jnp.float32)
    gm.ragged_gated_mlp(xs, w, w, w.transpose(0, 2, 1), lay, kernel=True)
    assert calls == []
    monkeypatch.setattr(gm, "_VMEM_CAP", 0)
    gm.ragged_gated_mlp(xs, w, w, w.transpose(0, 2, 1), lay, kernel=True)
    assert calls == [128]


# -- planted faults: each must fail the comparison ------------------------------
def _no_window(kp, qp, window):
    return REAL_VISIBLE(kp, qp, 1 << 30)


def _one_short(kp, qp, window):
    return REAL_VISIBLE(kp, qp, window - 1)


REAL_VISIBLE = pa.key_visible


@pytest.mark.parametrize("fault", ["no_window", "window_one_short",
                                   "global_layers_rotated",
                                   "window_layers_not_rotated"])
def test_a_planted_fault_fails_the_comparison(monkeypatch, fault):
    cfg = tiny_cfg()
    if fault == "no_window":
        monkeypatch.setattr(hybrid, "key_visible", _no_window)
    elif fault == "window_one_short":
        monkeypatch.setattr(hybrid, "key_visible", _one_short)
    m, w = build(cfg)
    if fault == "global_layers_rotated":
        for blk in m.model.blocks:
            blk.mixer.rope_theta = m.cfg.rope_theta
    elif fault == "window_layers_not_rotated":
        for blk in m.model.blocks:
            blk.mixer.rope_theta = None
    ids = ids_of()
    gap = np.abs(np.asarray(m(ids)) - ref_logits(w, ids, cfg))
    assert gap.mean() > BF16_MEAN, (fault, gap.mean())
