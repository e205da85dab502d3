"""``models/kimi_linear.py`` (three Kimi-Delta-Attention layers with a
per-channel decay to one NoPE latent-attention layer, a dense first FFN and
expert layers that HOLD A SHARE of their experts behind a sigmoid router, an
ungated shared expert) against the ONE plain reference,
``benchmarks/reference/kimi_linear.py``: tiny widths, seeded weights, CPU.

Tolerances.  With float32 parameters the program (chunked scan, slot state,
latent pages, absorbed decode, sorted expert rows) and the reference (a
token-by-token recurrence, expanded attention, a loop over the held
experts) compute the same float32 function by two routes: logits of
magnitude up to 0.67 agreed to 9.2e-7 over these shapes and three seeds, so
3e-5.  With bfloat16 parameters the program rounds every projection to
bfloat16 where the reference keeps float32: 0.0030 was the widest MEAN
logit gap over three seeds, so 0.01 (3 x); the widest single gap is a
routing flip's (a sigmoid top-4 of 16 whose fourth and fifth scores round
apart), so 0.6 and the mean is the limit that tells.  Each planted fault in
a KDA layer moves the MEAN logit by more than that bfloat16 mean tolerance
in float32, so the comparison fails it whichever type the parameters have;
the one in the latent layer (one mixer of four, whose softmax over scores
of N(0, 0.02) weights is nearly flat) moves it by 0.005, 170 x the float32
tolerance.
``rms_norm_eps`` is the preset's 1e-12 (``families/olmo_hybrid.py:TINY``
says why), so no norm hides a layer.
"""
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
from conftest import LATENT_WALK_CASES  # noqa: E402

from paddle_tpu.models import kimi_linear as kl  # noqa: E402
from paddle_tpu.models.latent_moe import LatentAttention  # noqa: E402
from paddle_tpu.moe import DroplessMoE  # noqa: E402
from paddle_tpu.serving import GenerationEngine  # noqa: E402

fam = loader.load_module("families", "kimi_linear")
ref = loader.load_module("reference", "kimi_linear")
with open(os.path.join(REPO, "benchmarks", "configs",
                       "kimi_linear_serve.json")) as _f:
    PUBLISHED = json.load(_f)

F32_TOL = 3e-5
BF16_MAX, BF16_MEAN = 0.6, 0.01


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
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert set(c["reduced"]) == set(c["published"]) == set(c["reduced_why"])
    assert (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts_per_token"], c["q_lora_rank"]) == (
                2304, 32, 512, 128, 64, 128, 9216, 1024, 8, None)
    lin = c["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == list(
        range(1, 28))
    assert fam.layer_types(c) == ("kda", "kda", "kda", "mla") * 2
    assert fam.ffn_types(c) == ("dense",) + ("moe",) * 7
    assert fam.held(c) == (0, 64) and fam.router_width(c) == 256
    spec = fam.param_spec(c)
    params = sum(int(np.prod(s)) for s, _, _ in spec.values())
    assert 7.5e9 < 2 * params < 7.6e9     # bfloat16 bytes
    kda = sum(int(np.prod(s)) for n, (s, _, _) in spec.items()
              if n.startswith("model.blocks.0.mixer."))
    mla = sum(int(np.prod(s)) for n, (s, _, _) in spec.items()
              if n.startswith("model.blocks.3.mixer."))
    assert round(kda / 1e6, 1) == 39.5 and round(mla / 1e6, 1) == 29.1
    mc = fam.model_config(c)
    assert mc.q_lora_rank is None and mc.rope_theta is None
    assert mc.page_width == 640 and mc.latent_width == 576
    assert mc.moe["held"] == (0, 64) and mc.moe["num_experts"] == 256
    assert mc.moe["routed_scale"] == 2.446 and mc.conv_width == 12288


def test_config_refuses_kinds_it_does_not_know_and_experts_without_a_layer():
    base = dict(vocab_size=32, hidden_size=16, intermediate_size=32,
                num_heads=2, kv_lora_rank=8, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8, linear_num_heads=2,
                linear_head_dim=8)
    with pytest.raises(Exception, match="layer_types"):
        kl.KimiLinearConfig(layer_types=["linear_attention"],
                            ffn_types=["dense"], **base)
    with pytest.raises(Exception, match="moe"):
        kl.KimiLinearConfig(layer_types=["kda"], ffn_types=["moe"], **base)
    with pytest.raises(Exception, match="moe"):
        kl.KimiLinearConfig(layer_types=["kda"], ffn_types=["dense"],
                            moe={"num_experts": 4}, **base)


# -- the model against the reference -----------------------------------------
@pytest.mark.parametrize("dtype,widest,mean", [
    ("float32", F32_TOL, F32_TOL), ("bfloat16", BF16_MAX, BF16_MEAN)])
def test_full_forward_logits_match_the_reference(dtype, widest, mean):
    cfg = tiny_cfg(dtype)
    m, w = build(cfg)
    ids = ids_of()
    got, want = np.asarray(m(ids)), ref_logits(w, ids, cfg)
    assert got.dtype == np.float32 and np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < widest
    assert np.abs(got - want).mean() < mean


def paged_logits(m, ids, plen, C, page, bucket, cache=None):
    """Admit ``plen`` tokens of each row in one ``[B, bucket]`` call, then
    decode the rest one token a call (teacher-forced) through a shuffled
    page table and the slots' states.  Returns ({position: logits [B, V]
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
    (2, 9),      # shorter than the conv's taps
    (13, 20), (27, 40),
    (64, 70)])   # a whole chunk exactly (the tiny chunk is still 64)
def test_prefill_then_decode_through_state_and_latent_pages_matches_full_forward(
        tiny, plen, total):
    m, w = tiny
    cfg = tiny_cfg(cache_len=128)
    ids = ids_of((3, total), seed=plen)
    got, cache = paged_logits(m, ids, plen, 128, 8, 64)
    want = ref_logits(w, ids, cfg)
    for p, lg in got.items():
        assert np.abs(lg - want[:, p]).max() < F32_TOL, p
    assert [sorted(kv) for kv in cache["layers"]] == [
        ["conv", "state"]] * 3 + [["latent"]]
    assert cache["layers"][0]["state"].shape == (3 + 1, 4, 16, 16)
    assert cache["layers"][0]["conv"].shape == (3 + 1, 3, 3 * 4 * 16)
    # [P + 1, page, 128]: the 40 used lanes (32 + 8) in one 128-lane tile
    assert cache["layers"][3]["latent"].shape == (3 * 16 + 1, 8, 128)


def test_a_second_tenant_starts_from_the_zero_state_in_reused_pages(tiny):
    """Slots and pages a long tenant left full: the short one that follows
    is admitted from the zero state, its pages overwritten where it writes
    and masked where it does not."""
    m, w = tiny
    cfg = tiny_cfg(cache_len=128)
    long_ids = ids_of((2, 50), seed=8)
    _, cache = paged_logits(m, long_ids, 45, 128, 8, 64)
    assert float(jnp.abs(cache["layers"][0]["state"][:2]).max()) > 1e-3
    short = ids_of((2, 12), seed=9)
    got, _ = paged_logits(m, short, 3, 128, 8, 64, cache=cache)
    want = ref_logits(w, short, cfg)
    for p, lg in got.items():
        assert np.abs(lg - want[:, p]).max() < F32_TOL, p


def test_an_inert_row_and_a_free_slot_leave_state_window_and_pages(tiny):
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
        for name in ("state", "conv"):
            assert not np.array_equal(new[name][2], old[name][2])
            for other in (0, 1, 3):
                assert np.array_equal(new[name][other], old[name][other])
    old, new = before["layers"][3]["latent"], after["layers"][3]["latent"]
    changed = np.any(np.asarray(new != old), axis=(1, 2))
    assert changed[:32].nonzero()[0].tolist() == [5, 9]
    # a decode step: slots 1 and 3 are free
    ids1 = np.array([[7], [0], [9], [0]], np.int32)
    pos1 = np.array([[3], [-1], [5], [-1]], np.int32)
    pm = np.full((B, G * page), -1, np.int32)
    tab = np.full((B, G), -1, np.int32)
    for b, n in ((0, 4), (2, 6)):
        pm[b, :n], tab[b, 0] = np.arange(n), b
    _, after = m.forward_paged(ids1, pos1, pm, tab, before)
    for old, new in zip(before["layers"][:3], after["layers"][:3]):
        for name in ("state", "conv"):
            for free in (1, 3, 4):
                assert np.array_equal(new[name][free], old[name][free])
            for live in (0, 2):
                assert not np.array_equal(new[name][live], old[name][live])


def test_copy_pages_copies_the_latent_pools_and_nothing_else(tiny):
    m, _ = tiny
    cache = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(2), a.shape,
                                    jnp.float32).astype(a.dtype),
        m.init_paged_cache(8, 8, slots=2))
    out = m.copy_pages(cache, np.array([1, 3, -1], np.int32),
                       np.array([6, 2, -1], np.int32))
    for old, new in zip(cache["layers"][:3], out["layers"][:3]):
        assert all(np.array_equal(new[n], old[n]) for n in ("state", "conv"))
    old, new = cache["layers"][3]["latent"], out["layers"][3]["latent"]
    assert np.array_equal(new[6], old[1]) and np.array_equal(new[2], old[3])
    for page in (0, 1, 3, 4, 5, 7):
        assert np.array_equal(new[page], old[page])


def test_through_the_engine_state_latent_pages_and_experts_in_one_loop(tiny):
    """Admission ([R, bucket] prompts, some shorter than the conv's taps,
    some past a chunk), then one token a step; 10 requests on 4 slots, so
    slots and pages are reused by a second tenant.  Every served token is
    the reference's own argmax of a full causal forward pass over its
    history, to the float32 tolerance."""
    m, w = tiny
    cfg = tiny_cfg(cache_len=128)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, size=n).astype(np.int32)
               for n in (5, 16, 20, 31, 9, 12, 2, 1, 27, 3)]
    eng = GenerationEngine(m, batch_size=4, prompt_buckets=[16, 32],
                           kv_page_size=8, speculative_k=0,
                           eos_token_id=None, name="kml")
    try:
        warm = eng.warmup()
        futures = [eng.submit(p, 12) for p in prompts]
        outs = [np.asarray(f.result(timeout=300)).tolist() for f in futures]
        assert eng.compile_count == warm
        st = eng.stats()
        assert st["admitted"] == 10 and st["state_slots_reset"] == 10
        # 3 KDA layers: 4 heads of 16 x 16 float32 and 3 window rows of
        # 3 x 4 x 16 values, float32 here
        assert m.slot_state_bytes() == 3 * (4 * 4 * 16 * 16 + 4 * 3 * 192)
        assert st["state_bytes_steps"] == (
            st["decode_steps"] * 2 * 4 * m.slot_state_bytes())
        assert st["gdn_prefill_tokens"] == sum(map(len, prompts))
        # every decode step routes 4 slots x 4 choices in each of 3 expert
        # layers; the 4 held of the router's 16 experts see about a quarter
        assert st["moe_pairs_routed"] == st["moe_layer_steps"] * 4 * 4 > 0
        assert st["moe_pairs_local"] == st["moe_routed_tokens"]
        assert 0.1 < st["moe_pairs_local"] / st["moe_pairs_routed"] < 0.45
        assert eng.expert_counts().shape == (4,) == (m.moe_experts,)
        texts = eng.compiled_programs()
        assert set(texts) == {"step", "admit[16]", "admit[32]"}
        assert all("/kda/" in t and "/mla/" in t and "/moe/" in t
                   for t in texts.values())
    finally:
        eng.close()
    assert all(len(o) == 12 for o in outs)
    gaps = ref.served_token_gaps(w, cfg, prompts, outs)
    assert max(g["gap"].max() for g in gaps) < F32_TOL


@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", LATENT_WALK_CASES)
def test_nope_latent_decode_kernel_against_absorbed_over_a_gathered_view(
        case, dtype, tol, latent_walk_check):
    """The NoPE latent layer (no bottleneck, nothing rotated, 4 heads
    padded to a sublane tile of rows): ``latent_decode`` walking the pool
    against ``absorbed`` over the gathered view, as a share of the layer's
    largest output (float32 8e-7 at the widest; bfloat16 one ulp, 0.5 %:
    the output is rounded to 8 bits of mantissa on both sides)."""
    m, _ = build(tiny_cfg(dtype))
    latent_walk_check(m.model.blocks[3].mixer, case, tol)


def test_through_the_engine_the_page_walk_serves_the_gather_paths_tokens(
        tiny, latent_walk_serves_the_same):
    rng = np.random.default_rng(0)
    latent_walk_serves_the_same(
        tiny[0], [rng.integers(1, 512, size=n).astype(np.int32)
                  for n in (5, 16, 20, 31, 9, 12, 2)], 8)


@pytest.mark.parametrize("kw,why", [
    ({"speculative_k": 2}, "speculative_k"), ({"role": "prefill"}, "role"),
    ({"role": "decode"}, "role"), ({"quantized": "int8"}, "quantized")])
def test_what_slot_state_refuses_is_refused_for_this_model_by_name(tiny, kw,
                                                                   why):
    m, _ = tiny
    with pytest.raises(Exception, match=why) as e:
        GenerationEngine(m, batch_size=2, prompt_buckets=[16],
                         kv_page_size=8, eos_token_id=None, name="kml-no",
                         **{"speculative_k": 0, **kw})
    assert "KimiLinearForCausalLM" in str(e.value)


def test_a_prefix_key_is_served_cold_and_a_handoff_refused(tiny):
    m, _ = tiny
    eng = GenerationEngine(m, batch_size=2, prompt_buckets=[16],
                           kv_page_size=8, speculative_k=0,
                           eos_token_id=None, name="kml-prefix")
    try:
        eng.warmup()
        p = ids_of((12,), seed=3)
        a = eng.submit(p, 4, prefix_key="doc", prefix_len=8).result(60)
        b = eng.submit(p, 4, prefix_key="doc", prefix_len=8).result(60)
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert eng.stats()["prefix_unshared"] == 2
        with pytest.raises(Exception, match="handoff"):
            eng.submit(p, 4, handoff=True)
    finally:
        eng.close()


# -- the shares of one expert layer -----------------------------------------------
def test_four_shares_of_the_experts_sum_to_the_uncut_reference_layer():
    """``held=(64 i, 64)`` for i in 0..3 of one 256-expert layer, the shared
    expert counted once, against the reference's layer holding all 256."""
    D, F, E, k = 32, 16, 256, 8
    kw = dict(hidden_size=D, expert_width=F, num_experts=E, top_k=k,
              shared_experts=1, routed_scale=2.446, norm_topk=True,
              router="sigmoid")
    whole = DroplessMoE(**kw)
    rng = np.random.default_rng(3)
    for p in whole.parameters():
        p.value = jnp.asarray(0.3 * rng.standard_normal(p.shape),
                              jnp.float32)
    x = jnp.asarray(rng.standard_normal((24, D)), jnp.float32)
    names = ("expert_gate", "expert_up", "expert_down")
    w = {"mlp." + n: p.value for n, p in whole.named_parameters()}
    cfg = {"num_experts_per_token": k, "routed_scaling_factor": 2.446,
           "moe_renormalize": 1, "expert_offset": 0}
    want = np.asarray(ref.moe(x, w, cfg, "f32"))
    zero = {"mlp.shared_" + n: jnp.zeros_like(w["mlp.shared_" + n])
            for n in ("gate", "up", "down")}
    shared = want - np.asarray(ref.moe(x, {**w, **zero}, cfg, "f32"))
    total = np.zeros_like(want)
    for i in range(4):
        part = DroplessMoE(held=(64 * i, 64), **kw)
        for n, p in part.named_parameters():
            full = dict(whole.named_parameters())[n].value
            p.value = full[64 * i:64 * (i + 1)] if n in names else full
        total += np.asarray(part(x)) - shared
        # the reference's own share agrees with the program's
        ref_part = np.asarray(ref.moe(
            x, {**w, **{"mlp." + n: w["mlp." + n][64 * i:64 * (i + 1)]
                        for n in names}},
            {**cfg, "expert_offset": 64 * i}, "f32"))
        assert np.abs(np.asarray(part(x)) - ref_part).max() < F32_TOL
    assert np.abs(total + shared - want).max() < F32_TOL
    assert np.abs(want).max() > 0.5 and np.abs(shared).max() > 0.05


# -- the latent layer's two options leave the latent model what it was -----------
def test_latent_attention_without_bottleneck_or_rotation_has_its_own_leaves():
    mc = fam.model_config(tiny_cfg())
    names = {n for n, _ in LatentAttention(mc).named_parameters()}
    assert names == {"q", "kv_a", "kv_norm.weight", "kv_b", "out"}
    joy = loader.load_module("families", "joyai_flash")
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "joyai_flash_serve.json")) as f:
        jc = joy.model_config({**json.load(f), **joy.TINY,
                               "param_dtype": "float32",
                               "serve": {"cache_len": 64}})
    names = {n for n, _ in LatentAttention(jc).named_parameters()}
    assert names == {"q_a", "q_norm.weight", "q_b", "kv_a", "kv_norm.weight",
                     "kv_b", "out"}


# -- planted faults: each must fail the comparison ------------------------------
def _one_decay_a_head(self, x, valid):
    g, beta = REAL_GATES(self, x, valid)
    return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta


def _beta_doubled(self, x, valid):
    g, beta = REAL_GATES(self, x, valid)
    return g, 2.0 * beta


REAL_GATES = kl.KimiDeltaAttention._gates


@pytest.mark.parametrize("fault,least", [
    ("one_decay_a_head", BF16_MEAN), ("beta_doubled", BF16_MEAN),
    ("latent_gain_doubled", 100 * F32_TOL), ("output_gate_silu", BF16_MEAN)])
def test_a_planted_fault_fails_the_comparison(monkeypatch, fault, least):
    cfg = tiny_cfg()
    if fault == "one_decay_a_head":
        monkeypatch.setattr(kl.KimiDeltaAttention, "_gates",
                            _one_decay_a_head)
    elif fault == "beta_doubled":
        monkeypatch.setattr(kl.KimiDeltaAttention, "_gates", _beta_doubled)
    elif fault == "output_gate_silu":
        monkeypatch.setattr(jax.nn, "sigmoid", jax.nn.silu)
    m, w = build(cfg)
    if fault == "latent_gain_doubled":
        gain = m.model.blocks[3].mixer.kv_norm.weight
        gain.value = 2.0 * gain.value
    ids = ids_of()
    gap = np.abs(np.asarray(m(ids)) - ref_logits(w, ids, cfg))
    assert gap.mean() > least, (fault, gap.mean())
