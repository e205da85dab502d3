"""The hybrid stack under the ``qwen3_next`` family's options (pre-norm blocks
with zero-centred gains, grouped K/V heads with per-head QK-norm, partial
rotate-half rotary and an output gate, 2 value heads a key head in the delta
rule, every FFN an expert layer that HOLDS A SHARE of its experts behind a
softmax router, a gated shared expert) against the ONE plain reference,
``benchmarks/reference/qwen3_next.py``: tiny widths, seeded weights, CPU.

Tolerances.  With float32 parameters the program (chunked WY form, fused
projections, sorted expert rows) and the reference (token-by-token
recurrence, a loop over the held experts) compute the same float32 function
by two routes: logits of magnitude up to 0.64 agreed to 2e-6 over these
shapes, so 2e-5.  With bfloat16 parameters the program rounds every
projection to bfloat16 where the reference keeps float32: 0.0122 was the
widest logit gap and 0.00122 the mean at this seed, so 0.04 and 0.004 (3 x).
Each planted fault moves the MEAN logit by more than that bfloat16 mean
tolerance in float32 (the smallest seen: 0.0056), so the comparison fails
it whichever type the parameters have.
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

from paddle_tpu import nn  # noqa: E402
from paddle_tpu.models import hybrid  # noqa: E402
from paddle_tpu.moe import DroplessMoE  # noqa: E402
from paddle_tpu.ops import gated_delta as gd  # noqa: E402
from paddle_tpu.ops import paged_attention as pa  # noqa: E402
from paddle_tpu.serving import GenerationEngine  # noqa: E402

# ``paddle_tpu.ops.grouped_matmul`` the attribute is the function of that name
gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")
fam = loader.load_module("families", "qwen3_next")
ref = loader.load_module("reference", "qwen3_next")
with open(os.path.join(REPO, "benchmarks", "configs",
                       "qwen3_next_serve.json")) as _f:
    PUBLISHED = json.load(_f)

F32_TOL = 2e-5
BF16_MAX, BF16_MEAN = 0.04, 0.004


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


def test_prefill_then_decode_through_pages_and_state_matches_full_forward(
        tiny):
    """Through the engine: admission ([2, bucket] prompts through the flash
    / gather path and the chunked scan), then one token a step through the
    K/V pages of 2 heads and the slots' states; every served token is the
    reference's own argmax of a full causal forward pass over its history,
    to the float32 tolerance.  Slot state AND experts in one engine."""
    m, w = tiny
    cfg = tiny_cfg(cache_len=128)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, size=n).astype(np.int32)
               for n in (5, 16, 20, 31, 9, 12, 2, 1)]
    eng = GenerationEngine(m, batch_size=4, prompt_buckets=[16, 32],
                           kv_page_size=8, speculative_k=0,
                           eos_token_id=None, name="qnx")
    try:
        warm = eng.warmup()
        futures = [eng.submit(p, 6) for p in prompts]
        outs = [np.asarray(f.result(timeout=300)).tolist() for f in futures]
        assert eng.compile_count == warm
        st = eng.stats()
        assert st["state_slots_reset"] == st["admitted"] == 8
        assert st["state_bytes_steps"] == (
            st["decode_steps"] * 2 * 4 * m.slot_state_bytes())
        # 3 linear layers: a [4, 8, 8] float32 state and 3 rows of the
        # (2 x 2 x 8 + 4 x 8)-wide conv input, float32 here
        assert m.slot_state_bytes() == 3 * (4 * 4 * 8 * 8 + 4 * 3 * 64)
        # every decode step routes 4 slots x 4 choices in each of 4 layers;
        # the 8 held of the router's 32 experts see about a quarter
        assert st["moe_pairs_routed"] == (
            st["moe_layer_steps"] * 4 * 4) > 0
        assert st["moe_pairs_local"] == st["moe_routed_tokens"]
        assert 0.1 < st["moe_pairs_local"] / st["moe_pairs_routed"] < 0.45
        assert eng.expert_counts().shape == (8,) == (m.moe_experts,)
        assert eng.expert_counts().sum() == st["moe_routed_tokens"]
        texts = eng.compiled_programs()
        assert set(texts) == {"step", "admit[16]", "admit[32]"}
        assert all("/gdn/" in t and "/attn/" in t and "/moe/" in t
                   for t in texts.values())
    finally:
        eng.close()
    assert all(len(o) == 6 for o in outs)
    gaps = ref.served_token_gaps(w, cfg, prompts, outs)
    assert max(g["gap"].max() for g in gaps) < F32_TOL


# -- the shares add up ---------------------------------------------------------
def _moe_weights(E=128, D=64, F=32, seed=0):
    rng = np.random.default_rng(seed)

    def n(*shape, std=0.3):
        return jnp.asarray(rng.normal(0, std, shape), jnp.float32)

    return {"mlp.router": n(D, E, std=1.0), "mlp.expert_gate": n(E, D, F),
            "mlp.expert_up": n(E, D, F), "mlp.expert_down": n(E, F, D),
            "mlp.shared_gate": n(D, F), "mlp.shared_up": n(D, F),
            "mlp.shared_down": n(F, D), "mlp.shared_gating": n(D, 1)}


def _share(w, held, k=10, shared=True):
    E, D, F = w["mlp.expert_gate"].shape
    layer = DroplessMoE(D, F, E, k, router="softmax", held=held,
                        shared_gated=shared, shared_experts=int(shared))
    first, count = held or (0, E)
    for name, p in layer.named_parameters():
        v = w["mlp." + name]
        p.value = v[first:first + count] if name.startswith("expert_") else v
    layer.eval()
    return layer


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Four chips hold experts 0-31, 32-63, 64-95, 96-127 of one 128-expert
    layer; what each computes for the tokens routed to ITS experts, with the
    shared expert (which every chip computes alike) counted once, adds up to
    the reference's whole layer.  5e-5: float32 sums in two orders, outputs
    of magnitude 6."""
    w = _moe_weights()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(48, 64)),
                    jnp.float32)
    cfg = {"num_experts_per_tok": 10, "expert_offset": 0,
           "norm_topk_prob": 1}
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.moe(x, w, cfg, "f32"))
        parts = [np.asarray(_share(w, (32 * j, 32), shared=j == 0)(x))
                 for j in range(4)]
        uncut = np.asarray(_share(w, None)(x))
    assert np.abs(whole).max() > 1.0
    assert np.abs(sum(parts) - whole).max() < 5e-5
    assert np.abs(uncut - whole).max() < 5e-5
    # and no share is the whole: each leaves out what it does not hold
    assert all(np.abs(p - whole).max() > 0.5 for p in parts)
    # the reference given a share leaves out the same
    for j in (0, 3):
        wj = {k: (v[32 * j:32 * j + 32] if k.startswith("mlp.expert_")
                  else v) for k, v in w.items()}
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.moe(x, wj, {**cfg, "expert_offset": 32 * j},
                                      "f32"))
            got = np.asarray(_share(w, (32 * j, 32))(x))
        assert np.abs(got - want).max() < 5e-5


# -- each part against its plain form -----------------------------------------
def test_the_softmax_router_chooses_and_weighs_as_the_reference():
    w = _moe_weights()
    x = jnp.asarray(0.1 * np.random.default_rng(2).normal(size=(64, 64)),
                    jnp.float32)
    layer = _share(w, (0, 32))
    ids, wt = layer.route(x)
    want_ids, want_wt = ref.route(x, w, {"num_experts_per_tok": 10,
                                         "norm_topk_prob": 1})
    # ties-free: the tenth and eleventh logits differ
    logit = np.sort(np.asarray(x @ w["mlp.router"]), -1)
    assert (logit[:, -10] - logit[:, -11]).min() > 1e-5
    assert np.array_equal(ids, want_ids) and ids.max() > 32  # all 128 routed
    assert np.abs(np.asarray(wt) - np.asarray(want_wt)).max() < 1e-6
    assert np.abs(np.asarray(wt).sum(-1) - 1).max() < 1e-6
    layer.norm_topk = False
    assert np.asarray(layer.route(x)[1]).sum(-1).max() < 0.999


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_a_pair_whose_expert_is_absent_costs_no_tile(kernel):
    """Experts 8-15 of 32 are held: the layout gives rows to their pairs
    only, tile by tile as a layer of 8 experts would, and says which pairs
    have one; with no pair here no tile is in use and the result is the
    shared expert's alone, finite."""
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, 32, size=96), jnp.int32)
    tm = 16
    lay = gm.ragged_layout(ids - 8, 8, tm, partial=True)
    here = (np.asarray(ids) >= 8) & (np.asarray(ids) < 16)
    counts = np.bincount(np.asarray(ids)[here] - 8, minlength=8)
    assert np.array_equal(lay["counts"], counts)
    assert np.array_equal(lay["present"], here)
    assert int(lay["used"][0]) == int(np.ceil(counts / tm).sum())
    assert lay["tiles"] == gm.ragged_tiles(96, 8, tm)   # the static bound
    dest = np.asarray(lay["dest"])
    assert (dest[~here] == lay["tiles"] * tm).all()
    assert len(set(dest[here])) == here.sum()
    assert (dest[here] < int(lay["used"][0]) * tm).all()
    # whole, the same ids are a plain layout over 8 groups
    whole = gm.ragged_layout(jnp.asarray(np.asarray(ids)[here] - 8), 8, tm)
    assert np.array_equal(np.asarray(whole["dest"]), dest[here])

    # through the layer, against the per-expert loop of the reference
    w = _moe_weights(E=32, seed=4)
    x = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    layer = _share(w, (8, 8), k=4)
    real = gm.ragged_gated_mlp
    cfg = {"num_experts_per_tok": 4, "expert_offset": 8, "norm_topk_prob": 1}
    wj = {k: (v[8:16] if k.startswith("mlp.expert_") else v)
          for k, v in w.items()}
    try:
        gm.ragged_gated_mlp = lambda *a, **kw: real(*a, kernel=kernel)
        with jax.default_matmul_precision("highest"):
            got = np.asarray(layer(x))
            want = np.asarray(ref.moe(x, wj, cfg, "f32"))
            assert np.abs(got - want).max() < 5e-5
            # nobody routes here: experts 24-31 of a router that never
            # picks them (their logit is -100 x a positive sum)
            w2, xp = dict(w), jnp.abs(x)
            w2["mlp.router"] = w["mlp.router"].at[:, 24:].set(-100.0)
            assert int(_share(w2, (24, 8), k=4).route(xp)[0].max()) < 24
            out = np.asarray(_share(w2, (24, 8), k=4)(xp))
            shared = np.asarray(_share(w2, (0, 8), k=4)(xp)) - np.asarray(
                _share(w2, (0, 8), k=4, shared=False)(xp))
        assert np.isfinite(out).all()
        assert np.abs(out - shared).max() < 5e-5
    finally:
        gm.ragged_gated_mlp = real


def _paged_case(B, H, Hkv, hd, T, page=8, G=6, seed=0):
    rng = np.random.default_rng(seed)
    P = B * G
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q, kp, vp = f(B, H, T, hd), f(P + 1, page, Hkv * hd), f(P + 1, page,
                                                           Hkv * hd)
    tab = np.full((B, G), -1, np.int32)
    pm = np.full((B, G * page), -1, np.int32)
    pos = np.full((B, T), -1, np.int32)
    for b, n in enumerate([19, 33, 5, 0][:B]):
        if n == 0:
            continue                                   # a free slot
        tab[b, :-(-n // page)] = rng.permutation(P)[:-(-n // page)]
        pm[b, :n] = np.arange(n)
        pos[b] = np.arange(n - T, n)
    return q, kp, vp, tab, pm, pos


@pytest.mark.parametrize("T", [1, 3])
def test_grouped_heads_in_paged_decode_and_in_the_gather_path(T):
    """8 query heads over 2 K/V heads: the kernel (interpret mode; the
    heads-as-rows form at T = 1, the rep x T query rows a K/V head at T = 3)
    and the gather path read the 2-head pool; both equal plain multi-head
    attention over a pool whose heads were repeated."""
    B, H, Hkv, hd, page = 3, 8, 2, 16, 8
    q, kp, vp, tab, pm, pos = _paged_case(B, H, Hkv, hd, T)
    C = pm.shape[1]
    mask = pa.key_visible(pm[:, None, :], pos[:, :, None], C)
    gtab = jnp.maximum(tab, 0)

    def mha(pool):   # [P+1, page, Hkv*hd] -> [P+1, page, H*hd], repeated
        t = pool.reshape(*pool.shape[:2], Hkv, hd)
        return jnp.repeat(t, H // Hkv, axis=2).reshape(*pool.shape[:2], -1)

    want = np.asarray(pa.paged_attention(q, mha(kp), mha(vp), gtab, mask))
    live = (pos >= 0)[:, None, :, None]
    gather = np.asarray(pa.paged_attention(q, kp, vp, gtab, mask))
    assert np.abs(np.where(live, gather - want, 0)).max() < 1e-5
    bound = pa.sweep_bound(np.asarray(mask), page)
    kernel = np.asarray(pa.paged_flash_decode(
        q, kp, vp, gtab, jnp.asarray(pm), jnp.asarray(pos),
        jnp.asarray(bound)))
    assert np.abs(np.where(live, kernel - want, 0)).max() < 1e-5
    assert not np.where(live, 0, kernel).any()   # rows that see nothing: 0
    with pytest.raises(Exception, match="K/V heads"):
        pa.paged_attention(q[:, :3], kp, vp, gtab, mask)


def test_partial_rotary_turns_the_leading_dims_and_no_other():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 7, 4, 16)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 100, size=(2, 7)), jnp.int32)
    theta, dims = 1e7, 4
    out = np.asarray(hybrid.rope_rotate_half(x, pos[:, :, None], theta, dims))
    assert np.array_equal(out[..., dims:], np.asarray(x)[..., dims:])
    inv = theta ** (-np.arange(2) * 2.0 / dims)
    ang = np.asarray(pos)[..., None, None] * inv
    a, b = np.asarray(x)[..., :2], np.asarray(x)[..., 2:4]
    assert np.abs(out[..., :2] - (a * np.cos(ang) - b * np.sin(ang))
                  ).max() < 1e-5
    assert np.abs(out[..., 2:4] - (b * np.cos(ang) + a * np.sin(ang))
                  ).max() < 1e-5
    zero = hybrid.rope_rotate_half(x, jnp.zeros_like(pos)[:, :, None], theta,
                                   dims)
    assert np.array_equal(zero, x)          # position 0, and padding (-1)
    assert np.array_equal(hybrid.rope_rotate_half(
        x, -jnp.ones_like(pos)[:, :, None], theta, dims), x)
    assert np.abs(out - np.asarray(ref.rope(x, pos[:, :, None], theta, dims))
                  ).max() < 1e-6


def _mixer(kind, cfg, w, layer):
    m = (hybrid.FullAttention if kind == "full_attention"
         else hybrid.GatedDeltaNet)(fam.model_config(cfg))
    p = f"model.blocks.{layer}.mixer."
    for name, par in m.named_parameters():
        par.value = w[p + name]
    return m, {k[len(f"model.blocks.{layer}."):]: v for k, v in w.items()
               if k.startswith(p)}


def test_each_mixer_alone_against_its_plain_form():
    """The full layer (grouped heads, per-head QK-norm with zero-centred
    gains, partial rotary, the output gate) and the linear layer (2 value
    heads a key head, beta = sigmoid, the plain-gain output norm), each
    with the rest of the model shut out."""
    cfg = tiny_cfg()
    w = fam.make_weights(cfg, 7)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 24, 64)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
    rcfg = dict(ref.static_items(cfg))
    with jax.default_matmul_precision("highest"):
        for kind, layer, plain in (("full_attention", 3, ref.full_attention),
                                   ("linear_attention", 0,
                                    ref.linear_attention)):
            m, wl = _mixer(kind, cfg, w, layer)
            got, want = np.asarray(m(x, pos)), np.asarray(
                plain(x, wl, rcfg, "f32"))
            assert np.abs(want).max() > 0.01
            assert np.abs(got - want).max() < F32_TOL, kind


def test_value_head_h_reads_key_head_h_over_rep_in_every_route():
    """16 -> 4 key heads serving 8 value heads: the recurrence, the chunked
    form and the step (``jnp`` and the kernel in interpret mode) on grouped
    q and k equal themselves on q and k repeated by hand."""
    rng = np.random.default_rng(0)
    B, T, Hk, Hv, dk, dv = 2, 70, 4, 8, 8, 8

    def unit(t):
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    q, k = (jnp.asarray(unit(rng.normal(size=(B, T, Hk, dk))), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(B, T, Hv, dv)), jnp.float32)
    g = jnp.asarray(-rng.uniform(0.01, 0.3, size=(B, T, Hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 1.0, size=(B, T, Hv)), jnp.float32)
    qr, kr = (jnp.repeat(t, Hv // Hk, axis=2) for t in (q, k))
    want, S = gd.gated_delta_recurrent(qr, kr, v, g, beta)
    for fn in (gd.gated_delta_recurrent, gd.gated_delta_chunk):
        o, s = fn(q, k, v, g, beta)
        assert np.abs(np.asarray(o) - np.asarray(want)).max() < F32_TOL
        assert np.abs(np.asarray(s) - np.asarray(S)).max() < F32_TOL
    # a key head off by one is another function
    off, _ = gd.gated_delta_recurrent(jnp.roll(qr, 1, 2), jnp.roll(kr, 1, 2),
                                      v, g, beta)
    assert np.abs(np.asarray(off) - np.asarray(want)).max() > 0.1
    state = jnp.concatenate([S, jnp.ones((1, Hv, dk, dv), jnp.float32)])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    o1, s1 = gd._step_jnp(qr[:, 0], kr[:, 0], *args[2:], state)
    for step in (gd._step_jnp,
                 lambda *a: gd._step_pallas(*a, block_h=4),
                 lambda *a: gd._step_pallas(*a, block_h=8)):
        o, s = step(*args, state)
        assert np.abs(np.asarray(o) - np.asarray(o1)).max() < F32_TOL
        assert np.abs(np.asarray(s) - np.asarray(s1)).max() < F32_TOL
        assert np.array_equal(s[B], state[B])       # the drop row
    # block sizes keep a key head's value heads together
    space = gd._step_space(*args, state)
    assert [c["block_h"] for c in space] == [2, 4, 8]


# -- slot state under this model's two calls ---------------------------------------
def _random_cache(m, B, pages, page, seed=2):
    cache = m.init_paged_cache(pages, page, slots=B)
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda t: jnp.asarray(rng.normal(size=t.shape), t.dtype), cache)


def _linear(cache):
    return [kv for kv in cache["layers"] if "state" in kv]


def test_an_admission_starts_from_zero_state_and_padding_is_the_identity(
        tiny):
    m, _ = tiny
    B, page, G = 4, 8, 16
    cache = m.init_paged_cache(32, page, slots=B)
    assert cache["layers"][3]["k"].shape == (33, page, 2 * 16)  # H_kv * hd
    assert cache["layers"][0]["state"].shape == (B + 1, 4, 8, 8)
    assert cache["layers"][0]["conv"].shape == (B + 1, 3, 64)
    ids = ids_of((2, 16), seed=4)
    pos = np.full((2, 16), -1, np.int32)
    pos[0, :11] = np.arange(11)            # row 1 is inert: slot -1
    pm = np.full((2, G * page), -1, np.int32)
    pm[0, :11] = np.arange(11)
    tab = np.full((2, G), -1, np.int32)
    tab[0, :2] = (5, 9)
    slots = np.array([2, -1], np.int32)
    before = _random_cache(m, B, 32, page)
    logits, after = m.forward_paged(ids, pos, pm, tab, before, slots=slots,
                                    gather_last=np.array([11, 0]))
    # whatever slot 2 held, it now holds what the zero state gives
    clean_logits, clean = m.forward_paged(ids, pos, pm, tab, cache,
                                          slots=slots,
                                          gather_last=np.array([11, 0]))
    assert np.abs(np.asarray(logits[0] - clean_logits[0])).max() < F32_TOL
    for old, new, zero in zip(_linear(before), _linear(after),
                              _linear(clean)):
        for name in ("state", "conv"):
            assert np.array_equal(new[name][2], zero[name][2])
            for other in (0, 1, 3):
                assert np.array_equal(new[name][other], old[name][other])
    # padding is the identity on the state and enters no conv window: a
    # longer padded tail changes nothing
    ids2 = np.concatenate([ids, ids], axis=1)
    pos2 = np.concatenate([pos, np.full_like(pos, -1)], axis=1)
    _, wide = m.forward_paged(ids2, pos2, pm, tab, before, slots=slots)
    # (to the float32 tolerance: the expert layers before a window sort
    # twice the rows, and the matmuls over them round in another order)
    for new, w in zip(_linear(after), _linear(wide)):
        assert np.abs(new["conv"][2] - w["conv"][2]).max() < F32_TOL
        assert np.abs(new["state"][2] - w["state"][2]).max() < F32_TOL
    mixer = m.model.blocks[0].mixer     # the first layer's window: bit for bit
    assert np.array_equal(_linear(after)[0]["conv"][2],
                          _linear(wide)[0]["conv"][2])
    assert mixer.cfg.linear_num_key_heads == 2


def test_a_decode_step_leaves_a_free_slot_untouched(tiny):
    m, _ = tiny
    B, page, G = 4, 8, 16
    before = _random_cache(m, B, 32, page)
    ids = np.array([[7], [0], [9], [0]], np.int32)
    pos = np.array([[3], [-1], [5], [-1]], np.int32)
    pm = np.full((B, G * page), -1, np.int32)
    tab = np.full((B, G), -1, np.int32)
    for b, n in ((0, 4), (2, 6)):
        pm[b, :n], tab[b, 0] = np.arange(n), b
    _, after = m.forward_paged(ids, pos, pm, tab, before)
    for old, new in zip(_linear(before), _linear(after)):
        for name in ("state", "conv"):
            for free in (1, 3, B):
                assert np.array_equal(new[name][free], old[name][free])
            for live in (0, 2):
                assert not np.array_equal(new[name][live], old[name][live])


# -- planted faults: each must fail the comparison ------------------------------
def _all_dims(x, positions, theta, dims):
    return REAL_ROPE(x, positions, theta, x.shape[-1])


REAL_ROPE = hybrid.rope_rotate_half
REAL_HEADS = gd._per_value_head
REAL_LAYOUT = gm.ragged_layout


def _plain_gain(self, x):
    self = type("N", (), {"epsilon": self.epsilon, "weight": self.weight,
                          "zero_centered": False})()
    return nn.RMSNorm.forward(self, x)


def _key_head_off_by_one(q, k, heads, axis):
    q, k = REAL_HEADS(q, k, heads, axis)
    return jnp.roll(q, 1, axis), jnp.roll(k, 1, axis)


def _absent_pairs_by_modulo(ids, groups, tile_m, partial=False):
    lay = REAL_LAYOUT(jnp.mod(ids, groups), groups, tile_m)
    return {**lay, "present": jnp.ones(ids.shape, bool)}


def _mlps(m, **attrs):
    for blk in m.model.blocks:
        for k, v in attrs.items():
            setattr(blk.mlp, k, v)


FAULTS = {
    "rotary_on_every_dim": lambda mp, m: mp.setattr(
        hybrid, "rope_rotate_half", _all_dims),
    "gains_w_not_one_plus_w": lambda mp, m: [
        mp.setattr(n, "forward", _plain_gain.__get__(n))
        for n in m.sublayers() if getattr(n, "zero_centered", False)],
    "shared_gate_left_out": lambda mp, m: _mlps(m, shared_gating=None),
    "topk_weights_not_renormalised": lambda mp, m: _mlps(m, norm_topk=False),
    "key_head_off_by_one": lambda mp, m: mp.setattr(
        gd, "_per_value_head", _key_head_off_by_one),
    "absent_pair_computed_by_expert_e_mod_held": lambda mp, m: mp.setattr(
        gm, "ragged_layout", _absent_pairs_by_modulo),
    "output_gate_left_out": lambda mp, m: mp.setattr(
        hybrid.FullAttention, "_merge",
        lambda self, ctx, gate: REAL_MERGE(self, ctx, None)),
}
REAL_MERGE = hybrid.FullAttention._merge


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    cfg = tiny_cfg()
    m, w = build(cfg)
    ids = ids_of()
    want = ref_logits(w, ids, cfg)
    assert np.abs(np.asarray(m(ids)) - want).max() < F32_TOL   # sound first
    FAULTS[fault](monkeypatch, m)
    off = np.abs(np.asarray(m(ids)) - want)
    assert off.mean() > BF16_MEAN, (fault, off.mean(), off.max())


def test_the_family_counts_what_the_issue_counted():
    """Parameter counts at the published sizes, by ``param_spec`` (shapes
    only): a linear mixer 33.72 M, a full mixer 27.26 M, an expert 3.146 M,
    embedding + head 622.3 M; and the cut's 8.27 GB of bfloat16."""
    spec = fam.param_spec(PUBLISHED)

    def count(prefix, *names):
        return sum(int(np.prod(shape)) for k, (shape, _, _) in spec.items()
                   if k.startswith(prefix)
                   and (not names or k[len(prefix):] in names))

    lin = count("model.blocks.0.mixer.")
    assert round(lin / 1e6, 2) == 33.72
    assert round(count("model.blocks.3.mixer.") / 1e6, 2) == 27.26
    gate = spec["model.blocks.0.mlp.expert_gate"][0]
    assert gate == (128, 2048, 512)
    assert round(3 * gate[1] * gate[2] / 1e6, 3) == 3.146
    assert spec["model.blocks.0.mlp.router"][0] == (2048, 512)
    assert round((count("model.embed") + count("head")) / 1e6, 1) == 622.3
    assert fam.layer_types(PUBLISHED) == ("linear_attention",) * 3 + (
        "full_attention",) + ("linear_attention",) * 3 + ("full_attention",)
    total = sum(int(np.prod(s)) for s, _, _ in spec.values())
    assert round(2 * total / 1e9, 2) == 8.27
    mc = fam.model_config(PUBLISHED)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.rotary_dim) == (
        16, 2, 256, 64)
    assert mc.moe["held"] == (0, 128) and mc.experts_held == 128
    with nn.abstract_parameters():
        model = hybrid.HybridForCausalLM(mc)
    # 6 linear layers: 32 x 128 x 128 float32 + 3 x 8192 bfloat16 a slot
    assert model.slot_state_bytes() == 6 * (2097152 + 3 * 8192 * 2)
    assert model.moe_experts == 128
