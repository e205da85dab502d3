"""The decoder of gated-delta-rule and full-attention layers
(``paddle_tpu/models/hybrid.py``, ``paddle_tpu/ops/gated_delta.py``) and the
engine's slot state, against the ONE plain reference,
``benchmarks/reference/olmo_hybrid.py``: tiny widths, seeded weights, CPU.

Tolerances.  With float32 parameters the program (chunked WY form, fused
projections) and the reference (token-by-token recurrence) compute the same
float32 function by two routes: logits of magnitude under 1 agreed to 2e-6
over these shapes, so 2e-5.  With bfloat16 parameters the program rounds
every projection to bfloat16 where the reference keeps float32, and the
delta rule's ``v - S^T k`` amplifies it: 0.047 was the widest logit and
0.0035 the mean at this seed (the reference computed in bfloat16 strays as
far from itself: 0.081 and 0.0045), so 0.15 and 0.01; a missing factor of 2
in beta, a dropped decay or a conv without its history moves the MEAN logit
by 0.04 to 0.17.  The kernels against the recurrence: 2e-6 was the worst
seen in float32, so 2e-5.
"""
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import loader  # noqa: E402
from benchmarks.reference import numerics as nm  # noqa: E402

from paddle_tpu.framework.errors import InvalidArgumentError  # noqa: E402
from paddle_tpu.ops import gated_delta as gd  # noqa: E402
from paddle_tpu.serving import GenerationEngine  # noqa: E402

fam = loader.load_module("families", "olmo_hybrid")
ref = loader.load_module("reference", "olmo_hybrid")

F32_TOL, KERNEL_TOL = 2e-5, 2e-5
BF16_MAX, BF16_MEAN = 0.15, 0.01


def tiny_cfg(dtype="float32", cache_len=64, **over):
    cfg = dict(param_dtype=dtype,
               linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
               rope_parameters={"rope_theta": None},
               serve={"cache_len": cache_len}, **fam.TINY)
    cfg.update(over)
    return cfg


def build(cfg, seed=5):
    w = fam.make_weights(cfg, seed)
    m = fam.build_model(cfg, w)
    m.eval()
    return m, w


def ref_logits(w, ids, cfg):
    return np.asarray(ref.logits(
        w, jnp.asarray(ids, jnp.int32), cfg_items=nm.static_items(cfg),
        layer_types=fam.layer_types(cfg)))


def engine(m, batch=4, **kw):
    kw = {"prompt_buckets": [16, 32], "kv_page_size": 8, "speculative_k": 0,
          "eos_token_id": None, "name": "hy", **kw}
    return GenerationEngine(m, batch_size=batch, **kw)


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, size=n).astype(np.int32) for n in lengths]


def serve(eng, prompts, new=6, **kw):
    futures = [eng.submit(p, new, **kw) for p in prompts]
    return [np.asarray(f.result(timeout=300)).tolist() for f in futures]


@pytest.fixture(scope="module")
def tiny():
    return build(tiny_cfg(cache_len=128))


@pytest.fixture(scope="module")
def alone(tiny):
    """What a fresh engine serves for each of eight prompts, one at a time
    in slot 0: the tokens every scheduling of them has to reproduce."""
    m, _ = tiny
    prompts = prompts_of((5, 16, 20, 31, 9, 12, 2, 1), seed=3)
    eng = engine(m, batch=1)
    try:
        eng.warmup()
        return prompts, [serve(eng, [p], new=10)[0] for p in prompts]
    finally:
        eng.close()


# -- the model against the reference ----------------------------------------
@pytest.mark.parametrize("dtype,widest,mean", [
    ("float32", F32_TOL, F32_TOL), ("bfloat16", BF16_MAX, BF16_MEAN)])
def test_full_forward_logits_match_the_reference(dtype, widest, mean):
    cfg = tiny_cfg(dtype)
    m, w = build(cfg)
    ids = np.random.default_rng(1).integers(1, 512, (2, 40)).astype(np.int32)
    got, want = np.asarray(m(ids)), ref_logits(w, ids, cfg)
    assert got.dtype == np.float32 and np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < widest
    assert np.abs(got - want).mean() < mean


def test_each_part_of_the_linear_layer_moves_the_logits(monkeypatch):
    """What the tolerances are tight enough to see: beta without its
    factor 2, the decay left out, the output gate left out."""
    from paddle_tpu.models import hybrid

    cfg = tiny_cfg()
    m, w = build(cfg)
    ids = np.random.default_rng(1).integers(1, 512, (2, 40)).astype(np.int32)
    want = ref_logits(w, ids, cfg)
    real_gates = hybrid.GatedDeltaNet._gates

    def half_beta(self, x, valid):
        g, beta = real_gates(self, x, valid)
        return g, beta / 2

    def no_decay(self, x, valid):
        g, beta = real_gates(self, x, valid)
        return jnp.zeros_like(g), beta

    def no_gate(self, x, o):
        y = self.o_norm(o).reshape(*x.shape[:-1], -1).astype(x.dtype)
        return hybrid._mm(y, self.out.value)

    for name, fault in (("_gates", half_beta), ("_gates", no_decay),
                        ("_output", no_gate)):
        with monkeypatch.context() as mp:
            mp.setattr(hybrid.GatedDeltaNet, name, fault)
            assert np.abs(np.asarray(m(ids)) - want).mean() > 3 * BF16_MEAN


def test_prefill_then_decode_through_both_caches_matches_full_forward(tiny):
    m, w = tiny
    cfg = tiny_cfg(cache_len=128)
    prompts = prompts_of((5, 16, 20, 31, 9, 12, 2, 1))
    eng = engine(m)
    try:
        warm = eng.warmup()
        assert warm == 4  # two buckets, the step, the page copy
        outs = serve(eng, prompts)
        assert eng.compile_count == warm
        st = eng.stats()
        assert st["state_slots_reset"] == st["admitted"] == 8
        assert st["gdn_prefill_tokens"] == st["admit_tokens"] == 96
        assert st["gdn_prefill_token_slots"] == st["admit_token_slots"]
        assert st["state_bytes_steps"] == (
            st["decode_steps"] * 2 * 4 * m.slot_state_bytes())
        # 3 linear layers: a [4, 8, 16] float32 state and 3 rows of the
        # 4 x (8 + 8 + 16)-wide conv input, float32 here
        assert m.slot_state_bytes() == 3 * (4 * 4 * 8 * 16 + 4 * 3 * 128)
        texts = eng.compiled_programs()
        assert set(texts) == {"step", "admit[16]", "admit[32]"}
        assert all("/gdn/" in t and "/attn/" in t for t in texts.values())
        assert eng.compile_count == warm
    finally:
        eng.close()
    assert all(len(o) == 6 for o in outs)
    gaps = ref.served_token_gaps(w, cfg, prompts, outs)
    assert max(g["gap"].max() for g in gaps) < F32_TOL


# -- the kernels against the recurrence ---------------------------------------
def _inputs(B, T, H, dk, dv, seed, beta_lo=0.0, decay=1.6):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    f = np.float32
    q = (unit(rng.normal(size=(B, T, H, dk))) * dk ** -0.5).astype(f)
    k = unit(rng.normal(size=(B, T, H, dk))).astype(f)
    v = rng.normal(size=(B, T, H, dv)).astype(f)
    g = -rng.uniform(0.001, decay, size=(B, T, H)).astype(f)
    beta = rng.uniform(beta_lo, 2.0, size=(B, T, H)).astype(f)
    return q, k, v, g, beta


@pytest.mark.parametrize("case", ["ragged_tails", "beta_near_2", "long",
                                  "short_of_one_chunk"])
@pytest.mark.parametrize("kernel", [False, True])
def test_gated_delta_chunk_against_the_token_by_token_recurrence(
        case, kernel, monkeypatch):
    T = {"long": 640, "short_of_one_chunk": 37}.get(case, 200)
    q, k, v, g, beta = _inputs(
        2, T, 3, 24, 40, seed=len(case),
        beta_lo=1.9 if case == "beta_near_2" else 0.0,
        decay=0.2 if case == "long" else 1.6)
    lens = (T, 130) if case == "ragged_tails" else (T, T)
    for b, n in enumerate(lens):   # padding: the identity on the state
        g[b, n:], beta[b, n:] = 0.0, 0.0
    # interpret mode off the chip: the kernel's own code path
    monkeypatch.setattr(gd, "gated_delta_eligible", lambda: kernel)
    o, S = gd.gated_delta_chunk(q, k, v, g, beta)
    for b, n in enumerate(lens):
        want_o, want_S = gd.gated_delta_recurrent(
            q[b:b + 1, :n], k[b:b + 1, :n], v[b:b + 1, :n], g[b:b + 1, :n],
            beta[b:b + 1, :n])
        assert np.abs(o[b, :n] - want_o[0]).max() < KERNEL_TOL
        assert np.abs(S[b] - want_S[0]).max() < KERNEL_TOL


def test_the_unit_lower_inverse_is_an_inverse():
    rng = np.random.default_rng(0)
    for C in (8, 16, 64):
        # entries as beta (k_i . k_j) has them: well under 1
        A = np.tril(0.2 * rng.normal(size=(3, C, C)), -1).astype(np.float32)
        got = np.asarray(gd._unit_lower_inverse(jnp.asarray(A)))
        assert np.abs(got @ (np.eye(C) + A) - np.eye(C)).max() < 1e-4
        assert np.abs(np.triu(got, 1)).max() == 0.0


@pytest.mark.parametrize("kernel", [False, True])
def test_gated_delta_step_updates_live_slots_and_only_them(kernel,
                                                           monkeypatch):
    B, H, dk, dv = 4, 3, 24, 40
    q, k, v, g, beta = (t[:, 0] for t in _inputs(B, 1, H, dk, dv, seed=7))
    g[2], beta[2] = 0.0, 0.0               # slot 2 is free: the identity
    state = np.random.default_rng(1).normal(
        size=(B + 1, H, dk, dv)).astype(np.float32)
    monkeypatch.setattr(gd, "gated_delta_eligible", lambda: kernel)
    o, new = gd.gated_delta_step(q, k, v, g, beta, jnp.asarray(state))
    want_o, want = gd.gated_delta_recurrent(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
        state[:B])
    assert np.abs(np.asarray(o) - want_o[:, 0]).max() < KERNEL_TOL
    assert np.abs(np.asarray(new)[:B] - want).max() < KERNEL_TOL
    # bit for bit: the free slot, and the write-drop row past the batch
    assert np.array_equal(np.asarray(new)[2], state[2])
    assert np.array_equal(np.asarray(new)[B], state[B])


# -- slot state under the model's two calls -----------------------------------
def _random_cache(m, B, pages, page, seed=2):
    cache = m.init_paged_cache(pages, page, slots=B)
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda t: jnp.asarray(rng.normal(size=t.shape), t.dtype), cache)


def _linear(cache):
    return [kv for kv in cache["layers"] if "state" in kv]


def test_an_admission_writes_its_slots_from_zero_state_and_no_other(tiny):
    m, _ = tiny
    B, page, G = 4, 8, 16
    ids = np.random.default_rng(4).integers(1, 512, (2, 16)).astype(np.int32)
    pos = np.full((2, 16), -1, np.int32)
    pos[0, :11] = np.arange(11)            # row 1 is inert: slot -1
    pm = np.full((2, G * page), -1, np.int32)
    pm[0, :11] = np.arange(11)
    tab = np.full((2, G), -1, np.int32)
    tab[0, :2] = (5, 9)
    before = _random_cache(m, B, 32, page)
    _, after = m.forward_paged(ids, pos, pm, tab, before,
                               slots=np.array([2, -1], np.int32))
    # whatever slot 2 held, it now holds what the zero state gives
    _, clean = m.forward_paged(ids, pos, pm, tab,
                               m.init_paged_cache(32, page, slots=B),
                               slots=np.array([2, -1], np.int32))
    for old, new, zero in zip(_linear(before), _linear(after),
                              _linear(clean)):
        for name in ("state", "conv"):
            assert np.array_equal(new[name][2], zero[name][2])
            for other in (0, 1, 3):
                assert np.array_equal(new[name][other], old[name][other])
    # the conv window is the last three REAL tokens', never the padding's:
    # a longer padded tail changes nothing
    ids2 = np.concatenate([ids, ids], axis=1)
    pos2 = np.concatenate([pos, np.full_like(pos, -1)], axis=1)
    _, wide = m.forward_paged(ids2, pos2, pm, tab, before,
                              slots=np.array([2, -1], np.int32))
    for new, w in zip(_linear(after), _linear(wide)):
        assert np.array_equal(new["conv"][2], w["conv"][2])
        assert np.abs(new["state"][2] - w["state"][2]).max() < KERNEL_TOL


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
    with pytest.raises(InvalidArgumentError, match="one token a row"):
        m.forward_paged(np.zeros((B, 2), np.int32),
                        np.full((B, 2), -1, np.int32), pm, tab, before)


# -- slot state under the engine ---------------------------------------------
def test_a_reused_slot_starts_from_zero_state(alone):
    """``alone`` served eight requests one after another through slot 0 of
    one engine; a fresh engine gives each the same tokens."""
    prompts, outs = alone
    m, _ = build(tiny_cfg(cache_len=128))
    for p, want in list(zip(prompts, outs))[3:5]:
        eng = engine(m, batch=1)
        try:
            assert serve(eng, [p], new=10)[0] == want
        finally:
            eng.close()


def test_chunks_of_one_admission_do_not_see_each_others_slots(tiny, alone):
    """Eight requests at once on four slots: admissions of two chunks of
    two rows, slots reused as they free up."""
    m, _ = tiny
    prompts, outs = alone
    eng = engine(m)
    try:
        assert serve(eng, prompts, new=10) == outs
        assert eng.stats()["admit_steps"] < eng.stats()["admit_rows"]
    finally:
        eng.close()


def test_slot_state_follows_rows_into_chunks_of_unequal_rows(
        tiny, alone, monkeypatch):
    """Buckets on both sides of the admission cap (two rows of 16, one row
    of 32): one iteration admits rows of 16, 32, 16, 32 as four calls, the
    first and third with an inert second row (slot -1)."""
    from paddle_tpu.serving import generation

    m, _ = tiny
    prompts, outs = alone
    order = [0, 2, 1, 3]  # lengths 5, 20, 16, 31
    monkeypatch.setattr(generation, "_ADMIT_TOKEN_SLOTS", 32)
    eng = engine(m)
    try:
        assert eng._admit_rows == {16: 2, 32: 1}
        warm = eng.warmup()
        assert warm == 4
        with eng._batcher._cv:  # the loop's poll sees none of them or all
            futures = [eng.submit(prompts[k], 10) for k in order]
        got = [np.asarray(f.result(timeout=300)).tolist() for f in futures]
        eng.close()
        st = eng.stats()
        assert (st["batches"], st["admit_steps"], st["admit_rows"],
                st["admit_row_slots"]) == (1, 4, 4, 2 + 1 + 2 + 1)
        assert st["admit_token_slots"] == 2 * 16 + 32 + 2 * 16 + 32
        assert st["gdn_prefill_token_slots"] == st["admit_token_slots"]
        assert st["state_slots_reset"] == 4
        assert eng.compile_count == warm
    finally:
        eng.close()
    assert got == [outs[k] for k in order]


def test_a_preempted_request_regenerates_the_same_tokens(tiny, alone):
    m, _ = tiny
    prompts, outs = alone
    # 16 pages = one whole window: two sequences growing to 31 + 40 tokens
    # cannot both stay, so the newer is preempted and prefilled again
    eng = engine(m, batch=2, kv_pages=16)
    try:
        got = serve(eng, [prompts[3], prompts[2], prompts[1]], new=40)
        assert eng.stats()["preempted"] > 0
    finally:
        eng.close()
    assert [g[:10] for g in got] == [outs[3], outs[2], outs[1]]


def test_what_slot_state_cannot_do_is_refused_by_name(tiny):
    m, _ = tiny
    for kw, why in (({"speculative_k": 2}, "cannot be rolled back"),
                    ({"role": "prefill"}, "pages, not slot state"),
                    ({"role": "decode"}, "pages, not slot state"),
                    ({"quantized": "int8"}, "no scale planes")):
        with pytest.raises(InvalidArgumentError, match=why):
            engine(m, **kw)
    eng = engine(m)
    try:
        with pytest.raises(InvalidArgumentError, match="not slot state"):
            eng.submit(np.arange(1, 9, dtype=np.int32), 4, handoff=True)
        p = prompts_of((20, 20), seed=9)
        p[1][:12] = p[0][:12]
        a, b = serve(eng, p, prefix_key="sys", prefix_len=12)
        st = eng.stats()
        assert st["prefix_unshared"] == 2 and st["prefix_hits"] == 0
        assert st["kv_pages_shared"] == 0 and st["cow_copies"] == 0
        assert [a, b] == serve(eng, p)     # the same tokens as without a key
    finally:
        eng.close()


# -- the other models' programs are what they were ---------------------------
_LOCATION = re.compile(
    r',?\s*(source_file="[^"]*"|(source_(end_)?(line|column)|stack_frame_id)'
    r'=\d+)|\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*')

#: sha256 of ``compiled_programs()`` (source locations removed) of the three
#: engines below, this suite's 8-device CPU configuration, produced by this
#: code at the commit that gave the step program the previous step's token
#: column (PR 34: one operand, ``prev``, and a select on each row's first id;
#: before it the digests were 29c4abe6..., 44498743... (dea3933's parent) and
#: 5b5e0496... (cc6cfe4), and a diff of the step's text showed that operand
#: and the renumbering behind it, nothing else)
PROGRAMS_BEFORE = {
    "gpt": "9472a0fede8a48f4651836c002efdb5c8f05191c88dfb1ddd1ab38246b0494b7",
    "latent": "2023c79525874e792d49224bfe94752e377ce1ad06802907a81cd977bd0dcd69",
    "hybrid": "4967c2ee7a859a6a8dce6d9c758a088c0b4a050c00f4329eb199b245f3bcc4cc",
}
#: ... and of each admission program alone, at that commit's PARENT (2441a91,
#: produced by the parent's code): the step's new operand left them as they
#: were
ADMIT_BEFORE = {
    "gpt": {
        "admit[16]":
        "bd0fc62c1b0bf1f07ff5db05314e6d699c737b770442191a2d3b1a11b11f6d33",
        "admit[32]":
        "2cf8fc1d6785e848730dcf58b1428e48141397545a4348066b016a1f5c02dae8"},
    "hybrid": {
        "admit[16]":
        "9f003015ed6fcf4b6f1bf77f858ccda2bd2fdb7e2b7eadbc9682ed31139abe36",
        "admit[32]":
        "a1f2712367897522fe70a20ebbd6172e1b22f9346c5307f9aafd44be5f80de8a"},
    "latent": {
        "admit[16]":
        "9be4ccc8956ca37b42428892bc82897c9439cd87a405a213f043ad27bd6991f5",
        "admit[32]":
        "4e79eb71c15dda631fb294db21a0d89d96b5b4864f15c02ad40418396d0fafc0"},
}


def _sha(text):
    return hashlib.sha256(_LOCATION.sub("", text).encode()).hexdigest()


def _digest(texts):
    return hashlib.sha256("\n".join(
        _LOCATION.sub("", texts[k]) for k in sorted(texts)).encode()
    ).hexdigest()


def test_gpt2s_benchmark_engine_lowers_the_two_row_programs():
    """An engine with the GPT-2 cells' settings (32 slots; the buckets of
    ``chat_open`` and ``docs_closed``, all under the admission cap) lowers,
    bucket for bucket, the text of its admission program lowered by hand at
    two rows, and the step's at 32."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(1234)
    m = GPTForCausalLM(GPTConfig(vocab_size=512, hidden_size=64,
                                 num_layers=2, num_heads=4,
                                 max_position=1024, dropout=0.0))
    m.eval()
    buckets, B, page = [64, 128, 256, 512, 640, 768], 32, 16
    eng = GenerationEngine(m, prompt_buckets=buckets, batch_size=B,
                           kv_page_size=page, speculative_k=0,
                           eos_token_id=None, name="gpt2-settings")
    try:
        assert eng._admit_rows == {b: 2 for b in buckets}
        texts = eng.compiled_programs()
        C, G = 1024, 1024 // page
        pool = jax.eval_shape(eng._empty_pool)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        want = {"step": eng._step_jit.lower(
            eng._params, eng._buffers, i32(B, 2 + C + G), i32(B, 1), pool)}
        for sb in buckets:
            want[f"admit[{sb}]"] = eng._padmit.lower(
                eng._params, eng._buffers, i32(2, sb), i32(2, sb), i32(2, C),
                i32(2, G), i32(2), pool, None, None)
        assert sorted(texts) == sorted(want)
        for key, lowered in want.items():
            assert _LOCATION.sub("", texts[key]) == _LOCATION.sub(
                "", lowered.compile().as_text()), key
    finally:
        eng.close()


def _gpt():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(1234)
    return GPTForCausalLM(GPTConfig(vocab_size=512, hidden_size=64,
                                    num_layers=2, num_heads=4,
                                    max_position=128, dropout=0.0))


def _latent():
    lfam = loader.load_module("families", "joyai_flash")
    cfg = dict(first_k_dense_replace=1, n_shared_experts=1,
               norm_topk_prob=True, routed_scaling_factor=2.5,
               rms_norm_eps=1e-6, rope_theta=32000000,
               param_dtype="float32", serve={"cache_len": 128}, **lfam.TINY)
    return lfam.build_model(cfg, lfam.make_weights(cfg, 5))


def _hybrid():
    return build(tiny_cfg(cache_len=128))[0]


@pytest.mark.parametrize("which", ["gpt", "latent", "hybrid"])
def test_a_model_without_slot_state_builds_the_programs_it_built_before(
        which):
    m = {"gpt": _gpt, "latent": _latent, "hybrid": _hybrid}[which]()
    m.eval()
    eng = engine(m, name=which)
    try:
        eng.warmup()
        texts = eng.compiled_programs()
    finally:
        eng.close()
    assert {k: _sha(t) for k, t in texts.items()
            if k != "step"} == ADMIT_BEFORE[which]
    assert _digest(texts) == PROGRAMS_BEFORE[which]
