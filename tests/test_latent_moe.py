"""The latent-attention decoder with routed experts
(``paddle_tpu/models/latent_moe.py``, ``moe.DroplessMoE``,
``ops.grouped_matmul.ragged_gated_mlp``) against the ONE plain reference,
``benchmarks/reference/joyai_flash.py``: tiny widths, seeded weights, CPU.

Tolerances.  With float32 parameters the program and the reference compute
the same float32 function in different summation orders (blocked attention,
absorbed decode, sorted experts): logits of magnitude under 1 agree to a few
float32 ulps of the largest intermediate, 2e-6 was the worst seen, so 2e-5.
With bfloat16 parameters the program rounds every matmul's result to
bfloat16 (8 bits of mantissa) where the reference keeps float32: 3e-3 was
the worst seen over these shapes, so 2e-2; a dropped shared expert, a
missing scale or the bias in the weights moves logits by 0.05 or more.
"""
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
from benchmarks.reference import numerics as nm  # noqa: E402

from paddle_tpu import nn  # noqa: E402
from paddle_tpu.moe import DroplessMoE  # noqa: E402
from paddle_tpu.moe import stats as moe_stats  # noqa: E402
from paddle_tpu.ops.grouped_matmul import (ragged_gated_mlp,  # noqa: E402
                                           ragged_layout, ragged_tiles)
from paddle_tpu.serving import GenerationEngine  # noqa: E402

fam = loader.load_module("families", "joyai_flash")
ref = loader.load_module("reference", "joyai_flash")

F32_TOL, BF16_TOL = 2e-5, 2e-2


def tiny_cfg(dtype="float32", layers=3, dense=1, cache_len=64, **over):
    cfg = dict(first_k_dense_replace=dense, n_shared_experts=1,
               norm_topk_prob=True, routed_scaling_factor=2.5,
               rms_norm_eps=1e-6, rope_theta=32000000, param_dtype=dtype,
               serve={"cache_len": cache_len}, **fam.TINY)
    cfg.update(num_hidden_layers=layers, **over)
    return cfg


def build(cfg, seed=5):
    w = fam.make_weights(cfg, seed)
    m = fam.build_model(cfg, w)
    m.eval()
    return m, w


def ref_logits(w, ids, cfg):
    return np.asarray(ref.logits(w, jnp.asarray(ids, jnp.int32),
                                 cfg_items=nm.static_items(cfg)))


def ids_of(shape, seed=1):
    return np.random.default_rng(seed).integers(1, 512, shape).astype(
        np.int32)


# -- the model against the reference ---------------------------------------
@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
def test_full_forward_logits_match_the_reference(dtype, tol):
    cfg = tiny_cfg(dtype)
    m, w = build(cfg, seed=2 ** 31 + 7)
    ids = ids_of((2, 40))
    got = np.asarray(m(jnp.asarray(ids)))
    assert got.dtype == np.float32 and got.shape == (2, 40, 512)
    assert np.abs(got - ref_logits(w, ids, cfg)).max() < tol


def paged_logits(m, ids, plen, C, page, bucket):
    """Prefill ``plen`` tokens in one ``[B, bucket]`` call, then decode the
    rest one token a call (teacher-forced), through a shuffled page table.
    Returns {position: logits [B, V] that predict position + 1}."""
    B, total = ids.shape
    G = C // page
    cache = m.init_paged_cache(B * G, page)
    table = np.random.default_rng(2).permutation(B * G).reshape(B, G).astype(
        np.int32)
    pos_map = np.full((B, C), -1, np.int32)
    pin = np.zeros((B, bucket), np.int32)
    pp = np.full((B, bucket), -1, np.int32)
    pin[:, :plen], pp[:, :plen] = ids[:, :plen], np.arange(plen)
    pos_map[:, :plen] = np.arange(plen)
    lg, cache = m.forward_paged(pin, pp, pos_map, table, cache,
                                gather_last=np.full((B,), plen, np.int32))
    got = {plen - 1: np.asarray(lg)}
    for p in range(plen, total):
        pos_map[:, p % C] = p
        lg, cache = m.forward_paged(ids[:, p:p + 1],
                                    np.full((B, 1), p, np.int32), pos_map,
                                    table, cache)
        got[p] = np.asarray(lg[:, 0])
    return got


@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
def test_prefill_then_decode_through_latent_pages_matches_full_forward(
        dtype, tol):
    """13 prompt tokens cross the page boundary at 8 inside the prefill;
    the decode crosses those at 16 and 24."""
    cfg = tiny_cfg(dtype, cache_len=32)
    m, w = build(cfg)
    ids = ids_of((2, 30))
    got = paged_logits(m, ids, plen=13, C=32, page=8, bucket=16)
    full = ref_logits(w, ids, cfg)
    assert max(np.abs(g - full[:, p]).max() for p, g in got.items()) < tol


def test_ring_wrap_attends_the_last_cache_len_positions():
    """Past ``cache_len`` the slot's pages wrap and a query sees the last
    C positions.  With ONE layer (an expert layer) a token's latent depends
    on that token alone and scores on relative positions alone, so the
    logits equal the reference's over the window ``ids[p - C + 1 .. p]``."""
    cfg = tiny_cfg(layers=1, dense=0, cache_len=16)
    m, w = build(cfg)
    ids = ids_of((2, 30))
    got = paged_logits(m, ids, plen=13, C=16, page=8, bucket=16)
    assert max(got) >= 2 * 16 - 3  # wrapped, and nearly twice
    for p, g in got.items():
        lo = max(0, p - 16 + 1)
        r = ref_logits(w, ids[:, lo:p + 1], cfg)[:, -1]
        assert np.abs(g - r).max() < F32_TOL, p


@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
def test_absorbed_decode_equals_expanded_attention(dtype, tol):
    cfg = tiny_cfg(dtype)
    m, _ = build(cfg)
    attn = m.model.blocks[1].attn
    rng = np.random.default_rng(0)
    B, S, H = 3, 24, 4
    dt = jnp.dtype(dtype)
    q_nope = jnp.asarray(rng.normal(size=(B, 1, H, 16)), dt)
    q_rope = jnp.asarray(rng.normal(size=(B, 1, H, 8)), dt)
    latent = jnp.asarray(rng.normal(size=(B, S, 40)), dt)
    kpos = jnp.asarray(np.where(rng.random((B, S)) < 0.8,
                                np.arange(S)[None], -1), jnp.int32)
    qpos = jnp.asarray([[S - 1], [10], [3]], jnp.int32)
    a = attn.absorbed(q_nope, q_rope, latent, qpos, kpos, 64)
    e = attn.expanded(q_nope, q_rope, latent, qpos, kpos, 64)
    assert a.shape == e.shape == (B, 1, H * 16)
    assert np.abs(np.asarray(a, np.float32)
                  - np.asarray(e, np.float32)).max() < tol * 5


def _positions(case, B, T, C):
    qpos = np.full((B, T), -1, np.int32)
    kpos = np.full((B, C), -1, np.int32)
    if case == "from_zero":          # row 0 a 20-token prompt, row 1 inert
        qpos[0, :20] = np.arange(20)
        kpos[0, :20] = np.arange(20)
    elif case == "after_a_shared_prefix":
        qpos[:, :24] = np.arange(30, 54)
        kpos[:, :54] = np.arange(54)
    else:                            # "wrapped": positions 100..131 in a
        qpos[:] = np.arange(100, 132)    # ring of 64, oldest overwritten
        kpos[:] = np.roll(np.arange(132 - C, 132), 132 % C)
    return qpos, kpos


@pytest.mark.parametrize("case", ["from_zero", "after_a_shared_prefix",
                                  "wrapped"])
def test_latent_prefill_kernel_against_plain_softmax(case, monkeypatch):
    from paddle_tpu.ops import latent_attention as la

    monkeypatch.setattr(la, "_BLOCK", 16)  # several tiles at this size
    rng = np.random.default_rng(0)
    B, H, T, C, dn, dr, dv = 2, 3, 32, 64, 16, 8, 16

    def f(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    qn, qr, kn, kr, v = (f(B, H, T, dn), f(B, H, T, dr), f(B, H, C, dn),
                         f(B, C, dr), f(B, H, C, dv))
    qpos, kpos = _positions(case, B, T, C)
    got = np.asarray(la.latent_prefill_attention(qn, qr, kn, kr, v, qpos,
                                                 kpos, C, 0.3))
    s = (jnp.einsum("bhqd,bhkd->bhqk", qn, kn)
         + jnp.einsum("bhqr,bkr->bhqk", qr, kr)) * 0.3
    kp, qp = kpos[:, None, :], qpos[:, :, None]
    seen = (kp >= 0) & (kp <= qp) & (kp > qp - C)
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), -1)
    want = np.asarray(jnp.einsum("bhqk,bhkd->bhqd",
                                 jnp.where(jnp.isnan(p), 0.0, p), v))
    assert np.abs(got - want).max() < F32_TOL
    inert = qpos < 0
    assert (got.transpose(0, 2, 1, 3)[inert] == 0).all()  # saw nothing: zero
    need = np.asarray(la.tile_need(jnp.asarray(qpos), jnp.asarray(kpos), C,
                                   16, 16))
    tiles = seen.reshape(B, T // 16, 16, C // 16, 16).any(axis=(2, 4))
    assert (need | ~tiles).all()      # never skips a tile that holds a key
    if case == "from_zero":
        assert need.sum() < need.size / 2   # and skips most of the rest


# -- the decode step's page walk ---------------------------------------------
from conftest import LATENT_WALK_CASES  # noqa: E402


@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
@pytest.mark.parametrize("case", LATENT_WALK_CASES)
def test_latent_decode_kernel_against_absorbed_over_a_gathered_view(
        case, dtype, tol, latent_walk_check):
    """The rotary layer at the cells' 32 heads: ``latent_decode`` walking
    the pool against ``absorbed`` over the gathered view, as a share of the
    layer's largest output: 8e-7 was the widest float32 gap (a key left out
    of 640 moves it by 1e-3), one bfloat16 ulp (0.5 %) the widest there."""
    m, _ = build(tiny_cfg(dtype, layers=1, num_attention_heads=32))
    latent_walk_check(m.model.blocks[0].attn, case, tol)


@pytest.mark.parametrize("keys", [16, 48, 128, 512])
def test_latent_decode_is_the_same_sum_in_blocks_of_any_size(keys):
    """Keys a block is a tile, not a result: 11 pages of 16 in blocks of 1,
    3, 8 pages and in one, bounds that end inside a block, a free slot."""
    from paddle_tpu.ops import latent_attention as la
    from paddle_tpu.ops.paged_attention import key_visible, sweep_bound

    rng = np.random.default_rng(2)
    B, H, W, r, page, G = 4, 5, 128, 40, 16, 11
    C = G * page
    pool = jnp.asarray(rng.normal(size=(B * G + 1, page, W)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    table = rng.permutation(B * G).reshape(B, G).astype(np.int32)
    pos = np.array([[C - 1], [37], [-1], [90]], np.int32)
    pos_map = np.where(np.arange(C)[None] <= pos, np.arange(C)[None], -1)
    seen = key_visible(pos_map[:, None, :], pos[:, :, None], C)
    bound = sweep_bound(seen, page)
    assert bound.tolist() == [11, 8, 0, 8]
    assert la.decode_block_pages(page, G, keys) == min(keys, 256) // page
    got = np.asarray(la.latent_decode(
        q, pool, jnp.asarray(table), jnp.asarray(pos_map), jnp.asarray(pos),
        jnp.asarray(bound), scale=0.3, value_width=r, block_keys=keys))
    view = np.asarray(pool)[table].reshape(B, C, W)
    s = np.where(seen, np.einsum("bhw,bcw->bhc", np.asarray(q), view) * 0.3,
                 -np.inf)
    with np.errstate(invalid="ignore"):
        p = np.nan_to_num(np.exp(s - s.max(-1, keepdims=True)))
    want = np.einsum("bhc,bcv->bhv", p, view[..., :r]) / np.maximum(
        p.sum(-1, keepdims=True), 1e-30)
    assert got.shape == (B, H, r)
    assert np.abs(got - want).max() < F32_TOL
    assert (got[2] == 0).all()  # the free slot: zeros, nothing fetched


def test_latent_decode_eligible_is_the_tpu_decode_width_on_one_device(
        monkeypatch, use_mesh):
    from paddle_tpu.framework import device as pdevice
    from paddle_tpu.ops import latent_attention as la

    pool = jax.ShapeDtypeStruct((9, 16, 640), jnp.bfloat16)
    assert not la.latent_decode_eligible(pool, 1)     # the CPU: gather path
    monkeypatch.setattr(pdevice, "on_tpu", lambda: True)
    assert not la.latent_decode_eligible(pool, 1)     # the suite's 8 devices
    use_mesh(jax.devices()[:1])
    assert la.latent_decode_eligible(pool, 1)
    assert not la.latent_decode_eligible(pool, 2)     # an admission, a verify
    for shape, dt in (((9, 16, 576), jnp.bfloat16),
                      ((9, 8, 640), jnp.bfloat16),
                      ((9, 12, 640), jnp.float32)):
        assert not la.latent_decode_eligible(
            jax.ShapeDtypeStruct(shape, dt), 1), shape
    assert la.latent_decode_eligible(
        jax.ShapeDtypeStruct((9, 8, 640), jnp.float32), 1)


# -- routing ---------------------------------------------------------------
def moe_layer(seed=0, E=16, k=4, D=64, F=32):
    import paddle_tpu as paddle

    paddle.seed(seed)
    layer = DroplessMoE(D, F, E, k, shared_experts=1, routed_scale=2.5,
                        dtype="float32", init_std=0.2)
    layer.eval()
    return layer


def ref_layer_weights(layer):
    return {"mlp." + n: p.value for n, p in layer.named_parameters()}


REF_CFG = {"n_routed_experts": 16, "num_experts_per_tok": 4,
           "routed_scaling_factor": 2.5, "norm_topk_prob": True}


def test_all_tokens_to_the_same_experts_and_nothing_is_dropped():
    layer = moe_layer()
    bias = np.zeros(16, np.float32)
    bias[[2, 5, 11, 12]] = 10.0  # sigmoid < 1: these four always win
    layer.score_bias.value = jnp.asarray(bias)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(37, 64)),
                    jnp.float32)
    with moe_stats.collect() as ms:
        y = layer(x)
    routed, dropped = np.asarray(ms.counts(16))
    assert routed.tolist() == [37 if e in (2, 5, 11, 12) else 0
                               for e in range(16)]
    assert dropped.sum() == 0
    assert np.asarray(ms.touched(16)).sum() == 4
    w = ref_layer_weights(layer)
    with jax.default_matmul_precision("highest"):
        want = ref.routed_experts(x, w, REF_CFG, "f32") + ref.gated_mlp(
            x, w["mlp.shared_gate"], w["mlp.shared_up"],
            w["mlp.shared_down"], "f32")
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < F32_TOL


def test_the_bias_moves_the_choice_but_not_the_weights():
    layer = moe_layer(seed=1)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(50, 64)),
                    jnp.float32)
    layer.score_bias.value = jnp.zeros(16, jnp.float32)
    ids0, w0 = (np.asarray(a) for a in layer.route(x))
    never = int(np.bincount(ids0.ravel(), minlength=16).argmin())
    bias = np.zeros(16, np.float32)
    bias[never] = 10.0
    layer.score_bias.value = jnp.asarray(bias)
    ids1, w1 = (np.asarray(a) for a in layer.route(x))
    assert (ids1 == never).any(axis=1).all()     # the choice moved
    s = np.asarray(jax.nn.sigmoid(
        np.asarray(x, np.float64) @ np.asarray(layer.router.value,
                                               np.float64)))
    want = np.take_along_axis(s, ids1, 1)
    want = want / want.sum(1, keepdims=True) * 2.5  # s of the chosen, no b
    assert np.abs(w1 - want).max() < 1e-5
    assert np.abs(w0.sum(1) - 2.5).max() < 1e-5
    assert np.abs(w1.sum(1) - 2.5).max() < 1e-5


def test_the_shared_expert_is_counted_once():
    layer = moe_layer(seed=2)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(21, 64)),
                    jnp.float32)
    y = np.asarray(layer(x))
    w = ref_layer_weights(layer)
    with jax.default_matmul_precision("highest"):
        routed = np.asarray(ref.routed_experts(x, w, REF_CFG, "f32"))
        shared = np.asarray(ref.gated_mlp(
            x, w["mlp.shared_gate"], w["mlp.shared_up"],
            w["mlp.shared_down"], "f32"))
    assert np.abs(shared).max() > 0.05      # it would show
    assert np.abs(y - (routed + shared)).max() < F32_TOL
    assert np.abs(y - (routed + 2 * shared)).max() > 0.05


# -- the ragged grouped gated MLP -------------------------------------------
def _loop_mlp(x, ids, wg, wu, wd):
    return np.stack([
        (jax.nn.silu(x[a] @ wg[ids[a]]) * (x[a] @ wu[ids[a]])) @ wd[ids[a]]
        for a in range(len(ids))])


GROUPS = {
    "random": np.random.default_rng(0).integers(0, 8, 40),
    "one_group": np.full(40, 3),
    "first_and_last": np.array([0, 7] * 8),
    "empty_between": np.array([1] * 17 + [6] * 3),
}


@pytest.mark.parametrize("kernel", [True, False], ids=["pallas", "xla"])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_ragged_gated_mlp_against_a_per_expert_loop(case, kernel):
    ids = GROUPS[case]
    rng = np.random.default_rng(1)
    E, D, F, tm = 8, 128, 128, 8
    x = jnp.asarray(rng.normal(size=(len(ids), D)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(E, D, F)) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(E, F, D)) * 0.1, jnp.float32)
    lay = ragged_layout(ids, E, tm)
    assert lay["tiles"] == ragged_tiles(len(ids), E, tm)
    counts = np.bincount(ids, minlength=E)
    assert np.asarray(lay["counts"]).tolist() == counts.tolist()
    assert int(lay["used"][0]) == int(np.ceil(counts / tm).sum())
    dest = np.asarray(lay["dest"])
    assert len(set(dest.tolist())) == len(ids)  # nobody shares a row
    tile_group = np.asarray(lay["tile_group"])
    assert (tile_group[dest // tm] == ids).all()  # a tile is one expert's
    xs = jnp.zeros((lay["tiles"] * tm, D), jnp.float32).at[dest].set(x)
    ys = ragged_gated_mlp(xs, wg, wu, wd, lay, kernel=kernel)
    want = _loop_mlp(np.asarray(x), ids, *(np.asarray(a)
                                           for a in (wg, wu, wd)))
    # float32 both ways; the kernel and the loop sum in different orders
    assert np.abs(np.asarray(ys)[dest] - want).max() < 2e-5


# -- serving ---------------------------------------------------------------
def test_abstract_parameters_hold_shapes_and_no_memory():
    with nn.abstract_parameters():
        lin = nn.Linear(1 << 20, 1 << 20)  # 4 TB if it were made
    assert lin.weight.shape == (1 << 20, 1 << 20)
    assert isinstance(lin.weight.value, jax.ShapeDtypeStruct)
    assert isinstance(nn.Linear(4, 4).weight.value, jax.Array)


def test_the_engine_serves_the_model_with_a_closed_compile_set():
    cfg = tiny_cfg(cache_len=128)
    m, w = build(cfg)
    eng = GenerationEngine(m, prompt_buckets=[16, 32], batch_size=4,
                           kv_page_size=8,
                           speculative_k=0, eos_token_id=None, name="lm")
    try:
        warm = eng.warmup()
        assert warm == 4  # two buckets, the step, the page copy
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 512, size=n).astype(np.int32)
                   for n in (5, 16, 20, 31, 9, 12)]
        outs = [np.asarray(f.result(timeout=300)) for f in
                [eng.submit(p, 6) for p in prompts]]
        assert eng.compile_count == warm
        st = eng.stats()
        assert st["moe_dropped_tokens"] == 0
        # 2 expert layers a step, each touching 1..16 experts; the tap
        # harvests a step's counts at the NEXT step, so warm-up's last
        # step is in and the newest live one is not
        assert abs(st["moe_layer_steps"] - 2 * st["decode_steps"]) <= 4
        assert (st["moe_layer_steps"] <= st["moe_experts_touched"]
                <= 16 * st["moe_layer_steps"])
        assert eng.expert_counts().sum() == st["moe_routed_tokens"]
        # the programs' own text names the mechanism each op came from
        # (a device trace does not), and asking for it compiles nothing new
        texts = eng.compiled_programs()
        assert set(texts) == {"step", "admit[16]", "admit[32]"}
        assert all("/mla/" in t and "/moe/" in t for t in texts.values())
        assert eng.compile_count == warm
    finally:
        eng.close()
    # logits, not tokens: each served token is the reference's best or
    # within float32 noise of it
    gaps = ref.served_token_gaps(w, cfg, prompts, outs)
    assert all(len(o) == 6 for o in outs)
    assert max(g["gap"].max() for g in gaps) < F32_TOL


def test_the_engine_serves_the_gather_paths_tokens_through_the_page_walk(
        latent_walk_serves_the_same):
    m, _ = build(tiny_cfg(cache_len=128))
    rng = np.random.default_rng(0)
    latent_walk_serves_the_same(
        m, [rng.integers(1, 512, size=n).astype(np.int32)
            for n in (5, 16, 20, 31, 9, 12)], 6)


#: what ``GenerationEngine`` served for this seeded GPT at commit ed5b0fe,
#: before the engine stopped reaching into ``model.gpt`` and before the
#: paged programs donated the pool (produced by this very code there)
GPT_BEFORE = [[153, 67, 508, 508, 508, 508, 508, 111],
              [73, 311, 311, 311, 311, 311, 311, 73],
              [280, 283, 360, 282, 280, 236, 174, 373],
              [402, 73, 311, 311, 3, 175, 362, 73],
              [81, 46, 46, 46, 46, 355, 3, 373],
              [508, 508, 73, 68, 73, 311, 311, 311]]


def test_gpt_through_the_engine_is_bit_identical_to_before_the_protocol():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(1234)
    m = GPTForCausalLM(GPTConfig(vocab_size=512, hidden_size=64,
                                 num_layers=2, num_heads=4,
                                 max_position=128, dropout=0.0))
    m.eval()
    eng = GenerationEngine(m, prompt_buckets=[16, 32], batch_size=4,
                           kv_page_size=8,
                           speculative_k=0, eos_token_id=None, name="g")
    try:
        # as at ed5b0fe: 4 on one device, one more on the suite's
        # 8-device global mesh (a step's outputs carry its sharding)
        warm = eng.warmup()
        assert warm == 5
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 512, size=n).astype(np.int32)
                   for n in (5, 16, 20, 31, 9, 12)]
        outs = [np.asarray(eng.submit(p, 8).result(timeout=300)).tolist()
                for p in prompts]
        assert eng.compile_count == warm
    finally:
        eng.close()
    assert outs == GPT_BEFORE


def test_the_engine_never_reaches_a_model_through_dot_gpt():
    src = open(os.path.join(REPO, "paddle_tpu", "serving",
                            "generation.py")).read()
    assert ".gpt" not in src


def test_the_configuration_carries_the_published_widths():
    cfg = loader.load_json("configs", "joyai_flash_serve.json")
    want = {"hidden_size": 2048, "num_attention_heads": 32,
            "qk_head_dim": 192, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128, "q_lora_rank": 1536,
            "kv_lora_rank": 512, "n_routed_experts": 256,
            "moe_intermediate_size": 768, "num_experts_per_tok": 8,
            "n_shared_experts": 1, "intermediate_size": 7168,
            "vocab_size": 129280}
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_nextn_predict_layers": 1}
    n = sum(int(np.prod(s)) for s, _, _ in fam.param_spec(cfg).values())
    assert abs(n - 5.558e9) < 2e6  # the cut's arithmetic: 11.12 GB of bf16
