"""Compile-only tests: the Pallas kernels of the main paths, at the real
widths the models run them at, against a DESCRIBED TPU v5e.

The chip's compiler (Mosaic + XLA:TPU) is installed with libtpu and
compiles for a topology that is described, not attached — so what the
chip would refuse is refused here, at no chip time.  Interpret mode (every
other kernel test in this suite) cannot see these failures: an i64 block
index under the package's global x64, a block that is not (8, 128)-tiled,
a kernel that overflows VMEM.  Nothing runs: these tests say "compiles,
and the kernel is in the program" (``tpu_custom_call``), never "correct"
or "fast".

The package's global x64 stays on and the matmul precision is the default,
as in production.  ``device.on_tpu`` — the one predicate every
``interpret=`` switch and kernel gate asks — is steered to True, the global
mesh is the described chip, tile configs are the heuristics (there is no
chip to measure on), and the persistent compilation cache is off around
the compiles (an entry written for a described device cannot be read back
without one, and warns).
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.framework import device as pdevice

bf16, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
fp8 = jnp.float8_e4m3fn


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    return topo.devices[0]


@pytest.fixture(autouse=True)
def _compile_for_the_chip(chip, monkeypatch, use_mesh):
    from jax.experimental.compilation_cache import compilation_cache as cc

    assert jax.config.jax_enable_x64  # production setting, kept on
    monkeypatch.setattr(pdevice, "on_tpu", lambda: True)
    use_mesh([chip])
    # conftest forces "highest" for the numpy-oracle tests; production runs
    # the default, and Mosaic refuses an fp32-precision dot on bf16/int8
    # operands
    prev_prec = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_default_matmul_precision", prev_prec)
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()


def _compiles_with_kernel(chip, fn, *shapes):
    one = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel is not in the program"


def _grad_of(fn, argnums):
    """Gradient of a scalar that NEEDS the forward's output, so the
    forward kernel stays in the program beside the backward."""
    def loss(*a):
        out = fn(*a)
        leaves = jax.tree_util.tree_leaves(out)
        return sum((l.astype(f32) ** 2).sum() for l in leaves)
    return jax.grad(loss, argnums=argnums)


# -- flash attention: the one kernel that had met a chip ----------------------
@pytest.mark.parametrize("shape", [(1, 8, 32768, 128), (1, 8, 4096, 128),
                                   (2, 12, 4096, 64)], ids=str)
@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention(chip, shape, bwd):
    from paddle_tpu.ops.flash_attention import flash_attention

    fn = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa: E731
    if bwd:
        fn = _grad_of(fn, (0, 1, 2))
    _compiles_with_kernel(chip, fn, *[(shape, bf16)] * 3)


# -- the five kernels in the benchmark cells' programs whose tile a measured
# -- search raced until PR 48: each lowers at its cell shape under the RULE's
# -- tile
def _cell_kernel(kernel):
    """``(fn, shapes, the rule's tiles)`` of one cell-run kernel at the
    widest shape its cell hands it."""
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    if kernel == "flash_fwd":              # olmo_hybrid: a one-row admission
        shape = ((1, 30, 4096, 128), bf16)
        return (lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
                [shape] * 3, fa.flash_blocks(4096, 4096, 128 * 2))
    if kernel == "flash_fwd_window":       # k_exaone: 64 heads over 8
        kv = ((1, 8, 4096, 128), bf16)
        return (lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                   window=128),
                [((1, 64, 4096, 128), bf16), kv, kv],
                [fa.window_block(4096)])
    if kernel == "moe_gated_mlp_wide":     # k_exaone: 16 held experts, a step
        gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")
        E, D, F, tm, NT = 16, 6144, 2048, 16, 32

        def fn(xs, tg, wg, wu, wd):
            lay = {"tiles": NT, "tile_m": tm, "tile_group": tg,
                   "tile_index": jnp.arange(NT, dtype=i32),
                   "used": jnp.full((1,), NT, i32)}
            return gm.ragged_gated_mlp(xs, wg, wu, wd, lay, kernel=True)

        return (fn, [((NT * tm, D), bf16), ((NT,), i32), ((E, D, F), bf16),
                     ((E, D, F), bf16), ((E, F, D), bf16)],
                [gm.wide_block(tm, D, F, 2)])
    if kernel == "layernorm_residual":     # bert_base: 256 x 128 rows
        from paddle_tpu.ops.fused_layernorm import layernorm_residual, ln_block
        return (layernorm_residual,
                [((32768, 768), bf16)] * 2 + [((768,), bf16)] * 2,
                [ln_block(32768)])
    from paddle_tpu.ops.fused_softmax_xent import (softmax_cross_entropy,
                                                   xent_blocks)
    return (softmax_cross_entropy,         # bert_base: 256 x 20 masked rows
            [((5120, 30522), bf16), ((5120,), i32)], xent_blocks(5120, 30522))


@pytest.mark.parametrize("kernel", [
    "flash_fwd", "flash_fwd_window", "moe_gated_mlp_wide",
    "layernorm_residual", "softmax_xent"])
def test_cell_kernel_lowers_at_its_cell_shape_under_the_rules_tile(chip,
                                                                   kernel):
    import re

    fn, shapes, tiles = _cell_kernel(kernel)
    _compiles_with_kernel(chip, fn, *shapes)
    # and the blocks of the call that was lowered are the rule's
    blocks = set(map(int, re.findall(r"block_size=(\d+)", str(jax.make_jaxpr(
        fn)(*[jax.ShapeDtypeStruct(*s) for s in shapes])))))
    assert set(tiles) <= blocks, (kernel, tiles, sorted(blocks))


def test_flash_forward_of_float32_heads_of_256_keeps_512_blocks(chip):
    # past 512 bytes a head the rule keeps 512-blocks: the 1024 it takes
    # below that run out of VMEM here (RESOURCE_EXHAUSTED at compile)
    from paddle_tpu.ops.flash_attention import flash_attention

    _compiles_with_kernel(
        chip, lambda q, k, v: flash_attention(q, k, v, causal=True),
        *[((2, 4, 2048, 256), f32)] * 3)


# -- the (i32, i64) class: BERT/GPT epilogues, serving linears, ResNet tail ---
@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd+bwd"])
def test_layernorm_residual_bert_batch(chip, bwd):
    from paddle_tpu.ops.fused_layernorm import layernorm_residual

    fn = layernorm_residual
    if bwd:
        fn = _grad_of(fn, (0, 1, 2, 3))
    _compiles_with_kernel(chip, fn, ((32768, 768), bf16), ((32768, 768), bf16),
                          ((768,), bf16), ((768,), bf16))


@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd+bwd"])
def test_softmax_xent_bert_mlm_head(chip, bwd):
    from paddle_tpu.ops.fused_softmax_xent import softmax_cross_entropy

    fn = softmax_cross_entropy
    if bwd:
        fn = _grad_of(fn, 0)
    _compiles_with_kernel(chip, fn, ((5120, 30522), bf16), ((5120,), i32))


@pytest.mark.parametrize("wdtype", [i8, fp8], ids=["int8", "fp8"])
def test_quantized_matmul_gpt_mlp(chip, wdtype):
    from paddle_tpu.ops.quantized_matmul import quantized_linear

    _compiles_with_kernel(chip, quantized_linear, ((64, 768), bf16),
                          ((768, 3072), wdtype), ((3072,), f32),
                          ((3072,), f32))


@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd+bwd"])
def test_conv1x1_bn_resnet_stage1(chip, bwd):
    # conv1x1_bn_stats + bn_apply_relu, as BottleneckBlock._fused_tail
    # calls them at batch 128: [128*56*56, 64] x [64, 256]
    from paddle_tpu.ops.fused_conv1x1_bn import conv1x1_bn_relu

    def fn(x, w, g, b, res):
        return conv1x1_bn_relu(x, w, g, b, residual=res,
                               fused_epilogue=True)[0]

    if bwd:
        fn = _grad_of(fn, (0, 1, 2, 3, 4))
    _compiles_with_kernel(chip, fn, ((401408, 64), bf16), ((64, 256), bf16),
                          ((256,), bf16), ((256,), bf16),
                          ((401408, 256), bf16))


# -- the tiling class ---------------------------------------------------------
@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd+bwd"])
def test_grouped_matmul_moe_experts(chip, bwd):
    from paddle_tpu.ops.grouped_matmul import grouped_matmul

    fn = grouped_matmul
    if bwd:
        fn = _grad_of(fn, (0, 1))
    _compiles_with_kernel(chip, fn, ((8, 1024, 256), bf16),
                          ((8, 256, 1024), bf16), ((8,), i32))


@pytest.mark.parametrize("T", [1, 5], ids=["decode", "verify1+4"])
@pytest.mark.parametrize("page", [16, 128])
@pytest.mark.parametrize("pool", [f32, i8], ids=["float", "int8"])
def test_paged_flash_decode_gpt2_small(chip, pool, page, T):
    # GPT-2-small decode as the chip benchmark's cells run it: 12 heads x
    # 64, cache 1024, 32 slots over 2049 pages of 16 (257 of 128); the
    # pool in its stored order, a token's heads side by side in one
    # 768-wide row, left in HBM; the page table and the sweep bounds are
    # the kernel's two scalar-prefetch operands
    from paddle_tpu.models.gpt import _paged_flash
    from paddle_tpu.ops.paged_attention import paged_flash_decode

    assert _paged_flash(64, page)  # the model's gate selects the kernel
    B, H, hd, G = 32, 12, 64, 1024 // page
    pages = 2048 * 16 // page + 1
    shapes = [((B, H, T, hd), f32), ((pages, page, H * hd), pool),
              ((pages, page, H * hd), pool), ((B, G), i32),
              ((B, G * page), i32), ((B, T), i32), ((B,), i32)]
    if pool == i8:
        shapes += [((pages, page, H), f32)] * 2
    _compiles_with_kernel(chip, paged_flash_decode, *shapes)


@pytest.mark.parametrize("R,T", [(2, 64), (2, 512), (2, 640), (2, 768),
                                 (2, 1024), (1, 1024)],
                         ids=lambda v: str(v))
def test_paged_flash_admission_prefill_gpt2_small(chip, R, T):
    # the paged admission program's attention: the same kernel over the
    # engine's two rows x the narrowest prompt bucket the chip benchmark
    # serves (chat_open 64: one query tile, the grid a decode step has) and
    # docs_closed's three, in query tiles of 256 or 320 rows each with its
    # own sweep bound, all 12 heads a grid step; and at 1024 rows, a width the
    # kernel could not hold while a step held the bucket whole (VMEM).
    # Float pages of 16
    import re

    from paddle_tpu.ops.paged_attention import (paged_flash_decode,
                                                query_tile)
    from paddle_tpu.serving.generation import admit_rows

    H, hd, page = 12, 64, 16
    assert admit_rows(T, 32) == 2
    G, pages = 1024 // page, 2048 + 1
    nq = -(-T // query_tile(T))  # 1, 2, 2, 3, 4, 4 tiles
    shapes = [((R, H, T, hd), f32),
              ((pages, page, H * hd), f32), ((pages, page, H * hd), f32),
              ((R, G), i32), ((R, G * page), i32), ((R, T), i32),
              ((R,) if nq == 1 else (R, nq), i32)]
    _compiles_with_kernel(chip, paged_flash_decode, *shapes)
    grid = re.findall(r"grid=\([0-9, ]*\)", str(jax.make_jaxpr(
        paged_flash_decode)(*[jax.ShapeDtypeStruct(*s) for s in shapes])))
    assert grid == [f"grid=({R}, 1)" if nq == 1 else f"grid=({R}, {nq}, 1)"]


def test_paged_decode_has_nothing_to_search_and_the_step_keeps_its_grid():
    # the tile is fixed by the shape and the heads a step by rule (nothing
    # is searched: tests/test_autotune.py), so two checkouts of one tree
    # build one program.  A one-tile call (the decode step's [32, 1], the
    # verify width) keeps the grid (slots, head blocks) it had
    import re

    from paddle_tpu.ops.paged_attention import paged_flash_decode

    B, H, hd, page, G, pages = 32, 12, 64, 16, 64, 2049
    for T in (1, 5):
        shapes = [((B, H, T, hd), f32), ((pages, page, H * hd), f32),
                  ((pages, page, H * hd), f32), ((B, G), i32),
                  ((B, G * page), i32), ((B, T), i32), ((B,), i32)]
        text = str(jax.make_jaxpr(paged_flash_decode)(
            *[jax.ShapeDtypeStruct(*s) for s in shapes]))
        assert re.findall(r"grid=\([0-9, ]*\)", text) == ["grid=(32, 1)"]


# -- the stored order of GPT-2's page pool ------------------------------------
@pytest.fixture(scope="module")
def gpt2_small_engine():
    # the engine of the chip benchmark's GPT-2 cells
    # (benchmarks/configs/gpt2_small_serve.json, traffic docs_closed) with
    # weights that are shapes only: nothing runs, its jitted programs are
    # lowered for the described chip
    from paddle_tpu import nn
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.generation import GenerationEngine

    with nn.abstract_parameters():
        model = GPTForCausalLM(GPTConfig(dropout=0.0))
    eng = GenerationEngine(
        model, prompt_buckets=[512, 640, 768], batch_size=32,
        kv_page_size=16, speculative_k=0,
        eos_token_id=None, name="compile-only")
    yield eng
    eng.close()


def test_gpt2_engine_speaks_the_paged_protocol_only(gpt2_small_engine):
    # one cache form: the model answers forward_paged and the page ops and
    # has no dense ring verbs left, and the engine holds the paged programs
    # and no others (a program that is not built cannot be compiled late)
    from paddle_tpu.models.gpt import GPTForCausalLM, GPTModel

    for cls in (GPTForCausalLM, GPTModel):
        for verb in ("forward_cached", "init_cache", "write_slots",
                     "reset_slots"):
            assert not hasattr(cls, verb), (cls.__name__, verb)
        for verb in ("forward_paged", "init_paged_cache", "copy_pages",
                     "gather_pages", "scatter_pages"):
            assert callable(getattr(cls, verb))
    eng = gpt2_small_engine
    assert sorted(eng._traces) == ["admit", "cow", "decode", "export",
                                   "import"]
    for gone in ("_prefill", "_decode", "_admit", "_evict", "_slot_loop",
                 "_run_batch", "_init_state"):
        assert not hasattr(eng, gone), gone
    assert eng._thread._target == eng._paged_loop


def _stray_pool_results(text, pool_elems):
    """Instructions of an optimized HLO program whose result is an array
    of exactly ``pool_elems`` elements (one K or V pool, in any shape or
    order) and that are NOT one of: the
    program's parameter, the in-place write (``scatter`` /
    ``dynamic-update-slice``, or a fusion whose body holds one), the
    Pallas kernel, or the plumbing around them (tuples, bitcasts).
    Returns ``(strays, kinds seen)``."""
    import math
    import re

    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"^(%?[\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S)}
    stray, kinds = [], set()
    for m in re.finditer(
            r"^\s*(?:ROOT )?(\S+) = [a-z0-9]+\[([0-9,]+)\]\S* ([a-z\-]+)\("
            r"([^\n]*)", text, re.M):
        name, dims, op, rest = m.groups()
        if math.prod(int(d) for d in dims.split(",")) != pool_elems:
            continue
        kinds.add(op)
        if op in ("parameter", "scatter", "dynamic-update-slice", "tuple",
                  "get-tuple-element", "bitcast"):
            continue
        if op == "custom-call" and "tpu_custom_call" in rest:
            continue
        if op == "fusion":
            body = bodies.get(re.search(r"calls=(%?[\w.\-]+)", rest).group(1))
            if body and re.search(r" (scatter|dynamic-update-slice)\(", body):
                continue
        stray.append((op, name))
    return stray, kinds


def test_stray_pool_results_sees_a_copy():
    text = """HloModule m
%fused_computation.1 (p: f32[9,4,8]) -> f32[9,4,8] {
  %p = f32[9,4,8]{2,1,0} parameter(0)
  ROOT %s = f32[9,4,8]{2,1,0} scatter(%p, %p, %p), to_apply=%x
}
%fused_computation.2 (p: f32[9,4,8]) -> f32[9,4,8] {
  %p = f32[9,4,8]{2,1,0} parameter(0)
  ROOT %c = f32[9,4,8]{0,2,1} copy(%p)
}
ENTRY %main (a: f32[9,4,8]) -> f32[9,4,8] {
  %a = f32[9,4,8]{2,1,0} parameter(0)
  %f1 = f32[9,4,8]{2,1,0} fusion(%a), kind=kCustom, calls=%fused_computation.1
  %b = f32[36,8]{1,0} bitcast(%f1)
  %f2 = f32[9,4,8]{0,2,1} fusion(%f1), kind=kLoop, calls=%fused_computation.2
  %small = f32[4,8]{1,0} copy(%a)
  ROOT %copy.7 = f32[9,4,8]{2,1,0} copy(%f2)
}
"""
    stray, kinds = _stray_pool_results(text, 9 * 4 * 8)
    assert sorted(stray) == [("copy", "%c"), ("copy", "%copy.7"),
                             ("fusion", "%f2")]
    assert {"parameter", "scatter", "fusion", "bitcast", "copy"} == kinds


@pytest.mark.parametrize("program", ["step", "admit512", "admit640",
                                     "admit768"])
def test_gpt2_paged_programs_never_copy_the_pool(chip, gpt2_small_engine,
                                                 program):
    # 24 K/V arrays of [2049, 16, 768] float32 (100 MB each) go in, are
    # scattered into in place, read by the kernel and handed back: no
    # instruction of the optimized program may produce another such
    # array (a `copy` to another order cost 40 ms in every execution
    # while the pool was stored [P+1, H, page, hd])
    eng = gpt2_small_engine
    one = SingleDeviceSharding(chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, i32, sharding=one)

    B, R, C, page, pages = 32, 2, 1024, 16, 2048
    G = C // page
    assert (eng._batch, eng._admit_rows, eng._C, eng._page,
            eng._kv_pages) == (B, {512: R, 640: R, 768: R}, C, page, pages)
    pool = on_chip(jax.eval_shape(
        lambda: eng._model.init_paged_cache(pages, page)))
    assert pool["layers"][0]["k"].shape == (pages + 1, page, 768)
    params, buffers = on_chip(eng._params), on_chip(eng._buffers)
    if program == "step":
        lowered = eng._step_jit.lower(params, buffers,
                                      ints(B, 2 + C + G), ints(B, 1), pool)
    else:
        T = int(program[len("admit"):])
        lowered = eng._padmit.lower(params, buffers, ints(R, T), ints(R, T),
                                    ints(R, C), ints(R, G), ints(R), pool,
                                    None)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 12  # the kernel, every layer
    stray, kinds = _stray_pool_results(text, (pages + 1) * page * 768)
    assert "parameter" in kinds and kinds & {"scatter",
                                             "dynamic-update-slice"}
    assert not stray, f"whole-pool results outside the allowed kinds: {stray}"
    # the pool is donated: the program's outputs alias its arguments
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 24 * (pages + 1) * page * 768 * 4
    assert mem.temp_size_in_bytes < 100 * 2 ** 20  # no second pool array


@pytest.mark.parametrize("rows,tile", [(32 * 8, 16), (2 * 4096 * 8, 128)],
                         ids=["decode", "admit_2x4096"])
def test_ragged_gated_mlp_256_experts_of_768(chip, rows, tile):
    # the routed experts of benchmarks/configs/joyai_flash_serve.json: 256
    # experts of 2048 x 768, top-8; a decode step's 32 slots and an
    # admission chunk of 2 rows x the 4096 bucket.  The three weight
    # blocks of one expert (9.4 MB, double-buffered) need more VMEM than
    # the compiler's default scope: the kernel asks for it
    from paddle_tpu.ops.grouped_matmul import (ragged_gated_mlp,
                                               ragged_layout)

    E, D, F = 256, 2048, 768

    def fn(x, ids, wg, wu, wd):
        lay = ragged_layout(ids, E, tile)
        src = jnp.full((lay["tiles"] * tile,), rows, i32).at[
            lay["dest"]].set(jnp.arange(rows, dtype=i32))
        xs = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[src]
        return ragged_gated_mlp(xs, wg, wu, wd, lay)[lay["dest"]]

    _compiles_with_kernel(chip, fn, ((rows, D), bf16), ((rows,), i32),
                          ((E, D, F), bf16), ((E, D, F), bf16),
                          ((E, F, D), bf16))


@pytest.mark.parametrize("T", [1536, 4096])
def test_latent_prefill_attention_32_heads_of_192(chip, T):
    # an admission chunk of the joyai_flash cell: 2 rows x a prompt bucket
    # against a slot's 4608-position view, 32 heads of 128 + 64 | 128
    from paddle_tpu.ops.latent_attention import (latent_prefill_attention,
                                                 latent_prefill_eligible)

    B, H, C = 2, 32, 4608
    assert latent_prefill_eligible(128, 64, 128, T, C)

    def fn(qn, qr, kn, kr, v, qp, kp):
        return latent_prefill_attention(qn, qr, kn, kr, v, qp, kp, C,
                                        192 ** -0.5)

    _compiles_with_kernel(
        chip, fn, ((B, H, T, 128), bf16), ((B, H, T, 64), bf16),
        ((B, H, C, 128), bf16), ((B, C, 64), bf16), ((B, H, C, 128), bf16),
        ((B, T), i32), ((B, C), i32))


# -- the hybrid decoder's kernels at the olmo_hybrid cell's shapes ------------
@pytest.mark.parametrize("T", [1536, 4096])
def test_gated_delta_chunk_30_heads_of_96_by_192(chip, T):
    # an admission chunk of the olmo_hybrid cell: 2 rows x a prompt bucket,
    # 30 heads with a [96, 192] float32 state, chunks of 64; the whole op,
    # the WY operands in XLA and the walk over chunks in the kernel
    from paddle_tpu.ops import gated_delta as gd

    assert gd.gated_delta_eligible()
    B, H, dk, dv = 2, 30, 96, 192
    _compiles_with_kernel(
        chip, gd.gated_delta_chunk, ((B, T, H, dk), f32), ((B, T, H, dk), f32),
        ((B, T, H, dv), f32), ((B, T, H), f32), ((B, T, H), f32))


def test_gated_delta_step_updates_17_rows_of_state_in_place(chip):
    # a decode step of the cell: 16 slots of the 17 stored rows, donated, so
    # that the kernel's alias is the program's
    from paddle_tpu.ops import gated_delta as gd

    B, H, dk, dv = 16, 30, 96, 192
    one = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one) for s in (
        (B, H, dk), (B, H, dk), (B, H, dv), (B, H), (B, H),
        (B + 1, H, dk, dv))]
    text = jax.jit(gd.gated_delta_step, donate_argnums=(5,)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text and "gated_delta_step" in text
    # updated where it lies: the 37.6 MB of states are not copied around it
    assert "input_output_alias" in text
    assert not [l for l in text.splitlines()
                if " copy(" in l and "f32[17,30,96,192]" in l.split("=")[1]
                .split("copy(")[0]]


def test_paged_decode_reads_30_heads_of_128_at_the_decode_width(chip):
    # the hybrid model's four full layers decode through GPT-2's kernel as
    # it is: 16 slots x 288 pages of 16, rows of 30 x 128 = 3840 lanes
    from paddle_tpu.models.hybrid import _paged_flash
    from paddle_tpu.ops.paged_attention import paged_flash_decode

    assert _paged_flash(128, 16)
    B, H, hd, page, G = 16, 30, 128, 16, 288
    pages = B * G + 1
    _compiles_with_kernel(
        chip, paged_flash_decode, ((B, H, 1, hd), bf16),
        ((pages, page, H * hd), bf16), ((pages, page, H * hd), bf16),
        ((B, G), i32), ((B, G * page), i32), ((B, 1), i32), ((B,), i32))


# -- a one-row admission of the two long-prompt models ------------------------
@pytest.mark.parametrize("config,cls,layers,kernels", [
    # the dense layer and one expert layer: prompt attention in both, the
    # experts' 128-row tile in one
    ("joyai_flash_serve", "LatentMoEForCausalLM", 2,
     {"latent_prefill_attention": 2, "moe_gated_mlp_tm128": 1}),
    # one period: the chunk walk in three linear layers, and the full
    # layer's prompt attention (ops/flash_attention.py names no kernel)
    ("olmo_hybrid_serve", "HybridForCausalLM", 4,
     {"gated_delta_chunk": 3, "": 4}),
], ids=["latent", "hybrid"])
def test_a_one_row_admission_of_the_long_prompt_models(chip, config, cls,
                                                       layers, kernels):
    # the ragdocs_closed cells' engines (benchmarks/configs/<config>.json:
    # published widths, a few of the layers, weights that are shapes only).
    # Every bucket there is past the admission cap, so the program is
    # [1, bucket]; lowered at the widest for the described chip, it takes
    # the TPU-only branches of a one-row call that no CPU test runs (the
    # kernel gates, the experts' row tile at 4096 x 8 routed rows, the
    # chunked scan over one row)
    import json
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks.harness import loader
    from paddle_tpu import nn
    from paddle_tpu.serving.generation import GenerationEngine

    bench = os.path.join(repo, "benchmarks")
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = {**json.load(f), "num_hidden_layers": layers}
    with open(os.path.join(bench, "traffic", "ragdocs_closed.json")) as f:
        buckets = json.load(f)["prompt_buckets"]
    fam = loader.load_module("families", cfg["family"], bench)
    serve = cfg["serve"]
    with nn.abstract_parameters():
        model = getattr(fam, cls)(fam.model_config(cfg))
    eng = GenerationEngine(
        model, prompt_buckets=buckets, batch_size=serve["batch_size"],
        cache_len=serve["cache_len"], kv_page_size=serve["kv_page_size"],
        speculative_k=0, eos_token_id=None, name="compile-only-1row")
    try:
        assert eng._admit_rows == {b: 1 for b in buckets}
        one = SingleDeviceSharding(chip)

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one), tree)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, i32, sharding=one)

        T, C = buckets[-1], serve["cache_len"]
        G = C // serve["kv_page_size"]
        text = eng._padmit.lower(
            on_chip(eng._params), on_chip(eng._buffers), ints(1, T),
            ints(1, T), ints(1, C), ints(1, G), ints(1),
            on_chip(jax.eval_shape(eng._empty_pool)), None,
            ints(1) if eng._slot_state else None).compile().as_text()
    finally:
        eng.close()
    # a kernel is a custom call whose instruction carries its name
    calls = [line.split("=")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    for kernel, n in kernels.items():
        assert sum(kernel in c for c in calls) == n, (kernel, calls)


# -- grouped heads, 128 x 128 states and a share of the experts: the kernels
# -- at the qwen3_next_serve configuration's shapes -----------------------------
def test_paged_decode_reads_2_kv_heads_of_256_for_16_query_heads(chip):
    # a decode step of the qwen3_next cell: 64 slots x 128 pages of 16, pool
    # rows of 2 x 256 = 512 lanes; the 16 query heads are the rows of one
    # head as wide as a pool row, head h in the lanes of K/V head h // 8
    from paddle_tpu.models.hybrid import _paged_flash
    from paddle_tpu.ops.paged_attention import paged_flash_decode

    assert _paged_flash(256, 16)
    B, H, Hkv, hd, page, G = 64, 16, 2, 256, 16, 128
    pages = B * G + 1
    _compiles_with_kernel(
        chip, paged_flash_decode, ((B, H, 1, hd), bf16),
        ((pages, page, Hkv * hd), bf16), ((pages, page, Hkv * hd), bf16),
        ((B, G), i32), ((B, G * page), i32), ((B, 1), i32), ((B,), i32))


def test_paged_decode_gives_grouped_heads_their_query_rows_at_a_width(chip):
    # the general form (a verify width of 4): the 8 query heads of a K/V
    # head become 8 x 4 query rows of it
    from paddle_tpu.ops.paged_attention import paged_flash_decode

    B, H, Hkv, hd, page, G, T = 8, 16, 2, 256, 16, 128, 4
    pages = B * G + 1
    _compiles_with_kernel(
        chip, paged_flash_decode, ((B, H, T, hd), bf16),
        ((pages, page, Hkv * hd), bf16), ((pages, page, Hkv * hd), bf16),
        ((B, G), i32), ((B, G * page), i32), ((B, T), i32), ((B,), i32))


def test_flash_attention_16_query_heads_over_2_kv_heads_of_256(chip):
    # the admission's prompt attention: 2 rows x the 1024 bucket; the K/V
    # blocks of query head h are those of head h // 8 by the index map
    from paddle_tpu.ops.flash_attention import flash_attention

    _compiles_with_kernel(
        chip, lambda q, k, v: flash_attention(q, k, v, causal=True),
        ((2, 16, 1024, 256), bf16), ((2, 2, 1024, 256), bf16),
        ((2, 2, 1024, 256), bf16))


@pytest.mark.parametrize("T", [256, 1024])
def test_gated_delta_chunk_16_key_heads_32_value_heads_of_128(chip, T):
    # an admission of 2 rows x a bucket: q and k of 16 heads, v, the gates
    # and the [128, 128] float32 state of 32
    from paddle_tpu.ops import gated_delta as gd

    B, Hk, Hv, d = 2, 16, 32, 128
    _compiles_with_kernel(
        chip, gd.gated_delta_chunk, ((B, T, Hk, d), f32), ((B, T, Hk, d), f32),
        ((B, T, Hv, d), f32), ((B, T, Hv), f32), ((B, T, Hv), f32))


def test_gated_delta_step_32_states_of_128_by_128_from_16_key_heads(chip):
    # a decode step: 64 slots of the 65 stored rows (2.1 MB a slot),
    # donated; a block of 8 value heads fetches its 4 key heads
    from paddle_tpu.ops import gated_delta as gd

    B, Hk, Hv, d = 64, 16, 32, 128
    one = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one) for s in (
        (B, Hk, d), (B, Hk, d), (B, Hv, d), (B, Hv), (B, Hv),
        (B + 1, Hv, d, d))]
    # a rule of the shape since PR 44, no measured search
    assert gd.step_heads(*args) == 8
    text = jax.jit(gd.gated_delta_step, donate_argnums=(5,)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text and "gated_delta_step" in text
    assert "input_output_alias" in text
    assert not [l for l in text.splitlines()
                if " copy(" in l and "f32[65,32,128,128]" in l.split("=")[1]
                .split("copy(")[0]]


@pytest.mark.parametrize("rows,tile", [(64 * 10, 16), (2 * 1024 * 10, 128)],
                         ids=["decode", "admit_2x1024"])
def test_ragged_gated_mlp_128_held_of_512_experts_of_512(chip, rows, tile):
    # one chip's share of a qwen3_next layer: the router's 512 ids, 128
    # experts of 2048 x 512 held; a pair whose expert is absent has no row
    from paddle_tpu.ops.grouped_matmul import (ragged_gated_mlp,
                                               ragged_layout)

    held, D, F = 128, 2048, 512

    def fn(x, ids, wg, wu, wd):
        lay = ragged_layout(ids, held, tile, partial=True)
        n = lay["tiles"] * tile
        src = jnp.full((n,), rows, i32).at[lay["dest"]].set(
            jnp.arange(rows, dtype=i32), mode="drop")
        xs = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[src]
        ys = ragged_gated_mlp(xs, wg, wu, wd, lay)
        return jnp.where(lay["present"][:, None],
                         ys[jnp.minimum(lay["dest"], n - 1)], 0)

    _compiles_with_kernel(chip, fn, ((rows, D), bf16), ((rows,), i32),
                          ((held, D, F), bf16), ((held, D, F), bf16),
                          ((held, F, D), bf16))


@pytest.mark.parametrize("program,kernels", [
    # one period of the model: three state kernels, the full layer's
    # paged_decode, four expert kernels at the 16-row tile
    ("step", {"gated_delta_step": 3, "paged_decode": 1,
              "moe_gated_mlp_tm16": 4, "": 8}),
    # a [2, 1024] admission: three chunk walks, the grouped flash kernel
    # and four expert kernels at the 128-row tile
    ("admit", {"gated_delta_chunk": 3, "moe_gated_mlp_tm128": 4,
               "flash_fwd_grouped": 1, "": 8}),
])
def test_the_qwen3_next_engine_lowers_its_programs_for_the_chip(
        chip, program, kernels):
    # benchmarks/configs/qwen3_next_serve.json at published widths, one
    # period of its layers, weights that are shapes only: 64 slots x 2048
    # positions, the engine's own jitted programs with every TPU-only
    # branch taken (slot state AND experts in one program)
    import json
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks.harness import loader
    from paddle_tpu import nn
    from paddle_tpu.serving.generation import GenerationEngine

    bench = os.path.join(repo, "benchmarks")
    with open(os.path.join(bench, "configs", "qwen3_next_serve.json")) as f:
        cfg = {**json.load(f), "num_hidden_layers": 4}
    with open(os.path.join(bench, "traffic", "longgen_closed.json")) as f:
        buckets = json.load(f)["prompt_buckets"]
    fam = loader.load_module("families", cfg["family"], bench)
    serve = cfg["serve"]
    with nn.abstract_parameters():
        model = fam.HybridForCausalLM(fam.model_config(cfg))
    eng = GenerationEngine(
        model, prompt_buckets=buckets, batch_size=serve["batch_size"],
        cache_len=serve["cache_len"], kv_page_size=serve["kv_page_size"],
        speculative_k=0, eos_token_id=None, name="compile-only-qnx")
    try:
        assert eng._admit_rows == {b: 2 for b in buckets}
        one = SingleDeviceSharding(chip)

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one), tree)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, i32, sharding=one)

        B, T, C = serve["batch_size"], buckets[-1], serve["cache_len"]
        G = C // serve["kv_page_size"]
        pool = on_chip(jax.eval_shape(eng._empty_pool))
        assert pool["layers"][3]["k"].shape == (B * G + 1, 16, 512)
        assert pool["layers"][0]["state"].shape == (B + 1, 32, 128, 128)
        params, buffers = on_chip(eng._params), on_chip(eng._buffers)
        if program == "step":
            text = eng._step_jit.lower(params, buffers, ints(B, 2 + C + G),
                                       ints(B, 1), pool).compile().as_text()
        else:
            text = eng._padmit.lower(
                params, buffers, ints(2, T), ints(2, T), ints(2, C),
                ints(2, G), ints(2), pool, None, ints(2)).compile().as_text()
    finally:
        eng.close()
    calls = [line.split("=")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    for kernel, n in kernels.items():
        assert sum(kernel in c for c in calls) == n, (kernel, calls)


# -- window rings, a window in the flash forward and an expert wider than
# -- VMEM: the kernels at the k_exaone_serve configuration's shapes ---------------
@pytest.mark.parametrize("T", [1536, 4096])
@pytest.mark.parametrize("block", [128, 256, 512])
def test_windowed_flash_forward_64_heads_over_8_kv_heads_of_128(chip, T,
                                                                block):
    # a one-row admission's window layer: every candidate of the search
    from paddle_tpu.ops.flash_attention import flash_attention

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, window=128,
                               block_q=block)

    one = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(s, bf16, sharding=one)
            for s in ((1, 64, T, 128), (1, 8, T, 128), (1, 8, T, 128))]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "flash_fwd_window" in text


def test_window_decode_reads_32_rings_of_128_rows_of_1024_lanes(chip):
    # a decode step's window layer: the rings are a pool of one 128-row
    # page a slot (row 32 the drop row), 64 query heads on 8 K/V heads
    from paddle_tpu.models.hybrid import _paged_flash
    from paddle_tpu.ops.paged_attention import paged_flash_decode

    assert _paged_flash(128, 128)
    B, H, Hkv, hd, W = 32, 64, 8, 128, 128

    def fn(q, rk, rv, tab, held, pos, bound):
        return paged_flash_decode(q, rk, rv, tab, held, pos, bound,
                                  name="window_decode")

    one = SingleDeviceSharding(chip)
    shapes = (((B, H, 1, hd), bf16), ((B + 1, W, Hkv * hd), bf16),
              ((B + 1, W, Hkv * hd), bf16), ((B, 1), i32), ((B, W), i32),
              ((B, 1), i32), ((B,), i32))
    text = jax.jit(fn).lower(*[jax.ShapeDtypeStruct(s, d, sharding=one)
                               for s, d in shapes]).compile().as_text()
    assert "tpu_custom_call" in text and "window_decode" in text


def test_paged_decode_reads_8_kv_heads_of_128_for_64_query_heads(chip):
    # a decode step's global layer: 32 slots x 288 pages of 16, pool rows
    # of 8 x 128 = 1024 lanes, rep 8
    from paddle_tpu.ops.paged_attention import paged_flash_decode

    B, H, Hkv, hd, page, G = 32, 64, 8, 128, 16, 288
    pages = B * G + 1
    _compiles_with_kernel(
        chip, paged_flash_decode, ((B, H, 1, hd), bf16),
        ((pages, page, Hkv * hd), bf16), ((pages, page, Hkv * hd), bf16),
        ((B, G), i32), ((B, G * page), i32), ((B, 1), i32), ((B,), i32))


@pytest.mark.parametrize("rows,tile,blocks", [
    (32 * 8, 16, (1024, 512, 256)), (4096 * 8, 128, (512, 256))],
    ids=["decode", "admit_1x4096"])
def test_ragged_gated_mlp_16_held_of_128_experts_of_6144_by_2048(
        chip, rows, tile, blocks):
    # one chip's share of a k_exaone layer: an expert's three matrices,
    # double-buffered, are 151 MB, so the kernel tiles the expert's width;
    # every candidate of the search, and the call the layer makes
    import importlib

    gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")
    held, D, F = 16, 6144, 2048
    assert tuple(gm._wide_blocks(tile, D, F, 2)) == blocks

    def layer(block_f):
        def fn(x, ids, wg, wu, wd):
            lay = gm.ragged_layout(ids, held, tile, partial=True)
            n = lay["tiles"] * tile
            src = jnp.full((n,), rows, i32).at[lay["dest"]].set(
                jnp.arange(rows, dtype=i32), mode="drop")
            xs = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[src]
            ys = (gm.ragged_gated_mlp(xs, wg, wu, wd, lay)
                  if block_f is None
                  else gm._gated_mlp_wide(xs, wg, wu, wd, lay, block_f))
            return jnp.where(lay["present"][:, None],
                             ys[jnp.minimum(lay["dest"], n - 1)], 0)
        return fn

    shapes = (((rows, D), bf16), ((rows,), i32), ((held, D, F), bf16),
              ((held, D, F), bf16), ((held, F, D), bf16))
    for block_f in (None, *blocks):
        _compiles_with_kernel(chip, layer(block_f), *shapes)


# -- Kimi Delta Attention at the kimi_linear cell's shapes ---------------------
@pytest.mark.parametrize("T", [1536, 4096])
def test_kda_chunk_32_heads_of_128_by_128_with_a_vector_decay(chip, T):
    # a one-row admission of the kimi_linear cell: 32 heads, a [128, 128]
    # float32 state, one decay a key channel; the whole op, the sub-block
    # WY operands in XLA and the walk over chunks (the state transposed in
    # VMEM) in the kernel
    from paddle_tpu.ops import kda

    B, H, d = 1, 32, 128
    one = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one) for s in (
        (B, T, H, d), (B, T, H, d), (B, T, H, d), (B, T, H, d), (B, T, H))]
    exe = jax.jit(kda.kda_chunk).lower(*args).compile()
    text = exe.as_text()
    assert "tpu_custom_call" in text and "kda_chunk" in text
    # the pairwise decays of the diagonal sub-blocks ([.., 16, 16, 128] a
    # sub-block: 1 GB at 4096 tokens) are summed where they are made
    assert exe.memory_analysis().temp_size_in_bytes < 1.0e9


def test_kda_step_updates_129_rows_of_state_in_place(chip):
    # a decode step of the cell: 128 slots of the 129 stored rows, donated
    from paddle_tpu.ops import kda

    B, H, d = 128, 32, 128
    assert kda.step_heads(H, d) * 3 <= d
    one = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one) for s in (
        (B, H, d), (B, H, d), (B, H, d), (B, H, d), (B, H),
        (B + 1, H, d, d))]
    text = jax.jit(kda.kda_step, donate_argnums=(5,)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text and "kda_step" in text
    assert "input_output_alias" in text
    assert not [l for l in text.splitlines()
                if " copy(" in l and "f32[129,32,128,128]" in l.split("=")[1]]


@pytest.mark.parametrize("program,kernels", [
    # one period of the model, layer 0 dense: three window layers' decode
    # over the rings, the global layer's paged_decode, three expert kernels
    ("step", {"window_decode": 3, "paged_decode": 1,
              "moe_gated_mlp_tm16": 3, "": 7}),
    # a [1, 4096] admission: three windowed flash calls, the global layer's
    # grouped flash call, three expert kernels at the 128-row tile
    ("admit", {"flash_fwd_window": 3, "flash_fwd_grouped": 1,
               "moe_gated_mlp_tm128": 3, "": 7}),
])
def test_the_k_exaone_engine_lowers_its_programs_for_the_chip(
        chip, program, kernels):
    # benchmarks/configs/k_exaone_serve.json at published widths, one
    # period of its layers, weights that are shapes only: 32 slots x 4608
    # positions, the engine's own jitted programs with every TPU-only
    # branch taken (rings, pages AND experts in one program)
    import json
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks.harness import loader
    from paddle_tpu import nn
    from paddle_tpu.serving.generation import GenerationEngine

    bench = os.path.join(repo, "benchmarks")
    with open(os.path.join(bench, "configs", "k_exaone_serve.json")) as f:
        cfg = {**json.load(f), "num_hidden_layers": 4}
    with open(os.path.join(bench, "traffic", "ragdocs_closed.json")) as f:
        buckets = json.load(f)["prompt_buckets"]
    fam = loader.load_module("families", cfg["family"], bench)
    serve = cfg["serve"]
    with nn.abstract_parameters():
        model = fam.HybridForCausalLM(fam.model_config(cfg))
    eng = GenerationEngine(
        model, prompt_buckets=buckets, batch_size=serve["batch_size"],
        cache_len=serve["cache_len"], kv_page_size=serve["kv_page_size"],
        speculative_k=0, eos_token_id=None, name="compile-only-kex")
    try:
        assert eng._admit_rows == {b: 1 for b in buckets}
        one = SingleDeviceSharding(chip)

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one), tree)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, i32, sharding=one)

        B, T, C = serve["batch_size"], buckets[-1], serve["cache_len"]
        G = C // serve["kv_page_size"]
        pool = on_chip(jax.eval_shape(eng._empty_pool))
        assert pool["layers"][3]["k"].shape == (B * G + 1, 16, 1024)
        # the window layers' cache is no function of the context
        assert pool["layers"][0]["ring_k"].shape == (B + 1, 128, 1024)
        params, buffers = on_chip(eng._params), on_chip(eng._buffers)
        if program == "step":
            text = eng._step_jit.lower(params, buffers, ints(B, 2 + C + G),
                                       ints(B, 1), pool).compile().as_text()
        else:
            text = eng._padmit.lower(
                params, buffers, ints(1, T), ints(1, T), ints(1, C),
                ints(1, G), ints(1), pool, None, ints(1)).compile().as_text()
    finally:
        eng.close()
    calls = [line.split("=")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    for kernel, n in kernels.items():
        assert sum(kernel in c for c in calls) == n, (kernel, calls)


@pytest.mark.parametrize("program,kernels", [
    # one period of the model, layer 1 dense: three KDA layers' state
    # kernel, three expert kernels, the latent layer's page walk
    ("step", {"kda_step": 3, "moe_gated_mlp_tm16": 3, "latent_decode": 1,
              "": 7}),
    # a [1, 4096] admission: three chunked scans, the latent layer's prompt
    # attention, three expert kernels at the 128-row tile
    ("admit", {"kda_chunk": 3, "latent_prefill_attention": 1,
               "moe_gated_mlp_tm128": 3, "": 7}),
])
def test_the_kimi_linear_engine_lowers_its_programs_for_the_chip(
        chip, program, kernels):
    # benchmarks/configs/kimi_linear_serve.json at published widths, one
    # period of its layers, weights that are shapes only: 128 slots x 4864
    # positions, the engine's own jitted programs with every TPU-only
    # branch taken (slot state, latent pages AND experts in one program)
    import json
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks.harness import loader
    from paddle_tpu import nn
    from paddle_tpu.serving.generation import GenerationEngine

    bench = os.path.join(repo, "benchmarks")
    with open(os.path.join(bench, "configs", "kimi_linear_serve.json")) as f:
        cfg = {**json.load(f), "num_hidden_layers": 4}
    with open(os.path.join(bench, "traffic", "longdoc_gen_closed.json")) as f:
        buckets = json.load(f)["prompt_buckets"]
    fam = loader.load_module("families", cfg["family"], bench)
    serve = cfg["serve"]
    with nn.abstract_parameters():
        model = fam.KimiLinearForCausalLM(fam.model_config(cfg))
    eng = GenerationEngine(
        model, prompt_buckets=buckets, batch_size=serve["batch_size"],
        cache_len=serve["cache_len"], kv_page_size=serve["kv_page_size"],
        speculative_k=0, eos_token_id=None, name="compile-only-kml")
    try:
        assert eng._admit_rows == {b: 1 for b in buckets}
        one = SingleDeviceSharding(chip)

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one), tree)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, i32, sharding=one)

        B, T, C = serve["batch_size"], buckets[-1], serve["cache_len"]
        G = C // serve["kv_page_size"]
        pool = on_chip(jax.eval_shape(eng._empty_pool))
        # latent pages beside slot state, under one manager
        assert pool["layers"][3]["latent"].shape == (B * G + 1, 16, 640)
        assert pool["layers"][0]["state"].shape == (B + 1, 32, 128, 128)
        assert pool["layers"][0]["conv"].shape == (B + 1, 3, 12288)
        params, buffers = on_chip(eng._params), on_chip(eng._buffers)
        if program == "step":
            exe = eng._step_jit.lower(params, buffers, ints(B, 2 + C + G),
                                      ints(B, 1), pool).compile()
        else:
            exe = eng._padmit.lower(
                params, buffers, ints(1, T), ints(1, T), ints(1, C),
                ints(1, G), ints(1), pool, None, ints(1)).compile()
    finally:
        eng.close()
    text = exe.as_text()
    calls = [line.split("=")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    for kernel, n in kernels.items():
        assert sum(kernel in c for c in calls) == n, (kernel, calls)
    # beside 10.8 GB of weights and caches a program's temporaries must fit
    # what is left of 16 GB (the admission's float32 WY operands; the step
    # gathers no slot views since its latent layer walks the pool)
    assert exe.memory_analysis().temp_size_in_bytes < 3.0e9


# -- the latent decoders' decode step walks the pool where it lies ------------
@pytest.mark.parametrize("B,G", [(32, 288), (128, 304)],
                         ids=["joyai_flash", "kimi_linear"])
def test_latent_decode_walks_the_latent_cells_pages_of_640_lanes(chip, B, G):
    # a decode step of the two latent cells: 32 slots x 288 pages and 128
    # slots x 304 pages of 16 rows of 640 bfloat16 lanes (512 latent + 64
    # shared key + pad), 32 heads as the query rows of a slot's grid step;
    # the page table rides flat in SMEM (38 912 entries at 128 slots)
    from paddle_tpu.ops.latent_attention import (DECODE_KEYS, latent_decode,
                                                 latent_decode_eligible)

    pool = ((B * G + 1, 16, 640), bf16)
    assert latent_decode_eligible(jax.ShapeDtypeStruct(*pool), 1)
    assert not latent_decode_eligible(jax.ShapeDtypeStruct(*pool), 1536)
    assert DECODE_KEYS == 512  # by rule, nothing to search

    def fn(q, pool, tab, pm, pos, bound):
        return latent_decode(q, pool, tab, pm, pos, bound, scale=192 ** -0.5,
                             value_width=512)

    _compiles_with_kernel(chip, fn, ((B, 32, 640), bf16), pool, ((B, G), i32),
                          ((B, G * 16), i32), ((B, 1), i32), ((B,), i32))


@pytest.mark.parametrize("config,traffic,cls,layers,walks", [
    # layer 0 and one expert layer: a latent layer each
    ("joyai_flash_serve", "ragdocs_closed", "LatentMoEForCausalLM", 2, 2),
    # one period: three KDA layers and the NoPE latent layer
    ("kimi_linear_serve", "longdoc_gen_closed", "KimiLinearForCausalLM", 4, 1),
], ids=["joyai_flash", "kimi_linear"])
def test_the_latent_step_programs_walk_the_pool_and_gather_no_slot_view(
        chip, config, traffic, cls, layers, walks):
    # the sibling of test_gpt2_paged_programs_never_copy_the_pool for the
    # latent cells' decode step (published widths, a few layers, weights
    # that are shapes only): every latent layer attends through
    # `latent_decode`, and no instruction of the optimized program produces
    # a [slots, C, 640] view of the slots' windows (the gather the step
    # made a layer until PR 45: 94 M elements at 32 slots, 398 M at 128),
    # its 576 used lanes, or another array of the pool's size
    import json
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks.harness import loader
    from paddle_tpu import nn
    from paddle_tpu.serving.generation import GenerationEngine

    bench = os.path.join(repo, "benchmarks")
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = {**json.load(f), "num_hidden_layers": layers}
    with open(os.path.join(bench, "traffic", traffic + ".json")) as f:
        buckets = json.load(f)["prompt_buckets"]
    fam = loader.load_module("families", cfg["family"], bench)
    serve = cfg["serve"]
    with nn.abstract_parameters():
        model = getattr(fam, cls)(fam.model_config(cfg))
    eng = GenerationEngine(
        model, prompt_buckets=buckets, batch_size=serve["batch_size"],
        cache_len=serve["cache_len"], kv_page_size=serve["kv_page_size"],
        speculative_k=0, eos_token_id=None, name="compile-only-walk")
    try:
        one = SingleDeviceSharding(chip)

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one), tree)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, i32, sharding=one)

        B, C, page = serve["batch_size"], serve["cache_len"], 16
        G = C // page
        pool = on_chip(jax.eval_shape(eng._empty_pool))
        pools = [kv["latent"] for kv in pool["layers"] if "latent" in kv]
        assert len(pools) == walks
        assert all(p.shape == (B * G + 1, page, 640) for p in pools)
        exe = eng._step_jit.lower(
            on_chip(eng._params), on_chip(eng._buffers),
            ints(B, 2 + C + G), ints(B, 1), pool).compile()
    finally:
        eng.close()
    text = exe.as_text()
    calls = [line.split("=")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert sum("latent_decode" in c for c in calls) == walks, calls
    for what, elems in (("pool", (B * G + 1) * page * 640),
                        ("slot views", B * C * 640),
                        ("slot views' latent lanes", B * C * 576)):
        stray, kinds = _stray_pool_results(text, elems)
        assert not stray, f"{what}-sized results: {stray}"
        if what == "pool":  # scattered into in place, donated
            assert "parameter" in kinds and kinds & {
                "scatter", "dynamic-update-slice", "fusion"}
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= walks * (B * G + 1) * page * 640 * 2
    # one slot view alone was 121 MB (32 slots) or 797 MB (128 slots)
    assert mem.temp_size_in_bytes < 0.8 * B * C * 640 * 2
