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
from paddle_tpu.framework.flags import get_flags, set_flags

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
    prev_mode = get_flags("kernel_autotune")["kernel_autotune"]
    set_flags({"kernel_autotune": "off"})
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
    set_flags({"kernel_autotune": prev_mode})


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
    # GPT-2-small decode: 12 heads x 64, cache 1024, 4 slots
    from paddle_tpu.models.gpt import _paged_flash
    from paddle_tpu.ops.paged_attention import paged_flash_decode

    assert _paged_flash(64, page)  # the model's gate selects the kernel
    B, H, hd, G = 4, 12, 64, 1024 // page
    pages = B * G + 1
    shapes = [((B, H, T, hd), f32), ((pages, H, page, hd), pool),
              ((pages, H, page, hd), pool), ((B, G), i32),
              ((B, T, G * page), jnp.bool_)]
    if pool == i8:
        shapes += [((pages, H, page), f32)] * 2
    _compiles_with_kernel(chip, paged_flash_decode, *shapes)


def test_paged_flash_admission_prefill_gpt2_small(chip):
    # the paged admission program's attention: the same kernel over the
    # engine's row chunk x the widest prompt bucket the chip benchmark
    # serves (docs_closed, 768), float pages of 16
    from paddle_tpu.ops.paged_attention import paged_flash_decode
    from paddle_tpu.serving.generation import _ADMIT_ROWS

    R, H, hd, page, T = _ADMIT_ROWS, 12, 64, 16, 768
    G, pages = 1024 // page, 2048 + 1
    _compiles_with_kernel(
        chip, paged_flash_decode, ((R, H, T, hd), f32),
        ((pages, H, page, hd), f32), ((pages, H, page, hd), f32),
        ((R, G), i32), ((R, T, G * page), jnp.bool_))


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
