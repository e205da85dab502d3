"""Every kernel's tile is a rule of its arguments' shapes (PR 48: nothing is
searched or timed at run time).  One case a (kernel, shape): the rule's tile
is in whole Mosaic tiles, no longer than the padded dimension, and the
blocks a grid step holds resident fit the VMEM budget the deleted search
filtered its candidates by (the same estimates, kernel by kernel).

The shapes are the benchmark cells' own for the five kernels their programs
hold (``benchmarks/configs``, the engines' buckets; the table the rules were
read from is ``tools/tile_table_chip.py``'s, ``PERF.md`` section 6), a
typical one for the six no cell runs, and one short and one ragged shape
apiece.  That the rule's tile LOWERS for the chip at the cell shapes is
``tests/test_tpu_compile.py``'s.
"""
import pytest

from paddle_tpu.ops import autotune as at
from paddle_tpu.ops.flash_attention import (
    flash_blocks,
    flash_bwd_blocks,
    window_block,
)
from paddle_tpu.ops.fused_conv1x1_bn import bn_blocks
from paddle_tpu.ops.fused_layernorm import ln_block
from paddle_tpu.ops.fused_softmax_xent import xent_blocks
from paddle_tpu.ops.grouped_matmul import (
    _VMEM_CAP,
    _gated_mlp_vmem,
    gmm_blocks,
    wide_block,
)
from paddle_tpu.ops.quantized_matmul import qmm_blocks

BF16, F32, I8 = 2, 4, 1


def _flash(S, D, item, bwd=False):
    bq, bk = (flash_bwd_blocks(S, S) if bwd
              else flash_blocks(S, S, D * item))
    resident = ((2 * bq + 2 * bk) * D * item
                + (2 * bq * 128 + (bq + 2 * bk) * D + 2 * bq) * 4)
    return [(bq, S, 8), (bk, S, 8)], resident


def _flash_bwd(S, D, item):
    return _flash(S, D, item, bwd=True)


def _window(S, D, item):
    b = window_block(S)
    return [(b, S, 8)], (4 * b * D * item
                         + (2 * b * 128 + 3 * b * D + 2 * b * b) * 4)


def _ln(M, D, item):
    bm = ln_block(M)
    return [(bm, M, 8)], 4 * bm * D * item + bm * D * 4 + 2 * D * 4


def _xent(M, V, item):
    bm, bv = xent_blocks(M, V)
    return ([(bm, M, 8), (bv, V, 128)],
            bm * bv * (item + 4) + 3 * bm * 128 * 4)


def _bn_stats(M, K, N, item):
    bm, bn = bn_blocks(M, N)
    return ([(bm, M, 8), (bn, N, 128)],
            (bm * K + K * bn + bm * bn) * item + 2 * bn * 4)


def _bn_apply(M, N, item):
    bm, bn = bn_blocks(M, N)
    return [(bm, M, 8), (bn, N, 128)], 3 * bm * bn * item + 2 * bn * 4


def _gmm(C, D, F, item):
    bm, bn = gmm_blocks(C, F)
    return ([(bm, C, 8), (bn, F, 128)],
            (bm * D + D * bn) * item + bm * bn * (4 + item))


def _qmm(M, K, N):
    bm, bn = qmm_blocks(M, N)
    Kp = -(-K // 128) * 128
    return ([(bm, M, 32), (bn, N, 128)],
            (bm * Kp + Kp * bn) * I8 + 2 * bn * 4 + bm * bn * 4)


_BUCKETS = (1536, 2048, 3072, 4096)        # ragdocs_closed
CASES = (
    # the five kernels in the cells' programs, at the cells' shapes
    [("flash_fwd", _flash, (b, 128, BF16)) for b in _BUCKETS]      # olmo,
    + [("flash_fwd", _flash, (b, 256, BF16))                       # k_exaone
       for b in (256, 512, 768, 1024)]                             # qwen3
    + [("flash_fwd", _flash, (40, 128, BF16)),
       ("flash_fwd", _flash, (600, 64, F32)),
       ("flash_fwd", _flash, (2048, 256, F32))]    # past 512 bytes a head
    + [("flash_fwd_window", _window, (b, 128, BF16)) for b in _BUCKETS]
    + [("flash_fwd_window", _window, (40, 128, BF16)),
       ("flash_fwd_window", _window, (600, 128, BF16))]
    + [("layernorm_residual", _ln, s) for s in (
        (256 * 128, 768, BF16), (5, 768, BF16), (1000, 1024, F32))]
    + [("softmax_xent", _xent, s) for s in (
        (256 * 20, 30522, BF16), (3, 100, F32), (1000, 50257, F32))]
    # the six no cell runs
    + [(k, _flash_bwd, s) for k in ("flash_bwd_dq", "flash_bwd_dkv")
       for s in ((2048, 64, BF16), (40, 64, F32), (600, 64, F32))]
    + [("conv1x1_bn_stats", _bn_stats, s) for s in (
        (128 * 56 * 56, 64, 256, BF16), (10, 24, 40, F32),
        (100, 24, 200, F32))]
    + [("conv1x1_bn_apply", _bn_apply, s) for s in (
        (128 * 56 * 56, 256, BF16), (10, 40, F32), (100, 200, F32))]
    + [("grouped_matmul", _gmm, s) for s in (
        (1024, 768, 3072, BF16), (8, 128, 100, F32), (80, 16, 160, F32))]
    + [("quantized_matmul", _qmm, s) for s in (
        (32, 768, 2304), (2, 64, 128), (100, 768, 300))])


@pytest.mark.parametrize(
    "kernel,rule,shape", CASES,
    ids=[f"{k}-{'x'.join(map(str, s))}" for k, _, s in CASES])
def test_rule_tile_is_aligned_clamped_and_fits(kernel, rule, shape):
    tiles, resident = rule(*shape)
    for tile, dim, multiple in tiles:
        assert tile % multiple == 0, (kernel, tile, multiple)
        assert 0 < tile <= -(-dim // multiple) * multiple, (kernel, tile, dim)
    assert at.vmem_fits(resident), (kernel, shape, resident)


def test_flash_forward_takes_1024_blocks_only_where_they_were_timed():
    # tools/tile_table_chip.py timed bfloat16 heads of 128 and 256; a head
    # of more bytes keeps the 512 it ran before (the chip's compiler refuses
    # a 1024-block of float32 heads of 256: tests/test_tpu_compile.py)
    assert flash_blocks(4096, 4096, 128 * BF16) == (1024, 1024)
    assert flash_blocks(4096, 4096, 256 * BF16) == (1024, 1024)
    assert flash_blocks(1536, 1536, 128 * BF16) == (512, 512)
    assert flash_blocks(768, 768, 256 * BF16) == (768, 768)
    assert flash_blocks(4096, 4096, 256 * F32) == (512, 512)
    assert flash_bwd_blocks(4096, 4096) == (512, 512)


@pytest.mark.parametrize("tm,D,F,item", [
    (16, 6144, 2048, BF16),     # k_exaone's experts in a decode step
    (128, 6144, 2048, BF16),    # and at admission
    (16, 256, 256, F32),        # short: one block under the width
    (128, 4096, 1536, BF16),    # ragged: 1536 = 12 lane tiles
])
def test_wide_block_divides_the_width_and_fits_its_cap(tm, D, F, item):
    # the width-tiled expert kernel asks for its own VMEM limit (up to
    # _VMEM_CAP of the core's 128 MiB), not the 16 MiB default the budget
    # of `vmem_fits` is a share of
    bf = wide_block(tm, D, F, item)
    assert bf % at.LANE == 0 and F % bf == 0 and bf < F
    need = _gated_mlp_vmem(tm, D, bf, item) + 4 * tm * D
    assert need * 5 // 4 <= _VMEM_CAP or bf == at.LANE
