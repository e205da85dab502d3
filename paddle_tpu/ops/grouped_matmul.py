"""Grouped matmul over ragged per-expert row groups — Pallas TPU kernel.

The MoE expert FFN (paddle_tpu/moe) is E independent matmuls whose row
counts are decided at runtime by the router: expert ``e`` owns the first
``group_sizes[e]`` rows of its ``[C, D]`` capacity bucket and the rest is
padding.  Under XLA the natural spelling is a batched einsum over the
full ``[E, C, D]`` buffer — every padding row burns MXU cycles and HBM
bandwidth.  This kernel runs one matmul per (expert, row-block,
col-block) grid step and masks the padding rows in-register, so the
output is exactly the masked einsum while each block stays in VMEM:

    XLA:    y = einsum("ecd,edf->ecf", x * rowmask, w)   (mask in HBM)
    here:   y = grouped_matmul(x, w, group_sizes)        (mask in VMEM)

Rows at or beyond ``group_sizes[e]`` are exactly zero in the output, so
downstream combine sums can trust the padding without re-masking.  The
backward is the closed-form VJP in plain XLA (two masked einsums — they
batch over E and fuse fine; no second custom kernel needed):

    dx = einsum("ecf,edf->ecd", dy, w) * rowmask
    dw = einsum("ecd,ecf->edf", x * rowmask, dy)

``group_sizes`` gets a symbolic-zero (float0) cotangent.

Tile sizes are a rule of the shape (:func:`gmm_blocks`); the contraction
dim D stays whole per block, so eligibility on real TPUs wants
``D % 128 == 0`` (same shape class as the other epilogues).

The DROPLESS layout (``ragged_layout`` / ``ragged_gated_mlp``) has no
capacity: the (token, choice) pairs are sorted by expert and each
expert's rows start at a multiple of the row tile, so every tile of
``tile_m`` rows belongs to ONE expert.  The kernel walks only the tiles
in use and fetches a tile's expert by a scalar-prefetched index: an
expert nobody chose owns no tile and its weights are never read from
HBM, which is what bounds a decode step over hundreds of experts.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from ..framework.errors import InvalidArgumentError
from . import autotune as _at

__all__ = ["grouped_matmul", "ragged_layout", "ragged_gated_mlp"]


def _kernel(gs_ref, x_ref, w_ref, o_ref):
    i = pl.program_id(1)
    bm, bn = o_ref.shape[1], o_ref.shape[2]
    gs = gs_ref[pl.program_id(0)]  # [E] i32 in SMEM (scalar prefetch)
    acc = jnp.dot(x_ref[0].astype(jnp.float32),
                  w_ref[0].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    # global row ids of this block; rows past the group's fill are padding
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + i * bm
    acc = jnp.where(rows < gs, acc, 0.0)
    o_ref[0] = acc.astype(o_ref.dtype)


def _gmm_pallas(x, w, group_sizes, block_m, block_n):
    """[E, C, D] @ [E, D, F] with per-expert valid-row counts [E] i32."""
    E, C, D = x.shape
    F = w.shape[2]
    bm = _at.clamp_tile(block_m, C)
    bn = _at.clamp_tile(block_n, F, _at.LANE)
    Cp = -(-C // bm) * bm
    Fp = -(-F // bn) * bn
    if Cp != C:
        x = jnp.pad(x, ((0, 0), (0, Cp - C), (0, 0)))
    if Fp != F:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, Fp - F)))
    # the per-expert fill counts are scalars the kernel only compares
    # against: they ride SMEM via scalar prefetch — a (1, 1) VMEM block of
    # an [E, 1] operand is not (8, 128)-tileable
    out = pl.pallas_call(
        _kernel,
        interpret=not _device.on_tpu(),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E, Cp // bm, Fp // bn),
            in_specs=[
                pl.BlockSpec((1, bm, D), lambda e, i, j, gs: (e, i, _at.I0)),
                pl.BlockSpec((1, D, bn), lambda e, i, j, gs: (e, _at.I0, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda e, i, j, gs: (e, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((E, Cp, Fp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(group_sizes.astype(jnp.int32), x, w)
    return out[:, :C, :F]


def gmm_blocks(C: int, F: int):
    """``(block_m, block_n)`` of ``grouped_matmul`` over ``C`` capacity
    rows and ``F`` columns: 128 x 128, held to the padded problem.  A rule
    of the shape and no measured search (it was one of ``ops.autotune``
    until PR 48, and 128 x 128 is what ran wherever that search did not:
    no benchmark cell runs this kernel, so no chip table is owed).
    ``block_m=`` / ``block_n=`` stay for the tests that run every block."""
    return _at.clamp_tile(128, C), _at.clamp_tile(128, F, _at.LANE)


def _rowmask(group_sizes, C):
    # [E, C, 1] — 1.0 for valid rows, 0.0 for capacity padding
    rows = jnp.arange(C, dtype=jnp.int32)[None, :]
    return (rows < group_sizes[:, None]).astype(jnp.float32)[..., None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(x, w, group_sizes, block_m, block_n):
    return _gmm_pallas(x, w, group_sizes, block_m, block_n)


def _gmm_fwd(x, w, group_sizes, block_m, block_n):
    y = _gmm_pallas(x, w, group_sizes, block_m, block_n)
    return y, (x, w, group_sizes)


def _gmm_bwd(block_m, block_n, res, dy):
    x, w, group_sizes = res
    mask = _rowmask(group_sizes, x.shape[1]).astype(dy.dtype)
    dx = jnp.einsum("ecf,edf->ecd", dy, w) * mask
    dw = jnp.einsum("ecd,ecf->edf", x * mask.astype(x.dtype), dy)
    return (dx.astype(x.dtype), dw.astype(w.dtype),
            np.zeros(group_sizes.shape, jax.dtypes.float0))


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(x, w, group_sizes, *, block_m: Optional[int] = None,
                   block_n: Optional[int] = None):
    """Per-expert matmul over ragged row groups in one kernel launch.

    x: ``[E, C, D]`` capacity-bucketed rows (expert-major), w: ``[E, D,
    F]`` stacked expert weights, group_sizes: ``[E]`` integer valid-row
    counts.  Returns ``[E, C, F]`` equal to ``einsum("ecd,edf->ecf", x *
    rowmask, w)`` — rows at or beyond ``group_sizes[e]`` are exactly
    zero.  Differentiable in x and w; ``group_sizes`` gets a
    symbolic-zero cotangent.  Blocks default to the rule
    (:func:`gmm_blocks`); an explicit one wins.
    """
    x = jnp.asarray(x)
    w = jnp.asarray(w)
    group_sizes = jnp.asarray(group_sizes)
    if x.ndim != 3 or w.ndim != 3:
        raise InvalidArgumentError(
            f"grouped_matmul: x {x.shape} / w {w.shape} must be rank 3")
    E, C, D = x.shape
    if w.shape[0] != E or w.shape[1] != D:
        raise InvalidArgumentError(
            f"grouped_matmul: w {w.shape} does not match x {x.shape} "
            f"(want [E={E}, D={D}, F])")
    if group_sizes.shape != (E,):
        raise InvalidArgumentError(
            f"grouped_matmul: group_sizes {group_sizes.shape} != ({E},)")
    if not jnp.issubdtype(group_sizes.dtype, jnp.integer):
        raise InvalidArgumentError(
            f"grouped_matmul: group_sizes dtype {group_sizes.dtype} is "
            f"not integer")
    group_sizes = group_sizes.astype(jnp.int32)
    return _gmm(x, w, group_sizes, *_at.blocks_or(
        gmm_blocks(C, w.shape[2]), block_m, block_n))


# -- dropless ragged groups: tile-aligned rows sorted by expert --------------
def ragged_tiles(num_rows: int, num_groups: int, tile_m: int) -> int:
    """Static upper bound on the tiles ``ragged_layout`` can use:
    ``sum_e ceil(c_e / tile_m) <= num_rows // tile_m + num_groups``."""
    return min(num_rows // tile_m + num_groups, num_rows)


def ragged_layout(group_ids, num_groups: int, tile_m: int,
                  partial: bool = False):
    """Sort ``[A]`` group ids (one per (token, choice) pair) into a
    tile-aligned row layout.  Returns a dict of int32 arrays:

    ``dest`` [A]: the row of pair ``a`` in the sorted layout;
    ``counts`` [E]: pairs per group; ``tile_group`` [NT]: the group of
    each tile (tiles past the last one in use repeat it, so a kernel's
    block index does not move there); ``tile_index`` [NT]: ``min(t,
    used - 1)``; ``used`` [1]: tiles in use.  The layout has ``NT *
    tile_m`` rows; a group's rows are contiguous from a tile boundary and
    the rows that pad its last tile belong to nobody.

    ``partial``: ids outside ``[0, num_groups)`` name groups that are not
    held here.  Such a pair gets NO row (its ``dest`` is ``NT * tile_m``,
    one past the layout, and ``present`` [A] bool says which pairs have
    one), is counted nowhere and costs no tile; no tile at all may be in
    use.  The static row bound stays that of all ``A`` pairs."""
    i32 = jnp.int32
    ids = jnp.asarray(group_ids, i32)
    A, E, tm = ids.shape[0], int(num_groups), int(tile_m)
    NT = ragged_tiles(A, E, tm)
    if partial:
        present = (ids >= 0) & (ids < E)
        ids = jnp.where(present, ids, E)       # the absent sort last
    counts = jnp.sum(ids[:, None] == jnp.arange(E, dtype=i32)[None, :],
                     axis=0, dtype=i32)
    order = jnp.argsort(ids, stable=True).astype(i32)
    sorted_ids = ids[order]
    tiles = (counts + (tm - 1)) // tm
    tile_end = jnp.cumsum(tiles, dtype=i32)
    tile_start = tile_end - tiles
    group_start = jnp.cumsum(counts, dtype=i32) - counts
    if partial:
        held = jnp.minimum(sorted_ids, E - 1)
        dest_sorted = jnp.where(
            sorted_ids < E, tile_start[held] * tm
            + jnp.arange(A, dtype=i32) - group_start[held], NT * tm)
    else:
        rank = jnp.arange(A, dtype=i32) - group_start[sorted_ids]
        dest_sorted = tile_start[sorted_ids] * tm + rank
    dest = jnp.zeros((A,), i32).at[order].set(dest_sorted)
    used = tile_end[-1]
    last = jnp.maximum(used - 1, 0) if partial else used - 1
    tile_index = jnp.minimum(jnp.arange(NT, dtype=i32), last)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, tile_index, side="right").astype(i32),
        E - 1)
    lay = {"dest": dest, "counts": counts, "tile_group": tile_group,
           "tile_index": tile_index, "used": used.reshape(1),
           "tiles": NT, "tile_m": tm}
    if partial:
        lay["present"] = present
    return lay


def _gated_mlp_kernel(tg_ref, ti_ref, used_ref, x_ref, wg_ref, wu_ref,
                      wd_ref, o_ref):
    del tg_ref, ti_ref  # consumed by the index maps

    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        o_ref[...] = jnp.dot(
            h, wd_ref[0],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


#: the most VMEM an expert call asks for: a v5e core has 128 MiB, the
#: compiler and the pipeline's own buffers want some of it
_VMEM_CAP = 100 << 20


def _gated_mlp_vmem(tm, D, F, item):
    """x and out tiles, the three weight blocks of ``F`` columns (all
    double-buffered) and the f32 intermediates of one tile."""
    return 2 * (2 * tm * D + 3 * D * F) * item + 4 * tm * (3 * F + D)


def _vmem_limit(need):
    return int(min(max(need * 5 // 4, 16 << 20), _VMEM_CAP))


def _gated_mlp_kernel_wide(tg_ref, ti_ref, used_ref, x_ref, wg_ref, wu_ref,
                           wd_ref, o_ref, acc_ref):
    """One (row tile, block of the expert's width) step: the block's part
    of the down projection joins the tile's float32 accumulator, which is
    written once, at the last block."""
    del tg_ref, ti_ref  # consumed by the index maps
    f = pl.program_id(1)

    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        @pl.when(f == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        acc_ref[...] += jnp.dot(h, wd_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(f == pl.num_programs(1) - 1)
        def _fin():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _wide_blocks(tm, D, F, item):
    """Blocks of an expert's width, widest first, that divide it in whole
    lane tiles and whose call fits :data:`_VMEM_CAP` (none does: the
    narrowest)."""
    blocks = [bf for bf in range(F - _at.LANE, 0, -_at.LANE) if F % bf == 0]
    fit = [bf for bf in blocks if bf > _at.LANE and (
        _gated_mlp_vmem(tm, D, bf, item) + 4 * tm * D) * 5 // 4 <= _VMEM_CAP]
    return fit or blocks[-1:]


def wide_block(tile_m: int, D: int, F: int, itemsize: int) -> int:
    """The block of an expert's width a grid step of the width-tiled
    kernel takes: the WIDEST that fits (:func:`_wide_blocks`), the fewest
    grid steps a tile.  A rule of the shape, held by the table
    ``tools/tile_table_chip.py`` timed on the chip (``PERF.md`` section 6,
    PR 48; 16 held experts of 6144 x 2048, bfloat16): at the decode step's
    16-row tiles 1024 and 512 tie (1.670 against 1.662 ms a layer call) and
    256 loses 4 %; at the admission's 128-row tiles 512 beats 256 by 6 %
    (5.25 against 5.58).  Until PR 48 a measured search of ``ops.autotune``
    over a few tiles of two stand-in experts (the real operands' twins did
    not fit beside a model that fills the chip); it drew these."""
    return _wide_blocks(tile_m, D, F, itemsize)[0]


def _gated_mlp_wide(xs, w_gate, w_up, w_down, lay, bf):
    """An expert too wide for VMEM whole: grid ``(NT, F / bf)``, the
    ``[tm, D]`` output accumulated in float32 scratch over the blocks of
    the width.  A tile in use fetches its expert's matrices block by block,
    so an expert's second row tile reads them again; a tile past the last
    one in use fetches nothing."""
    NT, tm = lay["tiles"], lay["tile_m"]
    E, D, F = w_gate.shape
    item = np.dtype(xs.dtype).itemsize
    need = _gated_mlp_vmem(tm, D, bf, item) + 4 * tm * D
    last = np.int32(F // bf - 1)

    def blk(t, f, u):
        # a tile not in use keeps the block the last tile in use ended on:
        # its index does not move, so nothing is fetched for it
        return jnp.where(t < u[0], f, last)

    return pl.pallas_call(
        _gated_mlp_kernel_wide,
        name=f"moe_gated_mlp_tm{tm}",
        interpret=not _device.on_tpu(),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(NT, F // bf),
            in_specs=[
                pl.BlockSpec((tm, D),
                             lambda t, f, tg, ti, u: (ti[t], _at.I0)),
                pl.BlockSpec((1, D, bf), lambda t, f, tg, ti, u: (
                    tg[t], _at.I0, blk(t, f, u))),
                pl.BlockSpec((1, D, bf), lambda t, f, tg, ti, u: (
                    tg[t], _at.I0, blk(t, f, u))),
                pl.BlockSpec((1, bf, D), lambda t, f, tg, ti, u: (
                    tg[t], blk(t, f, u), _at.I0)),
            ],
            out_specs=pl.BlockSpec((tm, D),
                                   lambda t, f, tg, ti, u: (ti[t], _at.I0)),
            scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((NT * tm, D), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(need)),
    )(lay["tile_group"], lay["tile_index"], lay["used"], xs, w_gate, w_up,
      w_down)


def _gated_mlp_pallas(xs, w_gate, w_up, w_down, lay):
    NT, tm = lay["tiles"], lay["tile_m"]
    E, D, F = w_gate.shape
    item = np.dtype(xs.dtype).itemsize
    need = _gated_mlp_vmem(tm, D, F, item)
    if need * 5 // 4 > _VMEM_CAP:
        # one whole expert, double-buffered, does not fit: tile its width
        return _gated_mlp_wide(xs, w_gate, w_up, w_down, lay,
                               wide_block(tm, D, F, item))
    return pl.pallas_call(
        _gated_mlp_kernel,
        name=f"moe_gated_mlp_tm{tm}",
        interpret=not _device.on_tpu(),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(NT,),
            in_specs=[
                pl.BlockSpec((tm, D), lambda t, tg, ti, u: (ti[t], _at.I0)),
                pl.BlockSpec((1, D, F),
                             lambda t, tg, ti, u: (tg[t], _at.I0, _at.I0)),
                pl.BlockSpec((1, D, F),
                             lambda t, tg, ti, u: (tg[t], _at.I0, _at.I0)),
                pl.BlockSpec((1, F, D),
                             lambda t, tg, ti, u: (tg[t], _at.I0, _at.I0)),
            ],
            out_specs=pl.BlockSpec((tm, D),
                                   lambda t, tg, ti, u: (ti[t], _at.I0)),
        ),
        out_shape=jax.ShapeDtypeStruct((NT * tm, D), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(need)),
    )(lay["tile_group"], lay["tile_index"], lay["used"], xs, w_gate, w_up,
      w_down)


def _gated_mlp_xla(xs, w_gate, w_up, w_down, lay):
    """The same function without a kernel (CPU, several-device meshes):
    ``lax.ragged_dot`` over the groups' padded row counts."""
    tm = lay["tile_m"]
    sizes = (lay["counts"] + (tm - 1)) // tm * tm
    f32 = jnp.float32
    g = jax.lax.ragged_dot(xs, w_gate, sizes, preferred_element_type=f32)
    u = jax.lax.ragged_dot(xs, w_up, sizes, preferred_element_type=f32)
    h = (g * jax.nn.sigmoid(g) * u).astype(xs.dtype)
    return jax.lax.ragged_dot(h, w_down, sizes,
                              preferred_element_type=f32).astype(xs.dtype)


def ragged_gated_mlp(xs, w_gate, w_up, w_down, layout, *, kernel=None):
    """``(silu(x W_gate[e]) * (x W_up[e])) W_down[e]`` for every row of
    ``xs`` ``[NT * tile_m, D]`` laid out by :func:`ragged_layout`; ``e`` is
    the row's tile's group.  Weights ``[E, D, F]``, ``[E, D, F]``, ``[E,
    F, D]``; matmuls accumulate in float32 and round to ``xs.dtype``
    between them.  Rows of tiles not in use are not written.  ``kernel``
    None asks the gate (a TPU, lane-aligned D and F, a one-device mesh)."""
    D, F = w_gate.shape[1], w_gate.shape[2]
    if kernel is None:
        kernel = (_at.fused_epilogues_eligible(D)
                  and _at.fused_epilogues_eligible(F))
    fn = _gated_mlp_pallas if kernel else _gated_mlp_xla
    return fn(xs, w_gate, w_up, w_down, layout)
