"""Fused 1x1-conv (GEMM) + BatchNorm-statistics Pallas kernel.

ResNet-50's 1x1 convolutions carry ~55% of its FLOPs and are
HBM-bandwidth-bound on v5e (tools/resnet_mfu_analysis.md: arithmetic
intensity 32-128 flop/byte vs the chip's ~243 balance point), so the win
is not more FLOP/s but FEWER passes over the activation tensor.  Train-
mode BatchNorm needs the batch mean/var of the conv OUTPUT, which XLA
computes as a separate reduction pass over Y after the conv custom call:

    XLA:    Y = conv(x, w)      write Y          (pass 1)
            mean/var over Y     read Y           (pass 2)
            normalize+relu      read+write Y     (pass 3)

This kernel folds the statistics into the GEMM epilogue — per-channel
sum and sum-of-squares accumulate in VMEM scratch while the matmul tiles
stream through the MXU, finalized on the last M-step of the sequential
TPU grid:

    here:   Y, Σ, Σ² = conv1x1_bn_stats(x, w)    write Y  (pass 1)
            normalize+relu      read+write Y     (pass 2)

i.e. one full read of Y removed (~25-33% of the tensor traffic on these
bandwidth-bound layers).  The normalize pass stays in XLA where it fuses
with the residual add and ReLU for free.

Both kernels are differentiable (the ResNet training step runs through
them): each carries a closed-form VJP in plain XLA, like the other
epilogues — ``pallas_call`` itself has no transpose rule, and the VJPs
are two GEMMs (stats) and one masked elementwise pass (apply) that XLA
already schedules well.

Reference capability matched: the fused_ops family
(paddle/fluid/operators/fused/conv_fusion_op.cc — cuDNN conv+bias+act
fusion); the TPU-native answer fuses what the TPU is short on (HBM
passes), not what cuDNN is short on (kernel launches).

Layout: NHWC.  A 1x1/s1 conv is exactly ``X[M=N*H*W, K=Cin] @ W[K, N=Cout]``.
Grid: (N-blocks, M-blocks) with M minor — the TPU grid is sequential, so
the VMEM stats scratch accumulates across the M sweep of each N column
and flushes once per N-block.  K is kept whole (ResNet's Cin ≤ 2048
easily fits VMEM at bf16).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from ..framework.errors import InvalidArgumentError
from . import autotune as _at

__all__ = ["conv1x1_bn_stats", "conv1x1_bn_relu", "bn_apply_relu"]



def _kernel(x_ref, w_ref, y_ref, sum_ref, sq_ref, acc_s, acc_q):
    mi = pl.program_id(1)
    m_steps = pl.num_programs(1)

    x = x_ref[...]
    w = w_ref[...]
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(mi == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        acc_q[...] = jnp.zeros_like(acc_q)

    # per-channel stats ride VMEM scratch across the sequential M sweep
    acc_s[...] += jnp.sum(y, axis=0, keepdims=True)
    acc_q[...] += jnp.sum(y * y, axis=0, keepdims=True)

    @pl.when(mi == m_steps - 1)
    def _flush():
        sum_ref[...] = acc_s[...]
        sq_ref[...] = acc_q[...]


def bn_blocks(M: int, N: int):
    """``(block_m, block_n)`` of both kernels here (``conv1x1_bn_stats``
    and ``bn_apply_relu``) over ``M`` rows and ``N`` channels: 512 x 256,
    held to the padded problem.  A rule of the shape and no measured search
    (each kernel had one of ``ops.autotune`` until PR 48, and 512 x 256 is
    what ran wherever that search did not: no benchmark cell runs them, so
    no chip table is owed).  ``block_m=`` / ``block_n=`` stay for the
    tests that run every block."""
    return _at.clamp_tile(512, M), _at.clamp_tile(256, N, _at.LANE)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n"))
def _stats_pallas(x, w, *, block_m: int, block_n: int):
    M, K = x.shape
    N = w.shape[1]
    # Mosaic lowers (sublane, lane)-tiled blocks: bm must be a multiple of
    # 8 and bn a multiple of 128, or non-aligned shapes (M=100, N=200)
    # fail to lower on a real TPU.  Padding already keeps the stats exact.
    bm = _at.clamp_tile(block_m, M)
    bn = _at.clamp_tile(block_n, N, _at.LANE)
    Mp = -(-M // bm) * bm
    Np = -(-N // bn) * bn
    xp = x if Mp == M else jnp.pad(x, ((0, Mp - M), (0, 0)))
    wp = w if Np == N else jnp.pad(w, ((0, 0), (0, Np - N)))

    y, s, q = pl.pallas_call(
        _kernel,
        interpret=not _device.on_tpu(),  # CPU tests: interpret mode
        grid=(Np // bn, Mp // bm),  # M minor: sequential stats sweep
        in_specs=[
            pl.BlockSpec((bm, K), lambda n, m: (m, _at.I0)),
            pl.BlockSpec((K, bn), lambda n, m: (_at.I0, n)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda n, m: (m, n)),
            pl.BlockSpec((1, bn), lambda n, m: (_at.I0, n)),
            pl.BlockSpec((1, bn), lambda n, m: (_at.I0, n)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Np), x.dtype),
            jax.ShapeDtypeStruct((1, Np), jnp.float32),
            jax.ShapeDtypeStruct((1, Np), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(xp, wp)
    return y[:M, :N], s[0, :N], q[0, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _stats(x, w, block_m, block_n):
    return _stats_pallas(x, w, block_m=block_m, block_n=block_n)


def _stats_fwd(x, w, block_m, block_n):
    y, s, q = _stats_pallas(x, w, block_m=block_m, block_n=block_n)
    return (y, s, q), (x, w, y)


def _stats_bwd(block_m, block_n, res, cts):
    x, w, y = res
    dy, ds, dq = cts
    # Σy is linear and Σy² quadratic in y: their cotangents fold into one
    # effective dY, then the usual two GEMMs
    g = (dy.astype(jnp.float32) + ds[None, :]
         + 2.0 * y.astype(jnp.float32) * dq[None, :]).astype(x.dtype)
    dx = jnp.dot(g, w.T, preferred_element_type=jnp.float32)
    dw = jnp.dot(x.T, g, preferred_element_type=jnp.float32)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_stats.defvjp(_stats_fwd, _stats_bwd)


def conv1x1_bn_stats(x, w, *, block_m: Optional[int] = None,
                     block_n: Optional[int] = None):
    """``Y = X @ W`` plus per-output-channel ``(Σy, Σy²)`` in ONE pass.

    x: ``[M, Cin]`` (flattened NHWC activations), w: ``[Cin, Cout]``.
    Returns ``(y [M, Cout], sum [Cout] f32, sumsq [Cout] f32)``.
    M and Cout are padded to block multiples internally (padding rows
    contribute zeros to the stats — exact).

    Tile sizes default to the rule (:func:`bn_blocks`); an explicit
    ``block_m``/``block_n`` wins.  Differentiable in x and w.
    """
    x, w = jnp.asarray(x), jnp.asarray(w)
    if x.ndim != 2 or w.ndim != 2 or x.shape[1] != w.shape[0]:
        raise InvalidArgumentError(f"shape mismatch {x.shape} @ {w.shape}")
    return _stats(x, w, *_at.blocks_or(
        bn_blocks(x.shape[0], w.shape[1]), block_m, block_n))


def _apply_kernel(*refs, has_residual):
    # normalize + (residual add) + relu on one (bm, bn) tile: Y and the
    # residual are each read once, the output written once.
    if has_residual:
        y_ref, sc_ref, sh_ref, r_ref, o_ref = refs
    else:
        y_ref, sc_ref, sh_ref, o_ref = refs
    out = y_ref[...].astype(jnp.float32) * sc_ref[...] + sh_ref[...]
    if has_residual:
        out = out + r_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.maximum(out, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n"))
def _apply_pallas(y, scale, shift, residual, *, block_m: int, block_n: int):
    M, N = y.shape
    bm = _at.clamp_tile(block_m, M)
    bn = _at.clamp_tile(block_n, N, _at.LANE)
    Mp = -(-M // bm) * bm
    Np = -(-N // bn) * bn
    yp = y if (Mp, Np) == (M, N) else jnp.pad(y, ((0, Mp - M), (0, Np - N)))
    scp = scale.reshape(1, N).astype(jnp.float32)
    shp = shift.reshape(1, N).astype(jnp.float32)
    if Np != N:
        scp = jnp.pad(scp, ((0, 0), (0, Np - N)))
        shp = jnp.pad(shp, ((0, 0), (0, Np - N)))
    has_residual = residual is not None
    operands = [yp, scp, shp]
    in_specs = [
        pl.BlockSpec((bm, bn), lambda n, m: (m, n)),
        pl.BlockSpec((1, bn), lambda n, m: (_at.I0, n)),
        pl.BlockSpec((1, bn), lambda n, m: (_at.I0, n)),
    ]
    if has_residual:
        rp = residual if (Mp, Np) == (M, N) else jnp.pad(
            residual, ((0, Mp - M), (0, Np - N)))
        operands.append(rp)
        in_specs.append(pl.BlockSpec((bm, bn), lambda n, m: (m, n)))

    out = pl.pallas_call(
        functools.partial(_apply_kernel, has_residual=has_residual),
        interpret=not _device.on_tpu(),
        grid=(Np // bn, Mp // bm),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda n, m: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(*operands)
    return out[:M, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _apply(y, scale, shift, residual, block_m, block_n):
    return _apply_pallas(y, scale, shift, residual,
                         block_m=block_m, block_n=block_n)


def _apply_fwd(y, scale, shift, residual, block_m, block_n):
    out = _apply_pallas(y, scale, shift, residual,
                        block_m=block_m, block_n=block_n)
    # a scalar stands in for the residual: bwd needs its presence and
    # dtype, not its values
    like = None if residual is None else jnp.zeros((), residual.dtype)
    return out, (y, scale, shift, out, like)


def _apply_bwd(block_m, block_n, res, dout):
    y, scale, shift, out, like = res
    dz = jnp.where(out > 0, dout.astype(jnp.float32), 0.0)  # relu mask
    dy = dz * scale.astype(jnp.float32)[None, :]
    dscale = jnp.sum(dz * y.astype(jnp.float32), axis=0)
    dshift = jnp.sum(dz, axis=0)
    dres = None if like is None else dz.astype(like.dtype)
    return (dy.astype(y.dtype), dscale.astype(scale.dtype),
            dshift.astype(shift.dtype), dres)


_apply.defvjp(_apply_fwd, _apply_bwd)


def bn_apply_relu(y, scale, shift, residual=None, *,
                  block_m: Optional[int] = None,
                  block_n: Optional[int] = None):
    """Fused BN-normalize + residual-add + ReLU epilogue:
    ``relu(y*scale + shift [+ residual])`` in ONE pass over ``y``.

    The XLA tail of :func:`conv1x1_bn_relu` is elementwise, but it sits
    downstream of a Pallas custom call XLA cannot fuse INTO, so whether
    the normalize, the residual read and the ReLU land in one fusion is
    the compiler's choice.  This kernel pins them: one read of ``y``, one
    read of the residual, one write of the output — the guaranteed
    2-pass schedule of the module doc.  y ``[M, Cout]``, scale/shift
    ``[Cout]`` (f32 math), residual optional ``[M, Cout]``.
    Differentiable in y, scale, shift and the residual.
    """
    y, scale, shift = jnp.asarray(y), jnp.asarray(scale), jnp.asarray(shift)
    if residual is not None:
        residual = jnp.asarray(residual)
    return _apply(y, scale, shift, residual, *_at.blocks_or(
        bn_blocks(*y.shape), block_m, block_n))


def conv1x1_bn_relu(x, w, gamma, beta, *, epsilon: float = 1e-5,
                    residual=None, momentum: float = 0.9,
                    running_mean=None, running_var=None,
                    fused_epilogue: bool = False,
                    block_m: Optional[int] = None,
                    block_n: Optional[int] = None):
    """Train-mode ``relu(BN(X @ W) [+ residual])`` in two passes instead of
    XLA's three (see module doc).  x ``[M, Cin]`` NHWC-flattened.

    Returns ``(out [M, Cout], new_running_mean, new_running_var)`` with
    paddle's momentum convention (``new = momentum*old + (1-m)*batch``);
    running stats pass through unchanged when not provided.

    ``fused_epilogue=True`` routes the normalize + residual-add + ReLU
    tail through :func:`bn_apply_relu` (one pinned pass) instead of
    leaving the elementwise tail to XLA's fusion heuristics.
    """
    M = x.shape[0]
    y, s, q = conv1x1_bn_stats(x, w, block_m=block_m, block_n=block_n)
    mean = s / M
    var = jnp.maximum(q / M - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + epsilon)
    scale = (gamma.astype(jnp.float32) * inv).astype(y.dtype)
    shift = (beta.astype(jnp.float32)
             - mean * gamma.astype(jnp.float32) * inv).astype(y.dtype)
    if fused_epilogue:
        res = None if residual is None else residual.astype(y.dtype)
        out = bn_apply_relu(y, scale, shift, res)
    else:
        out = y * scale + shift
        if residual is not None:
            out = out + residual.astype(out.dtype)
        out = jax.nn.relu(out)
    if (running_mean is None) != (running_var is None):
        raise InvalidArgumentError(
            "conv1x1_bn_relu: pass running_mean and running_var together "
            "(or neither)")
    if running_mean is not None:
        n = jnp.asarray(M, jnp.float32)
        unbiased = var * n / jnp.maximum(n - 1, 1)
        # f32 math, returned in the buffers' own dtype (bf16 under
        # net.astype("bfloat16")): a scan-chained train step carries them
        running_mean = (momentum * running_mean.astype(jnp.float32)
                        + (1 - momentum) * mean).astype(running_mean.dtype)
        running_var = (momentum * running_var.astype(jnp.float32)
                       + (1 - momentum) * unbiased
                       ).astype(running_var.dtype)
    return out, running_mean, running_var
