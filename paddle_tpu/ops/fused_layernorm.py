"""Fused LayerNorm + residual-add epilogue — Pallas TPU kernel.

Every transformer sublayer boundary runs ``s = x + sublayer_out`` followed
by (or preceded by, post-LN) ``LayerNorm(s)``.  Under XLA these are
separate HBM passes when the LN's reduction breaks fusion with the big
matmul producing ``sublayer_out``: write s, read s for mean/var, read s
again to normalize.  This kernel streams row blocks once — the residual
add, both statistics and the normalize+affine all happen on the block
while it sits in VMEM:

    XLA:    s = x + r        write s            (pass 1)
            mean/var over s  read s             (pass 2)
            normalize+affine read s, write y    (pass 3)
    here:   s, y = layernorm_residual(x, r, g, b)   read x,r / write s,y

Both the residual stream ``s`` and the normalized ``y`` are returned —
pre-LN blocks (GPT) consume both (``s`` carries forward, ``y`` feeds the
next sublayer), post-LN blocks (BERT) consume ``y``.  The backward is the
standard closed-form LayerNorm VJP in plain XLA (three row reductions that
fuse into one pass — no second custom kernel needed).

Numerics match ``nn.functional.layer_norm`` exactly: the sum is rounded
to the activation dtype first (that rounded value is what the unfused
path normalizes), statistics accumulate in float32.

The row block is a rule of the shape (:func:`ln_block`);
the feature dim stays whole per block, so eligibility on real TPUs wants
``D % 128 == 0`` (``autotune.fused_epilogues_eligible``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from ..framework.errors import InvalidArgumentError
from . import autotune as _at

__all__ = ["layernorm_residual"]


def _kernel(x_ref, r_ref, g_ref, b_ref, s_ref, y_ref, mean_ref, rstd_ref,
            *, epsilon: float):
    s32 = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    s_out = s32.astype(s_ref.dtype)
    s_ref[...] = s_out
    # normalize the ROUNDED sum — that is what the unfused path sees
    sf = s_out.astype(jnp.float32)
    mean = jnp.mean(sf, axis=-1, keepdims=True)
    c = sf - mean
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + epsilon)
    y = c * rstd
    y = y * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _ln_res_pallas(x, r, g, b, epsilon, block_m):
    """2-D [M, D] impl; returns (s, y, mean, rstd) with stats [M, 1] f32."""
    M, D = x.shape
    bm = _at.clamp_tile(block_m, M)
    Mp = -(-M // bm) * bm
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
        r = jnp.pad(r, ((0, Mp - M), (0, 0)))
    g2 = g.reshape(1, D)
    b2 = b.reshape(1, D)

    row = lambda i: (i, _at.I0)  # noqa: E731
    whole = lambda i: (_at.I0, _at.I0)  # noqa: E731
    s, y, mean, rstd = pl.pallas_call(
        functools.partial(_kernel, epsilon=epsilon),
        interpret=not _device.on_tpu(),
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, D), row),
            pl.BlockSpec((bm, D), row),
            pl.BlockSpec((1, D), whole),
            pl.BlockSpec((1, D), whole),
        ],
        out_specs=[
            pl.BlockSpec((bm, D), row),
            pl.BlockSpec((bm, D), row),
            pl.BlockSpec((bm, 1), row),
            pl.BlockSpec((bm, 1), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, D), x.dtype),
            jax.ShapeDtypeStruct((Mp, D), x.dtype),
            jax.ShapeDtypeStruct((Mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Mp, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(x, r, g2, b2)
    return s[:M], y[:M], mean[:M], rstd[:M]


def ln_block(M: int) -> int:
    """Rows a grid step of ``layernorm_residual`` takes of ``M``: 512, held
    to the padded rows.  A rule of the shape, held by the table
    ``tools/tile_table_chip.py`` timed on the chip (``PERF.md`` section 6,
    PR 48; BERT's ``[256 x 128, 768]`` bfloat16): 128, 256, 512 and 1024
    rows tie inside their spread (0.54-0.58 ms a call with the statistics a
    training step keeps, 0.41-0.42 the forward alone), and the chip's
    compiler REFUSES 1024 rows in a forward that drops the statistics (16.26
    MB of VMEM against 16.00).  Until PR 48 a measured search of
    ``ops.autotune``, which timed the four-output call, drew 1024 and so
    left a forward-only program of this shape unable to compile.
    ``block_m=`` stays for that tool and for the tests that run every
    block."""
    return _at.clamp_tile(512, M)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ln_res(x, r, g, b, epsilon, block_m):
    s, y, _, _ = _ln_res_pallas(x, r, g, b, epsilon, block_m)
    return s, y


def _ln_res_fwd(x, r, g, b, epsilon, block_m):
    s, y, mean, rstd = _ln_res_pallas(x, r, g, b, epsilon, block_m)
    return (s, y), (s, mean, rstd, g)


def _ln_res_bwd(epsilon, block_m, res, cts):
    s, mean, rstd, g = res
    ds_out, dy = cts
    sf = s.astype(jnp.float32)
    xhat = (sf - mean) * rstd
    dxhat = dy.astype(jnp.float32) * g.astype(jnp.float32)
    # closed-form LayerNorm VJP — three row reductions XLA fuses into one
    # pass over the block
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    ds = ds_out.astype(jnp.float32) + rstd * (dxhat - m1 - xhat * m2)
    dyf = dy.astype(jnp.float32)
    dg = jnp.sum(dyf * xhat, axis=0)
    db = jnp.sum(dyf, axis=0)
    return (ds.astype(s.dtype), ds.astype(s.dtype),
            dg.astype(g.dtype), db.astype(g.dtype))


_ln_res.defvjp(_ln_res_fwd, _ln_res_bwd)


def layernorm_residual(x, residual, weight, bias, *, epsilon: float = 1e-5,
                       block_m: Optional[int] = None):
    """``s = x + residual;  y = LayerNorm(s) * weight + bias`` in one pass.

    x/residual: ``[..., D]`` (same shape/dtype), weight/bias: ``[D]``.
    Returns ``(s, y)`` — the residual stream and the normalized output;
    pre-LN blocks use both, post-LN blocks use ``y``.  Differentiable in
    x, residual, weight and bias.  ``block_m`` defaults to the rule
    (:func:`ln_block`); an explicit one wins.
    """
    x = jnp.asarray(x)
    residual = jnp.asarray(residual)
    weight = jnp.asarray(weight)
    bias = jnp.asarray(bias)
    if x.shape != residual.shape:
        raise InvalidArgumentError(
            f"layernorm_residual: x {x.shape} vs residual {residual.shape}")
    D = x.shape[-1]
    if weight.shape != (D,) or bias.shape != (D,):
        raise InvalidArgumentError(
            f"layernorm_residual: affine shapes {weight.shape}/{bias.shape} "
            f"do not match feature dim {D}")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, D)
    r2 = residual.reshape(-1, D)
    if block_m is None:
        block_m = ln_block(x2.shape[0])
    s, y = _ln_res(x2, r2, weight, bias, float(epsilon), int(block_m))
    return s.reshape(*lead, D), y.reshape(*lead, D)
