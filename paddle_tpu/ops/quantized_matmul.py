"""Quantized linear kernels for the serving path — Pallas TPU.

The serving quantization stack (``GPTConfig.quantization`` /
``GenerationEngine(quantized=...)``) stores parallel-linear weights as
int8 or fp8-e4m3 plus a per-output-channel float32 dequant multiplier
(``weight_scale``, see ``slim.quantize_weights``).  This module is the
compute half: activations are quantized on the fly (per-tensor abs-max,
the LLM.int8() absmax recipe without the outlier split — serving-scale
models here stay within int8 range), the matmul runs on low-precision
operands, and ONE fused epilogue applies the combined
``weight_scale * act_scale`` rescale plus the bias:

    int8:  acc = x_q  @ w_q   (int8 × int8 → int32 on the MXU)
    fp8:   acc = x_q  @ w_q   (e4m3 operands, f32 accumulate)
    out    = acc * (weight_scale * act_scale) + bias

Tile sizes are a rule of the shape (:func:`qmm_blocks`), one for the
int8 and the fp8 leg.  int8/fp8 arrays tile
as (32, 128) on Mosaic — row blocks are multiples of 32, column blocks
of 128, and the whole contraction dim rides in VMEM zero-padded to a
lane multiple (exact: padded products are zero).

Off-TPU (and on any multi-device mesh — ``pallas_call`` has no GSPMD
partitioning rule) the same math runs as a plain XLA ``dot_general``
with the identical quantize → accumulate → rescale structure, so tokens
do not depend on which backend executed the layer.  Inference only: no
VJP is defined.
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

__all__ = ["quantized_matmul", "fp8_matmul", "quantized_linear",
           "quantize_activations"]

#: largest finite float8_e4m3fn (no inf in e4m3fn — clip before casting)
_FP8_MAX = 448.0

#: Mosaic sublane tile for 8-bit operand arrays
_SUBLANE_8BIT = 32


def _kernel(x_ref, w_ref, s_ref, b_ref, o_ref):
    if x_ref.dtype == jnp.int8:
        acc = jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.int32).astype(jnp.float32)
    else:
        # e4m3 operands: accumulate in f32 (upcast keeps the interpret
        # backend and older TPU generations on the same numerics)
        acc = jnp.dot(x_ref[...].astype(jnp.float32),
                      w_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    o_ref[...] = acc * s_ref[0] + b_ref[0]


def _qmm_pallas(xq, wq, scale, bias, block_m, block_n):
    """[M, K]q @ [K, N]q with the dequant+bias epilogue fused; returns
    float32 [M, N].  ``scale`` / ``bias`` are [N] float32 (the scale
    already folds the activation scale in)."""
    M, K = xq.shape
    N = wq.shape[1]
    bm = _at.clamp_tile(block_m, M, _SUBLANE_8BIT)
    bn = _at.clamp_tile(block_n, N, _at.LANE)
    Mp = -(-M // bm) * bm
    Np = -(-N // bn) * bn
    Kp = -(-K // 128) * 128
    if Mp != M or Kp != K:
        xq = jnp.pad(xq, ((0, Mp - M), (0, Kp - K)))
    if Kp != K or Np != N:
        wq = jnp.pad(wq, ((0, Kp - K), (0, Np - N)))
    if Np != N:
        scale = jnp.pad(scale, (0, Np - N))
        bias = jnp.pad(bias, (0, Np - N))
    s2 = scale.reshape(1, Np).astype(jnp.float32)
    b2 = bias.reshape(1, Np).astype(jnp.float32)

    out = pl.pallas_call(
        _kernel,
        interpret=not _device.on_tpu(),
        grid=(Mp // bm, Np // bn),
        in_specs=[
            pl.BlockSpec((bm, Kp), lambda i, j: (i, _at.I0)),
            pl.BlockSpec((Kp, bn), lambda i, j: (_at.I0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (_at.I0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (_at.I0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(xq, wq, s2, b2)
    return out[:M, :N]


def qmm_blocks(M: int, N: int):
    """``(block_m, block_n)`` of the quantized matmul over ``M`` rows and
    ``N`` output features: 128 x 128, held to the padded problem (rows in
    the 32-row tiles of 8-bit operands).  A rule of the shape and no
    measured search (it was one of ``ops.autotune`` until PR 48, and 128 x
    128 is what ran wherever that search did not: no benchmark cell runs
    this kernel, so no chip table is owed)."""
    return (_at.clamp_tile(128, M, _SUBLANE_8BIT),
            _at.clamp_tile(128, N, _at.LANE))


def quantize_activations(x, mode: str):
    """Dynamic per-tensor activation quantization: float [..., K] →
    (quantized x, scalar float32 dequant multiplier)."""
    xf = jnp.asarray(x, jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-9)
    if mode == "int8":
        xq = jnp.clip(jnp.round(xf * (127.0 / amax)),
                      -127, 127).astype(jnp.int8)
        return xq, amax / 127.0
    if mode == "fp8":
        xq = jnp.clip(xf * (_FP8_MAX / amax),
                      -_FP8_MAX, _FP8_MAX).astype(jnp.float8_e4m3fn)
        return xq, amax / _FP8_MAX
    raise InvalidArgumentError(
        f"quantization mode must be 'int8' or 'fp8', got {mode!r}")


def _use_pallas(n_features: int) -> bool:
    # same gate as the other fused epilogues: real TPU, lane-aligned
    # output features, a one-device mesh (pallas_call cannot be
    # GSPMD-partitioned).  Interpret-mode pallas would only slow the
    # CPU test path down; the XLA fallback is numerically identical.
    return _at.fused_epilogues_eligible(feature_dim=n_features)


def quantized_linear(x, w_q, weight_scale, bias=None):
    """The serving Linear hot path: float activations × pre-quantized
    weights, dispatched on the weight dtype.

    ``x`` float ``[..., K]``; ``w_q`` int8 or float8_e4m3fn ``[K, N]``;
    ``weight_scale`` float32 ``[N]`` per-channel dequant multiplier
    (``w ≈ w_q * weight_scale``, the ``slim.quantize_weights``
    convention); optional ``bias`` ``[N]``.  Activations are quantized
    on the fly per-tensor; output returns in ``x.dtype``."""
    x = jnp.asarray(x)
    w_q = jnp.asarray(w_q)
    if w_q.dtype == jnp.int8:
        mode = "int8"
    elif w_q.dtype == jnp.float8_e4m3fn:
        mode = "fp8"
    else:
        raise InvalidArgumentError(
            f"quantized_linear: weight dtype {w_q.dtype} is not int8 or "
            f"float8_e4m3fn")
    if weight_scale is None:
        raise InvalidArgumentError(
            "quantized_linear: quantized weights need a weight_scale "
            "(per-output-channel float32 dequant multiplier)")
    K, N = w_q.shape
    lead = x.shape[:-1]
    xq, act_scale = quantize_activations(x, mode)
    x2 = xq.reshape(-1, K)
    combined = (jnp.asarray(weight_scale, jnp.float32).reshape(-1)
                * act_scale)
    b = (jnp.zeros((N,), jnp.float32) if bias is None
         else jnp.asarray(bias, jnp.float32).reshape(-1))
    if _use_pallas(N):
        out2 = _qmm_pallas(x2, w_q, combined, b, *qmm_blocks(x2.shape[0], N))
    else:
        if mode == "int8":
            acc = jax.lax.dot_general(
                x2, w_q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
        else:
            acc = jnp.dot(x2.astype(jnp.float32),
                          w_q.astype(jnp.float32))
        out2 = acc * combined[None, :] + b[None, :]
    out_dtype = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.float32
    return out2.reshape(*lead, N).astype(out_dtype)


def quantized_matmul(x, w_q, weight_scale, bias=None):
    """int8 leg of :func:`quantized_linear` (validates the dtype)."""
    w_q = jnp.asarray(w_q)
    if w_q.dtype != jnp.int8:
        raise InvalidArgumentError(
            f"quantized_matmul: weight dtype {w_q.dtype} is not int8")
    return quantized_linear(x, w_q, weight_scale, bias)


def fp8_matmul(x, w_q, weight_scale, bias=None):
    """fp8-e4m3 leg of :func:`quantized_linear` (validates the dtype)."""
    w_q = jnp.asarray(w_q)
    if w_q.dtype != jnp.float8_e4m3fn:
        raise InvalidArgumentError(
            f"fp8_matmul: weight dtype {w_q.dtype} is not float8_e4m3fn")
    return quantized_linear(x, w_q, weight_scale, bias)
