"""Fused softmax-cross-entropy — Pallas TPU kernel, no [M, V] prob matrix.

The LM losses (GPT next-token, BERT MLM) compute
``-log_softmax(logits)[label]`` over a vocab-sized axis.  The XLA lowering
materializes the full ``[M, V]`` log-probability tensor in HBM just to
gather one element per row — for GPT at B·S = 8k rows and V = 50k that is
a 1.6 GB write + read whose only purpose is a ``[M]`` gather.  This kernel
streams vocab blocks through VMEM with the flash-attention online-softmax
recurrence (running max + running sum-of-exp) and picks the label logit on
the fly, so nothing vocab-sized is ever written:

    loss[i] = logsumexp(logits[i, :]) - logits[i, label[i]]

The backward needs ``d logits`` — an [M, V] tensor by definition — but it
is produced directly as ``(exp(logits - lse) - onehot) * g`` in one fused
XLA elementwise pass from the saved per-row ``lse``; the probability
matrix still never exists on its own.  Integer labels get a symbolic-zero
(float0) cotangent.

Tile sizes are a rule of the shape (:func:`xent_blocks`).  The
vocab axis is padded to the block multiple and masked in-kernel, so any V
works (no 128-alignment requirement on the caller).
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

__all__ = ["softmax_cross_entropy"]

_NEG_INF = -jnp.inf


def _kernel(x_ref, lab_ref, loss_ref, lse_ref, m_scr, l_scr, acc_scr,
            *, V: int, block_v: int):
    i32 = jnp.int32
    vi = pl.program_id(1).astype(i32)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)               # (bm, bv)
    v_pos = vi * i32(block_v) + jax.lax.broadcasted_iota(i32, x.shape, 1)
    x = jnp.where(v_pos < i32(V), x, _NEG_INF)       # mask the padded tail

    # flash-style online logsumexp over the vocab sweep
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    l_new = l_scr[:, :1] * alpha + jnp.sum(jnp.exp(x - m_safe), axis=-1,
                                           keepdims=True)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # the label logit lives in exactly one vocab block — accumulate it
    lab = lab_ref[...]                               # (bm, 1) i32
    hit = jnp.sum(jnp.where(v_pos == lab, x, 0.0), axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] + jnp.broadcast_to(hit, acc_scr.shape)

    @pl.when(vi == nv - 1)
    def _fin():
        l = l_scr[:, :1]
        lse = m_scr[:, :1] + jnp.log(l)
        lse_ref[...] = lse
        loss_ref[...] = lse - acc_scr[:, :1]


def _sxent_pallas(logits, labels, block_m, block_v):
    """2-D [M, V] impl; labels [M] i32.  Returns (loss [M], lse [M]) f32."""
    M, V = logits.shape
    bm = _at.clamp_tile(block_m, M)
    bv = _at.clamp_tile(block_v, V, _at.LANE)
    Mp = -(-M // bm) * bm
    Vp = -(-V // bv) * bv
    xp = logits
    if (Mp, Vp) != (M, V):
        xp = jnp.pad(logits, ((0, Mp - M), (0, Vp - V)))
    lab = labels.reshape(M, 1)
    if Mp != M:
        lab = jnp.pad(lab, ((0, Mp - M), (0, 0)))

    row = lambda i, j: (i, _at.I0)  # noqa: E731
    loss, lse = pl.pallas_call(
        functools.partial(_kernel, V=V, block_v=bv),
        interpret=not _device.on_tpu(),
        grid=(Mp // bm, Vp // bv),  # vocab minor: sequential online sweep
        in_specs=[
            pl.BlockSpec((bm, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), row),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), row),
            pl.BlockSpec((bm, 1), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Mp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, 128), jnp.float32),  # running max
            pltpu.VMEM((bm, 128), jnp.float32),  # running sum-of-exp
            pltpu.VMEM((bm, 128), jnp.float32),  # label-logit accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(xp, lab)
    return loss[:M, 0], lse[:M, 0]


def xent_blocks(M: int, V: int):
    """``(block_m, block_v)`` of ``softmax_cross_entropy`` over ``M`` rows
    of ``V`` logits: 512 x 2048, held to the padded problem.  A rule of the
    shape, from the table ``tools/tile_table_chip.py`` timed on the chip
    (``PERF.md`` section 6, PR 48; BERT's 5120 masked rows x 30 522,
    bfloat16, all seventeen blocks that fit): 512 x 2048 wins at 2.359 ms
    (spread 0.006), 256 x 2048 (the default before there was a search)
    2.417, 128 x 8192 2.447, 512 x 1024 2.462, 512-wide blocks 2.69-4.13.
    Until PR 48 a measured search of ``ops.autotune``, which drew it too.
    ``block_m=`` / ``block_v=`` stay for that tool and for the tests that
    run every block."""
    return _at.clamp_tile(512, M), _at.clamp_tile(2048, V, _at.LANE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _sxent(logits, labels, block_m, block_v):
    loss, _ = _sxent_pallas(logits, labels, block_m, block_v)
    return loss


def _sxent_fwd(logits, labels, block_m, block_v):
    loss, lse = _sxent_pallas(logits, labels, block_m, block_v)
    return loss, (logits, labels, lse)


def _sxent_bwd(block_m, block_v, res, g):
    logits, labels, lse = res
    V = logits.shape[-1]
    # d logits = (softmax(logits) - onehot) * g — one fused elementwise
    # pass; the exp never exists separately from the cotangent output
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    onehot = (labels[:, None] == jnp.arange(V, dtype=labels.dtype)[None, :])
    dlogits = (p - onehot.astype(jnp.float32)) * g[:, None].astype(
        jnp.float32)
    return (dlogits.astype(logits.dtype),
            np.zeros(labels.shape, jax.dtypes.float0))


_sxent.defvjp(_sxent_fwd, _sxent_bwd)


def softmax_cross_entropy(logits, labels, *, block_m: Optional[int] = None,
                          block_v: Optional[int] = None):
    """Per-row ``-log_softmax(logits)[label]`` without materializing the
    probability (or log-probability) matrix in the forward.

    logits: ``[..., V]``, labels: ``[...]`` integer class ids in
    ``[0, V)``.  Returns float32 losses of the label shape.  Blocks
    default to the rule (:func:`xent_blocks`); an explicit one wins.
    Differentiable in logits; labels get a symbolic-zero cotangent.
    """
    logits = jnp.asarray(logits)
    labels = jnp.asarray(labels)
    if logits.shape[:-1] != labels.shape:
        raise InvalidArgumentError(
            f"softmax_cross_entropy: logits {logits.shape} vs labels "
            f"{labels.shape}")
    V = logits.shape[-1]
    lead = labels.shape
    x2 = logits.reshape(-1, V)
    lab2 = labels.reshape(-1).astype(jnp.int32)
    loss = _sxent(x2, lab2, *_at.blocks_or(
        xent_blocks(x2.shape[0], V), block_m, block_v))
    return loss.reshape(lead)
