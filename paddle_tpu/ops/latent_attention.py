"""Prompt attention for latent-K/V decoders — Pallas TPU flash kernel.

The expanded (prefill) form of multi-head latent attention
(``models/latent_moe.py``): per-head keys are ``[k_nope | k_rope]`` with ONE
rotary key shared by all heads, values have their own width, and what a
query may see is decided by POSITIONS, not by where a key lies: the keys
are a slot's gathered page view, in ring order, holes and stale entries
marked ``-1``.

    visible(q, k) = kpos >= 0  and  kpos <= qpos  and  kpos > qpos - ring

One grid step holds a ``[block_q, block_k]`` tile of scores in VMEM with
the running max / sum / accumulator of online softmax, so VMEM does not
grow with the prompt bucket and the ``[T, C]`` scores never reach HBM.
Whether a tile can hold anything visible is worked out beforehand from the
tiles' position ranges (``tile_need``) and rides in as a scalar-prefetched
table: a tile that cannot is skipped, which is most of them for a short
prompt in a long slot.  Rows that see nothing (padding) come out zero.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from . import autotune as _at

__all__ = ["latent_prefill_attention", "latent_prefill_eligible",
           "tile_need"]

_NEG_INF = -jnp.inf
_BLOCK = 512


def latent_prefill_eligible(nope, rope, vdim, T, C) -> bool:
    """TPU, a one-device mesh, lane-aligned head widths and whole tiles."""
    if not _at.fused_epilogues_eligible(nope):
        return False
    return (vdim % _at.LANE == 0 and rope % 64 == 0 and T % 128 == 0
            and C % 128 == 0)


def tile_need(qpos, kpos, ring, block_q, block_k):
    """``[B, T/bq, C/bk]`` bool: could any query of the tile see any key of
    the tile?  From the tiles' position ranges alone, so it may say yes for
    a tile that holds nothing visible, never no for one that does."""
    B = qpos.shape[0]
    big = jnp.iinfo(jnp.int32).max
    q = qpos.reshape(B, -1, block_q)
    k = kpos.reshape(B, -1, block_k)
    q_hi = q.max(-1)[:, :, None]
    q_lo = jnp.where(q >= 0, q, big).min(-1)[:, :, None]
    k_hi = k.max(-1)[:, None, :]
    k_lo = jnp.where(k >= 0, k, big).min(-1)[:, None, :]
    return (k_lo <= q_hi) & (k_hi > q_lo - ring)


def _kernel(need_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, qp_ref, kp_ref,
            o_ref, m_scr, l_scr, acc_scr, *, scale, ring, nq, nk):
    i32 = jnp.int32
    b, qi, ki = (pl.program_id(0).astype(i32), pl.program_id(2).astype(i32),
                 pl.program_id(3).astype(i32))

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(need_ref[(b * nq + qi) * nk + ki] != 0)
    def _step():
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[0, 0], kn_ref[0, 0], nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0, 0], kr_ref[0], nt,
                                   preferred_element_type=jnp.float32))
        qp, kp = qp_ref[0], kp_ref[0]          # [bq, 1], [1, bk]
        seen = (kp >= 0) & (kp <= qp) & (kp > qp - i32(ring))
        s = jnp.where(seen, s * np.float32(scale), _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # rows that have seen nothing yet: exp(-inf - -inf) would be nan
        m_safe = jnp.where(jnp.isneginf(m_new), np.float32(0.0), m_new)
        p = jnp.exp(s - m_safe)
        alpha = jnp.where(jnp.isneginf(m_prev), np.float32(0.0),
                          jnp.exp(m_prev - m_safe))
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, 0],
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _fin():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(
            l == 0.0, np.float32(1.0), l)).astype(o_ref.dtype)


def latent_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, qpos, kpos,
                             ring, scale):
    """``q_nope`` ``[B, H, T, dn]``, ``q_rope`` ``[B, H, T, dr]``,
    ``k_nope`` ``[B, H, C, dn]``, ``k_rope`` ``[B, C, dr]`` (shared by the
    heads), ``v`` ``[B, H, C, dv]``, positions ``qpos`` ``[B, T]`` and
    ``kpos`` ``[B, C]`` (``-1``: padding / nothing written).  Returns
    ``[B, H, T, dv]`` in ``v``'s dtype: ``softmax(scale * (q_nope k_nope^T
    + q_rope k_rope^T))`` over the visible keys, times ``v``."""
    B, H, T, dn = q_nope.shape
    C, dr, dv = k_nope.shape[2], q_rope.shape[3], v.shape[3]
    bq, bk = math.gcd(T, _BLOCK), math.gcd(C, _BLOCK)
    nq, nk = T // bq, C // bk
    qpos = jnp.asarray(qpos, jnp.int32)
    kpos = jnp.asarray(kpos, jnp.int32)
    need = tile_need(qpos, kpos, ring, bq, bk).astype(jnp.int32).reshape(-1)
    kernel = functools.partial(_kernel, scale=float(scale), ring=int(ring),
                               nq=nq, nk=nk)
    z = _at.I0
    return pl.pallas_call(
        kernel,
        name="latent_prefill_attention",
        interpret=not _device.on_tpu(),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, dn), lambda b, h, i, j, n: (b, h, i, z)),
                pl.BlockSpec((1, 1, bq, dr), lambda b, h, i, j, n: (b, h, i, z)),
                pl.BlockSpec((1, 1, bk, dn), lambda b, h, i, j, n: (b, h, j, z)),
                pl.BlockSpec((1, bk, dr), lambda b, h, i, j, n: (b, j, z)),
                pl.BlockSpec((1, 1, bk, dv), lambda b, h, i, j, n: (b, h, j, z)),
                pl.BlockSpec((1, bq, 1), lambda b, h, i, j, n: (b, i, z)),
                pl.BlockSpec((1, 1, bk), lambda b, h, i, j, n: (b, z, j)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, dv),
                                   lambda b, h, i, j, n: (b, h, i, z)),
            scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, dv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, T, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(need, q_nope, q_rope, k_nope, k_rope, v, qpos[:, :, None],
      kpos[:, None, :])
