"""Attention for latent-K/V decoders — two Pallas TPU kernels: the prompt's
flash kernel over an expanded view, and the decode step's page walk over the
latent pool where it lies.

``latent_prefill_attention``.  The expanded (prefill) form of multi-head
latent attention (``models/latent_moe.py``): per-head keys are ``[k_nope | k_rope]`` with ONE
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

``latent_decode``.  The absorbed (decode, one token a slot) form needs no
view at all: ``W^K`` is folded into the query, so a head's scores are its
absorbed query row against the page rows themselves, ``[c_kv | k_rope]`` as
the pool stores them, and its context is the probabilities times the FIRST
``kv_lora_rank`` lanes of the same rows.  So the walk of
``ops/paged_attention.py`` with ONE buffer where that kernel has a K and a V
one: grid ``(B,)``, one grid step a slot, all ``H`` heads the query ROWS of
it; an ``lax.fori_loop`` over the slot's key blocks up to its sweep bound
(:func:`~paddle_tpu.ops.paged_attention.sweep_bound`, 0 for a free slot),
each block's pages located in the scalar-prefetched page table and copied
from the pool in HBM (``memory_space=pl.ANY``) into a two-slot VMEM buffer,
block ``i + 1`` in flight while block ``i`` is multiplied: EACH PAGE IS
FETCHED ONCE, and a block costs two products, ``[H, width] x [width, keys]``
and ``[H, keys] x [keys, rank]``.  Visibility is
:func:`~paddle_tpu.ops.paged_attention.key_visible` of the slot's
``pos_map`` row and its query position, a tile at a time; online softmax in
float32; the products' operands in the pages' dtype with float32
accumulation, the probabilities rounded to the pages' dtype before the
context product (what ``LatentAttention.absorbed`` states over a gathered
view, which stays the CPU path and the reference this kernel is held to).
There is nothing to search: the keys a block holds are fixed by the shape
(:data:`DECODE_KEYS`, :func:`decode_block_pages`), the winner of
``tools/paged_decode_chip.py --latent`` on a v5e.
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
from ..framework.errors import InvalidArgumentError
from ..framework.flags import flag
from . import autotune as _at
from .paged_attention import _NEG, _ZERO, key_visible

__all__ = ["latent_prefill_attention", "latent_prefill_eligible",
           "tile_need", "latent_decode", "latent_decode_eligible",
           "decode_block_pages", "DECODE_KEYS"]

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


# -- the decode step: the page walk over the latent pool --------------------
#: Keys a block of the decode walk holds at most.  Fixed by the shape,
#: nothing to search: the winner of ``tools/paged_decode_chip.py --latent`` on
#: a v5e (a block is one dependent chain of scores, weights and context
#: whatever its keys, so few long blocks beat many short ones until the last
#: block's masked tail costs more than the chains saved).
DECODE_KEYS = 512


def _row_tile(dtype) -> int:
    """Rows of one sublane tile of ``dtype`` (8 of float32, 16 of bfloat16)."""
    return _at.SUBLANE * max(1, 4 // np.dtype(dtype).itemsize)


def decode_block_pages(page: int, G: int, keys: int = DECODE_KEYS) -> int:
    """Logical pages a key block of the decode walk holds: ``keys`` keys'
    worth, at least one page, at most the window rounded up to whole lane
    tiles of scores (a short window is one block)."""
    window = -(-G * page // _at.LANE) * _at.LANE
    return max(1, min(keys, window) // page)


def latent_decode_eligible(pool, T: int) -> bool:
    """Should ``LatentAttention.forward_paged`` walk the pool in the kernel?
    A real TPU backend (interpret mode loses; the gather path is the CPU
    reference), the decode width, page rows of whole lane tiles, pages of
    whole sublane tiles of the pool's dtype, and a one-device mesh
    (``autotune.mesh_admits_kernels``).  ``FLAGS_paged_flash`` shuts it as
    it shuts the other page walk."""
    if not flag("paged_flash") or not _device.on_tpu() or T != 1:
        return False
    _, page, width = pool.shape
    if width % _at.LANE or page % _row_tile(pool.dtype):
        return False
    return _at.mesh_admits_kernels()


def _decode_kernel(tab_ref, nb_ref, qpos_ref, q_ref, kp_ref, pool_hbm, o_ref,
                   buf, sem, m_s, l_s, acc_s, *, ppb: int, window: int,
                   scale: float):
    """One slot's sweep: the online softmax of its ``H`` query rows over
    the key blocks of its own bound, each page fetched once by this step's
    own DMA."""
    # i32 constants are typed: under the package's global x64 a Python int
    # next to a traced i32 becomes an i64, which Mosaic does not lower
    i32 = np.int32
    b = pl.program_id(0)
    n = nb_ref[b]      # key blocks to walk
    qp = qpos_ref[b]   # the slot's query position (-1: a free slot)
    bk = buf.shape[1]
    page = bk // ppb
    vw = acc_s.shape[1]
    row0 = b * i32(kp_ref.shape[1] * ppb)  # the slot's row of the flat table

    def copies(i, slot):
        """The DMAs of key block ``i`` into buffer ``slot``.  ``i`` None:
        descriptors to WAIT with (a wait reads the destination and the
        semaphore; its source only has to have the shape)."""
        return [pltpu.make_async_copy(
            pool_hbm.at[i32(0) if i is None
                        else tab_ref[row0 + i * i32(ppb) + i32(j)]],
            buf.at[slot, pl.ds(j * page, page)], sem.at[slot])
            for j in range(ppb)]

    m_s[...] = jnp.full_like(m_s, _NEG)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    q = q_ref[0]  # [H, width]: [q_abs | q_rope | 0]

    def multiply(i, slot):
        """Key block ``i``, landed in buffer ``slot``, into the running
        max / sum / accumulator of every head."""
        valid = key_visible(kp_ref[0, i], qp, i32(window))       # [1, bk]
        s = jax.lax.dot_general(
            q, buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        s = jnp.where(valid, s, _NEG)                            # [H, bk]
        m_prev = m_s[...]                        # [H, LANE], lanes equal
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # masked keys -> 0 (a row with nothing yet: exp(0))
        p = jnp.where(valid, jnp.exp(s - m_new[:, :1]), _ZERO)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        # the values are the first lanes of the rows the scores read
        acc_s[...] = acc_s[...] * alpha[:, :1] + jnp.dot(
            p.astype(buf.dtype), buf[slot, :, :vw],
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    def step(i, carry):
        """Iteration ``i`` of ``bound + 1``: start block ``i``'s copies,
        then multiply block ``i - 1`` while they fly."""
        @pl.when(i < n)
        def _start():
            for c in copies(i, jax.lax.rem(i, i32(2))):
                c.start()

        @pl.when(i > i32(0))
        def _multiply():
            slot = jax.lax.rem(i - i32(1), i32(2))
            for c in copies(None, slot):
                c.wait()
            multiply(i - i32(1), slot)

        return carry

    # a bound of 0 (a free slot): one empty iteration
    jax.lax.fori_loop(i32(0), n + i32(1), step, i32(0))

    l = l_s[:, :1]  # a slot that saw nothing: l == 0, zeros out
    o_ref[0] = jnp.where(l > 0, acc_s[...] / jnp.maximum(l, 1e-30),
                         _ZERO).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "value_width",
                                             "block_keys"))
def latent_decode(q, pool, tables, pos_map, positions, bound=None, *,
                  scale: float, value_width: int,
                  block_keys: int = DECODE_KEYS):
    """Absorbed latent attention of one query token a slot over the paged
    latent pool, page walk in-kernel.

    q: ``[B, H, width]`` absorbed query rows ``[q_nope W^K | q_rope | 0]``,
    lane for lane what a page row holds; pool: ``[P+1, page, width]``, the
    latent pages in their stored order (the last page is the write-drop
    page), ALREADY scattered with this step's latents; tables: ``[B, G]``
    i32 page-table rows, unmapped entries pre-clipped to a valid page (their
    ``pos_map`` is -1); pos_map: ``[B, G*page]`` i32, the absolute position
    each cache entry holds (-1: none); positions: ``[B, 1]`` i32, the
    queries' absolute positions (-1: a free slot); validity is
    ``paged_attention.key_visible`` of the two with the window ``G*page``,
    the gather path's mask; bound: ``[B]`` i32 logical pages to walk
    (``paged_attention.sweep_bound`` of that mask; None walks the whole
    window).  Returns ``[B, H, value_width]`` in the pool's dtype:
    ``softmax(scale * q rows^T)`` over the visible keys times the rows'
    first ``value_width`` lanes, zeros for a slot that sees nothing.
    ``block_keys`` (keys a block, see :func:`decode_block_pages`) is the
    rule's unless a table or a test asks for another."""
    B, H, W = q.shape
    _, page, Wp = pool.shape
    G = tables.shape[1]
    C = G * page
    if W != Wp or pos_map.shape != (B, C) or positions.shape != (B, 1):
        raise InvalidArgumentError(
            f"latent_decode: q {q.shape} against pool {pool.shape}, pos_map "
            f"{pos_map.shape} != {(B, C)} or positions {positions.shape} != "
            f"{(B, 1)}")
    ppb = decode_block_pages(page, G, block_keys)
    nblk, bk = -(-G // ppb), ppb * page
    vw = min(-(-value_width // _at.LANE) * _at.LANE, W)
    rows = _row_tile(pool.dtype)
    Hp = -(-H // rows) * rows
    qp = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, Hp - H), (0, 0)))
    # a window that is not whole blocks: the last block's tail is page 0 at
    # position -1, like any unmapped entry
    kpos = jnp.pad(pos_map.astype(jnp.int32), ((0, 0), (0, nblk * bk - C)),
                   constant_values=-1).reshape(B, nblk, 1, bk)
    tab = jnp.pad(tables.astype(jnp.int32),
                  ((0, 0), (0, nblk * ppb - G))).reshape(-1)
    nb = (jnp.full((B,), nblk, jnp.int32) if bound is None  # pages -> blocks
          else jnp.minimum(-(-bound.astype(jnp.int32) // ppb), nblk))
    z = _at.I0
    out = pl.pallas_call(
        functools.partial(_decode_kernel, ppb=ppb, window=C,
                          scale=float(scale)),
        name="latent_decode",
        interpret=not _device.on_tpu(),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # the page table (flat), the blocks to walk, the query positions
            num_scalar_prefetch=3,
            grid=(B,),  # the key blocks are the kernel's own loop
            in_specs=[
                pl.BlockSpec((1, Hp, W), lambda b, *_: (b, z, z)),
                pl.BlockSpec((1, nblk, 1, bk), lambda b, *_: (b, z, z, z)),
                # the pool as it is stored, left in HBM: the kernel copies
                # the pages it walks
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, Hp, vw), lambda b, *_: (b, z, z)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, W), pool.dtype),  # ONE two-slot buffer
                pltpu.SemaphoreType.DMA((2,)),       # one a buffer slot
                pltpu.VMEM((Hp, _at.LANE), jnp.float32),  # running max
                pltpu.VMEM((Hp, _at.LANE), jnp.float32),  # running sum
                pltpu.VMEM((Hp, vw), jnp.float32),        # out accum
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hp, vw), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(tab, nb, positions[:, 0].astype(jnp.int32), qp, kpos, pool)
    return out[:, :H, :value_width]
