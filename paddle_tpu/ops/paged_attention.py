"""Paged flash-decode attention — the page-table walk INSIDE the kernel.

The paged serving decode path (``GPTModel.forward_paged``) historically
attended over a gathered KV view: ``jnp.take`` materializes each slot's
logical ``[B, H, C, hd]`` cache from the shared page pool, quantized
pages are dequantized to float IN FULL before attention, and a plain
einsum runs over the result — one full HBM round-trip of the decode
working set per layer per step, twice that for quantized pools (read
int8, write float, read float).  PagedAttention (Kwon SOSP'23) puts the
page-table indirection inside the attention kernel instead; this module
is that kernel for TPU, in the shape of the repo's other Pallas kernels:

* the pool is read in its STORED order, ``[P+1, page, H*hd]``: one
  token's K (or V) for all heads is one contiguous lane-dense row, the
  same rows ``forward_paged``'s scatter writes and the paged programs
  take in and hand back, so nothing re-orders the pool between them.  The
  pools stay in HBM (``memory_space=pl.ANY``), as do a quantized pool's
  scale planes (those padded to a lane tile first: a DMA moves whole
  lane tiles); the kernel fetches what it reads itself;
* grid ``(B, H/bh)``: one grid step is one slot's whole sweep for ``bh``
  heads.  The page dimension is NOT in the grid (until PR 30 it was: one
  ``[page, bh*hd]`` pair a grid step, whose fixed cost, not its bytes, was
  the kernel's time, and every page of the window was swept whatever was
  live).  Inside the step an ``lax.fori_loop`` walks the slot's KEY
  BLOCKS: ``ceil(128 / page)`` logical pages, so that a block's keys are
  one lane tile of scores (8 pages of 16, 1 of 128: fixed by the page
  size, nothing to search).  A block's pages are located in the
  scalar-prefetched i32 page table and copied into a two-slot VMEM buffer
  by ``make_async_copy`` (the ``bh*hd`` lanes of this step's heads of each
  page's rows), block ``i+1`` in flight while block ``i`` is multiplied;
* a call of more than :data:`QUERY_TILE` (320) query rows, an admission
  bucket, is cut evenly into the fewest QUERY TILES of at most that many
  rows (:func:`query_tile`: 768 -> 3 x 256, 640 -> 2 x 320), and the tile
  is a grid axis: grid ``(B, nq, H/bh)``, the same kernel body.  The q, out and
  position blocks and the online-softmax scratch are a TILE's, so VMEM does
  not grow with the bucket (a step that held a 768-row bucket whole had
  room for two of GPT-2's twelve heads and 896 rows did not compile; a tile
  takes all twelve, the page table walked once and each page row fetched in
  one piece for all of them).  A call of at most one tile (the decode step,
  the verify width, a short bucket) keeps the two-axis grid and is the
  program it was;
* the loop walks only the blocks of the first ``bound`` pages, a second
  scalar-prefetched operand: the SWEEP BOUND (:func:`sweep_bound`), up to
  the last page that holds a key some query row of the step can see, in
  whole blocks; 0 for a free slot or a padding row.  It is a slot's,
  ``[B]``, for a one-tile call and a tile's, ``[B, nq]``, past it: each
  tile is swept to ITS OWN last visible key block, so an admission walks
  the causal triangle and not its square, and a tile of padding rows
  nothing.  Skipping is exact: a block in which no row of the tile sees a
  key would leave the running max, sum and accumulator bit for bit as
  they were.  ``GPTModel.forward_paged`` computes the bound once a program
  from the validity mask it builds anyway and every layer shares it; it
  is data, not shape (a row admitted behind a shared prefix reads the
  prefix's blocks from its first tile), and an upper bound only (a
  ring-wrapped slot gets the whole window): every key inside a walked
  block is still tested by the model's rule;
* that rule (:func:`key_visible`: a real token, causally at or before the
  query, within the last ``C`` positions) is rebuilt in the kernel from
  the slot's ``pos_map`` row and the rows' ``positions`` — three integer
  comparisons on a ``[rows, 128]`` tile, shared by the block's heads —
  instead of moving a ``[B, T, C]`` float mask through HBM (at an
  admission width the mask tile was four times the K/V bytes).  Causality,
  ragged page counts, the write-drop page and the speculative ``1+k``
  verify width all fold into it (the pool is scattered BEFORE attention,
  so intra-step draft causality is just ``kp <= qp``).  Unmapped table
  entries are pre-clipped to page 0 and their ``pos_map`` entries are
  ``-1``; rows that see nothing (query padding, free slots) emit zeros;
* inside the block a head is a STATIC ``hd``-lane slice of the
  ``[128, bh*hd]`` tile: the same per-head products as a per-head block,
  every head, every visible key;
* at the decode width (one query token a slot, float pages) the heads
  ARE the query rows: the query is laid out block-diagonal, ``[H, H*hd]``
  with row h holding ``q_h`` in head h's own lanes and zeros elsewhere,
  and swept as ONE head of width ``H*hd`` — one ``[H, H*hd] x [H*hd,
  128]`` product gives every head's scores (the other heads' lanes add
  exact zeros), one ``[H, 128] x [128, H*hd]`` product their contexts,
  of which row h keeps its own lanes.  Same kernel body, same products
  in the same f32 accumulation; 2 products a block instead of 2H;
* flash-style online softmax: running max / normalizer / output
  accumulator ride VMEM scratch across the block loop, so attention
  memory is O(block), never O(C);
* quantized pools (int8 / fp8-e4m3) dequantize PER BLOCK inside the
  loop — ``k_f32 = k_q * k_scale`` on the tile that is already in VMEM.
  A float KV view is never materialized in HBM; the pool bytes crossing
  the memory bus per step are the quantized bytes of the walked pages.

Equivalence: same math as the gather-then-attend reference modulo
float reassociation (online softmax accumulates in f32, 128 keys a
partial sum); the reference path stays the bit-identical CPU/fallback —
``paged_flash_eligible`` gates dispatch exactly like
``fused_epilogues_eligible`` does for the other epilogues (TPU backend,
one-device mesh, aligned dims).

There is nothing to tune, so two checkouts of one tree build one program:
the key block is fixed by the page size, the query tile by the shape
(:data:`QUERY_TILE`), and ``block_h`` — heads per grid step, i.e. how many
lanes of a page row one step fetches — is by rule the most that fit the
VMEM budget (:func:`_heads_a_step`) among the divisors of H whose
``bh*hd`` lanes are whole lane tiles (or all of ``H*hd``).  Until PR 42
``block_h`` was a measured search of ``ops.autotune``, whose near-ties fell
either way in a cold checkout; the rule is the winner of
``tools/paged_decode_chip.py --admit`` on the chip.  ``block_h=`` stays as
an explicit argument; per-block equivalence and the tiled grid's bit
identity with the one-tile grid are tested in
tests/test_paged_attention.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from ..framework.errors import InvalidArgumentError
from ..framework.flags import flag
from . import autotune as _at

__all__ = ["paged_flash_decode", "paged_flash_eligible", "paged_attention",
           "key_visible", "block_pages", "sweep_bound", "query_tile"]

# mask fill; exp(_NEG - m) underflows to exactly 0.0 in f32.  Typed f32:
# under the package's global x64 a bare Python float reaches ``jnp.where``
# as an f64 scalar, which Mosaic cannot legalize.
_NEG = np.float32(-1e30)
_ZERO = np.float32(0.0)


def key_visible(kp, qp, window):
    """THE validity rule of the paged cache, for numpy and jax arrays
    alike: the key at absolute position ``kp`` (``-1``: nothing written)
    is visible to the query at ``qp`` (``-1``: padding) iff it holds a
    real token, causally at or before the query, within the last
    ``window`` positions.  ``forward_paged`` builds its mask with it, the
    kernel applies it a tile at a time, the engine's loop counts with
    it."""
    return (kp >= 0) & (kp <= qp) & (kp > qp - window)


def block_pages(page: int) -> int:
    """Logical pages a key block of the sweep holds: as many as make the
    block's keys one lane tile of scores (8 pages of 16, 1 page of 128)."""
    return -(-_at.LANE // page)


#: Query rows a grid step holds at most: a call of more rows (an admission
#: bucket) is cut evenly into the fewest tiles of at most this many
#: (:func:`query_tile`), each swept to its own bound.  Fixed by the shape,
#: nothing to search: the winner of ``tools/paged_decode_chip.py --admit``
#: on a v5e (a tile's every (head, key block) chain of products costs
#: 0.4 us whatever its rows and 0.19 us per 128 rows: 128-row tiles lose
#: the triangle's gain to their chains, 256 to 320 keep it).
QUERY_TILE = 320


def query_tile(T: int, most: int = QUERY_TILE) -> int:
    """Rows of a query tile of a call of ``T`` rows: the call whole up to
    ``most`` rows (one tile: the grid a decode step has), else ``T`` cut
    evenly into the fewest tiles of at most ``most``, in whole sublane
    tiles (768 -> 3 x 256, 640 -> 2 x 320, 1024 -> 4 x 256)."""
    Tp = -(-T // _at.SUBLANE) * _at.SUBLANE
    nq = -(-Tp // most)
    return -(-Tp // (nq * _at.SUBLANE)) * _at.SUBLANE


#: Heads of a grid step whose chains of products (scores, weights, context)
#: the kernel issues abreast, phase by phase: a chain is bound by the
#: latency of its dependent products, not by their work
#: (``tools/paged_decode_chip.py --admit``: 0.159 ms one head at a time,
#: 0.117 four abreast, a ``[2, 768]`` layer call in tiles of 256 on a v5e).
#: The decode width sweeps ONE head as wide as a pool row: nothing abreast
_ABREAST = 4

#: VMEM the tiled grid's kernel may use (the compiler's default scope is
#: ``autotune.VMEM_BYTES``, which the one-tile programs keep)
_TILED_VMEM = 32 * 1024 * 1024


def sweep_bound(visible, page: int, tile: Optional[int] = None):
    """Logical pages the sweep has to walk: up to the last page that holds
    a key visible to ANY query row, rounded up to whole key blocks
    (:func:`block_pages` pages; the window's end cuts the last one), 0
    where nothing is visible.  ``visible``: ``[B, T, C]`` bool (numpy or
    jax), the mask of :func:`key_visible`.  A call of at most ``tile`` rows
    (:func:`query_tile` of the call's, unless given) is one tile: ``[B]``
    int32, a slot's bound.  A wider one gets a bound a QUERY TILE, ``[B,
    ceil(T / tile)]``: the causal triangle's rows, not its square; 0 for a
    tile of padding rows.  A ring-wrapped slot's live pages are not a
    prefix of its table: its bounds are simply the whole window."""
    B, T, C = visible.shape
    tile = tile or query_tile(T)
    G = C // page
    nth = np.arange(1, G + 1, dtype=np.int32)  # a page's number, from 1
    if T <= tile:  # the reduction a step program has had since PR 30
        live = visible.any(axis=1).reshape(B, G, page).any(axis=2)  # [B, G]
        pages = (live * nth).max(axis=1)
    else:  # the same, a tile of rows at a time
        nq = -(-T // tile)
        if nq * tile != T:
            xp = np if isinstance(visible, np.ndarray) else jnp
            visible = xp.pad(visible, ((0, 0), (0, nq * tile - T), (0, 0)))
        live = visible.reshape(B, nq, tile, C).any(axis=2).reshape(
            B, nq, G, page).any(axis=3)  # [B, nq, G]
        pages = (live * nth).max(axis=2)
    ppb = block_pages(page)
    return ((pages + (ppb - 1)) // ppb * ppb).clip(0, G).astype(np.int32)


def _kernel(tab_ref, bound_ref, q_ref, qp_ref, kp_ref, k_hbm, v_hbm, *refs,
            block_h: int, window: int, sm_scale: float, quantized: bool,
            tiled: bool, abreast: int = 1):
    """One (slot, head-block) step, or with ``tiled`` one (slot, query
    tile, head-block) step: the online-softmax sweep of the step's query
    rows over the key blocks of its own bound, each fetched by this step's
    own DMA, ``abreast`` heads' products at a time.  One body for both
    grids: a tile is a slot's rows, fewer."""
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem,
         m_s, l_s, acc_s) = refs
    else:
        o_ref, k_buf, v_buf, sem, m_s, l_s, acc_s = refs
    # i32 constants are typed: under the package's global x64 a Python int
    # next to a traced i32 becomes an i64, which Mosaic does not lower
    i32 = np.int32
    if tiled:  # grid (B, tiles, head blocks), bound [B, tiles]
        b, hb = pl.program_id(0), pl.program_id(2)
        n = bound_ref[b, pl.program_id(1)]
    else:
        b, hb = pl.program_id(0), pl.program_id(1)
        n = bound_ref[b]  # key blocks to walk
    hd = q_ref.shape[-1]
    _, ppb, page, width = k_buf.shape  # two slots of ppb pages' lanes
    bk = ppb * page
    whole_row = width == k_hbm.shape[2]
    lane0 = pl.multiple_of(hb * i32(width), width)  # this head block's lanes

    def copies(i, slot):
        """The DMAs of key block ``i`` into buffer ``slot``.  ``i`` None:
        descriptors to WAIT with (a wait reads the destination and the
        semaphore; its source only has to have the shape)."""
        out = []
        for j in range(ppb):
            pg = i32(0) if i is None else tab_ref[b, i * i32(ppb) + i32(j)]
            lanes = (slice(None) if whole_row else
                     pl.ds(i32(0) if i is None else lane0, width))
            for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                out.append(pltpu.make_async_copy(
                    pool.at[pg, :, lanes], buf.at[slot, i32(j)],
                    sem.at[slot]))
            if quantized:  # a page's scales for all heads, [page, H]
                for pool, buf in ((ks_hbm, ks_buf), (vs_hbm, vs_buf)):
                    out.append(pltpu.make_async_copy(
                        pool.at[pg], buf.at[slot, i32(j)], sem.at[slot]))
        return out

    m_s[...] = jnp.full_like(m_s, _NEG)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    qp = qp_ref[0]  # [Tp, 1] absolute positions of the query rows

    def multiply(i, slot):
        """Key block ``i``, landed in buffer ``slot``, into the running
        max / sum / accumulator of each head."""
        # the model's rule on this block's keys: [1, bk] against [Tp, 1]
        valid = key_visible(kp_ref[0, i], qp, i32(window))
        if quantized:
            ks_all, vs_all = ks_buf[slot], vs_buf[slot]  # [ppb, page, H..]
            head_of = jax.lax.broadcasted_iota(jnp.int32, ks_all.shape, 2)
            h0 = hb * i32(block_h)  # first head of this block (i32)

        def scores(h):
            """Head ``h`` of this step's block: its scores against the key
            block, and the block's values."""
            lanes = slice(h * hd, (h + 1) * hd)  # this head's lanes of a row
            q = q_ref[0, h].astype(jnp.float32)              # [Tp, hd]
            k = k_buf[slot, :, :, lanes].astype(jnp.float32)  # [ppb,page,hd]
            v = v_buf[slot, :, :, lanes].astype(jnp.float32)
            if quantized:
                # fused dequant: one multiplier per (page entry, head), a
                # column over the head's tile — the f32 K/V never exists
                # outside this register window
                mine = head_of == h0 + i32(h)
                k = k * jnp.sum(jnp.where(mine, ks_all, _ZERO), axis=2,
                                keepdims=True)
                v = v * jnp.sum(jnp.where(mine, vs_all, _ZERO), axis=2,
                                keepdims=True)
            k, v = k.reshape(bk, hd), v.reshape(bk, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [Tp, bk]
            return jnp.where(valid, s, _NEG), v

        def weights(h, s):
            """The running max and sum of head ``h`` take the block in."""
            m_prev = m_s[h]                       # [Tp, LANE], lanes equal
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)       # [Tp, LANE]
            # masked / padded keys -> 0 (a row with nothing yet: exp(0))
            p = jnp.where(valid, jnp.exp(s - m_new[:, :1]), _ZERO)
            l_s[h] = l_s[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            return m_new, alpha, p

        # static unroll, 2-D MXU dots per head, ``abreast`` heads at a time:
        # a head's scores, weights and context are one chain of dependent
        # products, bound by their latency; the chains of a group are
        # issued phase by phase, for the compiler to overlap
        for g0 in range(0, block_h, abreast):
            heads = range(g0, min(g0 + abreast, block_h))
            sv = [scores(h) for h in heads]
            mp = [weights(h, s) for h, (s, _) in zip(heads, sv)]
            for h, (_, v), (m_new, alpha, p) in zip(heads, sv, mp):
                acc_s[h] = (acc_s[h] * alpha[:, :1]
                            + jax.lax.dot_general(
                                p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
                m_s[h] = m_new

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

    # a bound of 0 (a free slot, a padding row): one empty iteration
    jax.lax.fori_loop(i32(0), n + i32(1), step, i32(0))

    for h in range(block_h):
        l = l_s[h][:, :1]  # rows that saw nothing (query padding): l == 0
        out = jnp.where(l > 0, acc_s[h] / jnp.maximum(l, 1e-30), _ZERO)
        o_ref[0, h] = out.astype(o_ref.dtype)


def _head_blocks(H: int, hd: int):
    """Head-block sizes a ``[page, H*hd]`` row can be cut into: divisors
    of H whose ``bh*hd`` lanes are whole lane tiles, and H itself (the
    whole row), largest first."""
    return [bh for bh in range(H, 0, -1)
            if H % bh == 0 and (bh == H or (bh * hd) % _at.LANE == 0)]


def _heads_a_step(q, k_pool, tables, quantized: bool,
                  tile: Optional[int] = None) -> int:
    """Heads a grid step takes, by rule: the most that fit
    (:func:`_head_blocks`, largest first): the fewest grid steps, each page
    row fetched in the fewest pieces (one, where the whole row fits), the
    validity tile built once for all of them.  What a step keeps resident is
    a TILE's, whatever the width of the call: the pipelined q / out /
    position blocks twice (double-buffered), the two-slot K/V (and scale)
    buffers and the three scratch accumulators once, minor dims padded to
    whole tiles as VMEM holds them."""
    _, H, T, hd = q.shape
    page = k_pool.shape[1]
    ppb = block_pages(page)
    bk, nblk = ppb * page, -(-tables.shape[1] // ppb)
    tile = tile or query_tile(T)
    tq = min(-(-T // _at.SUBLANE) * _at.SUBLANE, tile)
    kv_item = np.dtype(k_pool.dtype).itemsize
    q_item = np.dtype(q.dtype).itemsize

    def lanes(n):
        return -(-n // _at.LANE) * _at.LANE

    # the tiled grid asks the compiler for its own scope of VMEM
    budget = int(_at.VMEM_BUDGET_FRAC
                 * (_TILED_VMEM if T > tile else _at.VMEM_BYTES))
    blocks = _head_blocks(H, hd)
    for bh in blocks:
        piped = (2 * bh * tq * lanes(hd) * q_item      # q + out
                 + tq * _at.LANE * 4                   # positions column
                 + nblk * _at.SUBLANE * lanes(bk) * 4)  # pos_map row
        bufs = 2 * 2 * bk * lanes(bh * hd) * kv_item   # k + v, two slots
        if quantized:
            bufs += 2 * 2 * bk * lanes(H) * 4  # scale planes, all heads
        scratch = bh * tq * (2 * _at.LANE + lanes(hd)) * 4  # m/l/acc
        if 2 * piped + bufs + scratch <= budget:
            return bh
    return blocks[-1]


@functools.partial(jax.jit, static_argnames=("block_h", "sm_scale", "name",
                                             "tile", "abreast"))
def _sweep(q, k_pool, v_pool, tables, pos_map, positions, bound, k_scale,
           v_scale, *, block_h: int, sm_scale: float,
           name: str = "paged_decode", tile: Optional[int] = None,
           abreast: int = _ABREAST):
    B, H, T, hd = q.shape
    P1, page, D = k_pool.shape
    G = tables.shape[1]
    C = G * page
    if D != H * hd or v_pool.shape != k_pool.shape:
        raise InvalidArgumentError(
            f"paged_flash_decode: pool {k_pool.shape}/{v_pool.shape} vs "
            f"q {q.shape}")
    if pos_map.shape != (B, C) or positions.shape != (B, T):
        raise InvalidArgumentError(
            f"paged_flash_decode: pos_map {pos_map.shape} != {(B, C)} or "
            f"positions {positions.shape} != {(B, T)}")
    bh = block_h if block_h in _head_blocks(H, hd) else H
    quantized = k_scale is not None
    ppb = block_pages(page)
    nblk = -(-G // ppb)  # key blocks in a slot's window
    bk = ppb * page

    # pad the verify width to the sublane tile, a wider call to whole query
    # tiles; padded rows sit at position -1, see nothing, finalize to zeros
    # and are sliced away
    Tp = -(-T // _at.SUBLANE) * _at.SUBLANE
    tile = tile or query_tile(T)
    nq = -(-Tp // tile)  # query tiles: the one-tile grid stays what it was
    tiled = nq > 1
    tq = tile if tiled else Tp
    Tp = nq * tq
    qp = q if Tp == T else jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    qpos = jnp.pad(positions.astype(jnp.int32), ((0, 0), (0, Tp - T)),
                   constant_values=-1)[:, :, None]           # [B, Tp, 1]
    # a window that is not whole blocks: the last block's tail is page 0
    # at position -1, like any unmapped entry
    kpos = jnp.pad(pos_map.astype(jnp.int32), ((0, 0), (0, nblk * bk - C)),
                   constant_values=-1).reshape(B, nblk, 1, bk)
    tab = jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, nblk * ppb - G)))
    nb = (jnp.full((B,), nblk, jnp.int32) if bound is None  # pages -> blocks
          else jnp.minimum(-(-bound.astype(jnp.int32) // ppb), nblk))
    if nb.ndim == 2 and not (tiled and nb.shape == (B, nq)):
        raise InvalidArgumentError(
            f"paged_flash_decode: bound {nb.shape} for {nq} query tile(s) "
            f"of {tq} rows")
    if tiled:
        # a slot's bound holds for each of its tiles
        nb = jnp.broadcast_to(nb if nb.ndim == 2 else nb[:, None], (B, nq))
        grid = (B, nq, H // bh)

        def qmap(b, t, h, *_):
            return (b, h, t, _at.I0)

        def pmap(b, t, *_):
            return (b, t, _at.I0)
    else:
        grid = (B, H // bh)

        def qmap(b, h, *_):
            return (b, h, _at.I0, _at.I0)

        def pmap(b, *_):
            return (b, _at.I0, _at.I0)

    def kmap(b, *_):  # a slot's position map, whole, at every step of it
        return (b, _at.I0, _at.I0, _at.I0)

    in_specs = [
        pl.BlockSpec((1, bh, tq, hd), qmap),
        pl.BlockSpec((1, tq, 1), pmap),
        pl.BlockSpec((1, nblk, 1, bk), kmap),
        # the pools as they are stored, left in HBM: the kernel copies
        # the pages it walks, the bh*hd lanes of this head block of each
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [qp, qpos, kpos, k_pool, v_pool]
    scratch = [pltpu.VMEM((2, ppb, page, bh * hd), k_pool.dtype),
               pltpu.VMEM((2, ppb, page, bh * hd), v_pool.dtype)]
    if quantized:
        # a page's scales for ALL heads, its [page, H] plane whole; the
        # kernel picks its heads' columns by lane.  A DMA moves whole lane
        # tiles, so the planes go in padded to one: a copy of the planes
        # a layer (1/hd of the pool's values), the one thing here that
        # is not read as stored
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        Hl = -(-H // _at.LANE) * _at.LANE
        operands += [jnp.pad(s, ((0, 0), (0, 0), (0, Hl - H)))
                     for s in (k_scale, v_scale)]
        scratch += [pltpu.VMEM((2, ppb, page, Hl), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),                # one a buffer slot
        pltpu.VMEM((bh, tq, _at.LANE), jnp.float32),  # running max
        pltpu.VMEM((bh, tq, _at.LANE), jnp.float32),  # running sum
        pltpu.VMEM((bh, tq, hd), jnp.float32),        # out accum
    ]

    kern = functools.partial(_kernel, block_h=bh, window=C,
                             sm_scale=sm_scale, quantized=quantized,
                             tiled=tiled, abreast=abreast)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # the page table and the sweep bounds
            grid=grid,  # the key blocks are the kernel's own loop
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bh, tq, hd), qmap),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
            **({"vmem_limit_bytes": _TILED_VMEM} if tiled else {})),
        interpret=not _device.on_tpu(),
        name=name,
    )(tab, nb, *operands)
    return out[:, :, :T, :]


def _decode_width(q, k_pool, v_pool, tables, pos_map, positions, bound,
                  name="paged_decode"):
    """The decode width, ``q`` ``[B, H, 1, hd]`` over float pages: the
    heads become the query rows of ONE head as wide as a pool row (see
    the module docstring), so a block costs two products, not 2H.  With
    grouped K/V heads (a pool row of ``H_kv * hd`` lanes) row h holds
    ``q_h`` in the lanes of ITS K/V head, ``h // (H / H_kv)``."""
    B, H, _, hd = q.shape
    D = k_pool.shape[2]
    Hkv = D // hd
    Hp = -(-H // _at.SUBLANE) * _at.SUBLANE
    if Hkv == H:
        own = jnp.eye(H, dtype=q.dtype)
    else:  # [H, H_kv]: query head h reads K/V head h // rep
        own = (jnp.arange(H)[:, None] // (H // Hkv)
               == jnp.arange(Hkv)[None, :]).astype(q.dtype)
    # row h: q_h in its K/V head's lanes, zeros elsewhere
    qbd = (q[:, :, 0, None, :] * own[None, :, :, None]).reshape(B, H, D)
    qbd = jnp.pad(qbd, ((0, 0), (0, Hp - H), (0, 0)))[:, None]
    out = _sweep(qbd, k_pool, v_pool, tables, pos_map,
                 jnp.broadcast_to(positions, (B, Hp)), bound, None, None,
                 block_h=1, sm_scale=1.0 / math.sqrt(hd),
                 name=name)                                # [B, 1, Hp, D]
    # row h's own lanes are head h's context; the rest is other heads'
    # values under head h's weights, dropped
    out = out[:, 0, :H].reshape(B, H, Hkv, hd)
    if Hkv == H:
        return jnp.einsum("bhhd->bhd", out)[:, :, None, :]
    return jnp.einsum("bhgd,hg->bhd", out, own)[:, :, None, :]


def _kv_heads(q, k_pool):
    """K/V heads of a pool row for queries of ``q``'s head size."""
    H, hd = q.shape[1], q.shape[3]
    Hkv = k_pool.shape[2] // hd
    if Hkv * hd != k_pool.shape[2] or H % Hkv:
        raise InvalidArgumentError(
            f"paged attention: a pool row of {k_pool.shape[2]} lanes holds "
            f"no whole number of K/V heads of {hd} that {H} query heads "
            f"divide into")
    return Hkv


def _fold_bound(bound, T: int, rep: int):
    """The bounds of the ``rep x T`` query rows of the grouped-head fold
    (``jnp.tile(positions, (1, rep))``) from those of a call's ``T`` rows:
    a row keeps the bound its own positions gave it, a tile of the fold
    takes the largest of its rows'.  A slot's bound ``[B]`` holds for any
    row of it."""
    if bound is None or bound.ndim == 1:
        return bound
    rows = jnp.tile(jnp.repeat(bound, query_tile(T), axis=1)[:, :T], (1, rep))
    tq = query_tile(rep * T)
    nq = -(-rep * T // tq)
    return jnp.pad(rows, ((0, 0), (0, nq * tq - rep * T))).reshape(
        -1, nq, tq).max(axis=2)


def paged_flash_decode(q, k_pool, v_pool, tables, pos_map, positions,
                       bound=None, k_scale=None, v_scale=None, *,
                       block_h: Optional[int] = None,
                       name: str = "paged_decode"):
    """Flash decode over a paged KV pool, page walk in-kernel.

    q: ``[B, H, T, hd]`` query block (T = 1 or the speculative ``1+k``
    verify width, or an admission bucket); k_pool/v_pool: ``[P+1, page,
    H_kv*hd]`` shared page pools in their stored order, a token's K/V heads
    side by side in one row (float, int8 or fp8-e4m3; the last page is the
    write-drop page), ALREADY scattered with this step's K/V; ``H_kv`` is
    read off the row's width and divides ``H`` (grouped heads: query head h
    reads K/V head ``h // (H / H_kv)``; float pools only); tables:
    ``[B, G]`` i32 page-table rows with unmapped entries pre-clipped to
    a valid page (``jnp.maximum(table, 0)`` — their ``pos_map`` is -1);
    pos_map: ``[B, G*page]`` i32, the absolute position each cache entry
    holds (-1: none); positions: ``[B, T]`` i32, the query rows' absolute
    positions (-1: padding) — validity is :func:`key_visible` of the two,
    the gather path's mask; bound: i32 logical pages to walk
    (:func:`sweep_bound` of that mask: ``[B]``, a slot's, or for a call of
    more than :data:`QUERY_TILE` rows ``[B, nq]``, a query tile's; a
    slot's bound is taken for each of its tiles; None walks the whole
    window); k_scale/v_scale: ``[P+1, page, H]`` f32 dequant multipliers
    for quantized pools (both or neither).

    Returns the attention context ``[B, H, T, hd]`` in q's dtype.  The
    grid is ``(B, H / block_h)`` up to one query tile of rows and ``(B,
    nq, H / block_h)`` past it; there is nothing to tune: the tile is
    fixed by the shape and ``block_h`` (heads, so ``block_h*hd`` lanes of
    a page row, per grid step) is the most that fit
    (:func:`_heads_a_step`); pass it explicitly to choose another.  At
    the decode width over float pages the whole row is swept as one
    block-diagonal head (module docstring) unless ``block_h`` asks for
    the per-head form.  ``name`` names the kernel's call in the program
    and the trace (a model whose per-slot key rings are one-page pools
    tells those calls from its page pools').
    """
    if (k_scale is None) != (v_scale is None):
        raise InvalidArgumentError(
            "paged_flash_decode: pass k_scale and v_scale together "
            "(or neither)")
    if q.shape[2] == 1 and k_scale is None and block_h is None:
        _kv_heads(q, k_pool)
        return _decode_width(q, k_pool, v_pool, tables, pos_map, positions,
                             bound, name)
    rep = q.shape[1] // _kv_heads(q, k_pool)
    if rep > 1:
        # the rep query heads of a K/V head are rep x T query rows of it
        if k_scale is not None:
            raise InvalidArgumentError(
                "paged_flash_decode: quantized pools have one scale a "
                "query head; grouped K/V heads are not among them")
        B, H, T, hd = q.shape
        out = paged_flash_decode(
            q.reshape(B, H // rep, rep * T, hd), k_pool, v_pool, tables,
            pos_map, jnp.tile(positions, (1, rep)),
            _fold_bound(bound, T, rep), block_h=block_h, name=name)
        return out.reshape(B, H, T, hd)
    if block_h is None:
        block_h = _heads_a_step(q, k_pool, tables, k_scale is not None)
    return _sweep(q, k_pool, v_pool, tables, pos_map, positions, bound,
                  k_scale, v_scale, block_h=block_h,
                  sm_scale=1.0 / math.sqrt(q.shape[3]), name=name)


def paged_attention(q, k_pool, v_pool, gather_tab, mask, walk=None,
                    k_scale=None, v_scale=None):
    """The attention context ``[B, H, T, hd]`` of ``q`` ``[B, H, T, hd]``
    over pools ALREADY scattered with this call's K/V (stored order ``[P+1,
    page, H*hd]``), by either path of a paged model's ``forward_paged``:

    * ``walk`` given (``(pos_map, positions, bound)``, the TPU hot path):
      :func:`paged_flash_decode` — page-table walk, dequant and online
      softmax in one kernel over the pool in its stored order; the float
      ``[B, H, C, hd]`` view is never materialized;
    * else: each slot's logical view is gathered through its page-table
      row (``gather_tab`` ``[B, G]``, pre-clipped to valid pages) and
      plain masked attention runs over it (``mask`` ``[B, T, C]``, the
      rule of :func:`key_visible`) — the CPU path and the reference the
      kernel is held to."""
    if walk is not None:
        return paged_flash_decode(q, k_pool, v_pool, gather_tab, *walk,
                                  k_scale, v_scale)
    B, H, _, hd = q.shape
    G, page = gather_tab.shape[1], k_pool.shape[1]
    rep = H // _kv_heads(q, k_pool)

    def view(pool, *tail):
        # [P+1, page, *] pages → the slots' logical [B, H, C, *tail]
        t = jnp.take(pool, gather_tab, axis=0)  # [B,G,page,H*...]
        t = t.reshape(B, G * page, H // rep, *tail)
        t = jnp.moveaxis(t, 2, 1)
        # grouped K/V heads: query head h reads K/V head h // rep
        return t if rep == 1 else jnp.repeat(t, rep, axis=1)

    kview, vview = view(k_pool, hd), view(v_pool, hd)
    if k_scale is not None:
        # dequantize the gathered view: one multiplier per (page entry,
        # head), broadcast over hd — drop-page entries carry scale 0 and
        # are masked out below anyway
        kview = (kview.astype(jnp.float32)
                 * view(k_scale)[..., None]).astype(q.dtype)
        vview = (vview.astype(jnp.float32)
                 * view(v_scale)[..., None]).astype(q.dtype)
    scores = jnp.einsum("bhqd,bhcd->bhqc", q, kview) / math.sqrt(hd)
    scores = jnp.where(mask[:, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqc,bhcd->bhqd", probs, vview)


def paged_flash_eligible(head_dim: Optional[int] = None,
                         page_size: Optional[int] = None,
                         backend: Optional[str] = None) -> bool:
    """Should ``forward_paged`` dispatch to the Pallas kernel?  Mirrors
    ``fused_epilogues_eligible``: a real TPU backend (interpret mode
    loses; the gather path is the bit-identical CPU reference), Mosaic-
    friendly head/page dims, and a one-device mesh
    (``autotune.mesh_admits_kernels``).  ``backend`` overrides the backend
    check so CI on CPU can assert the would-dispatch-on-TPU decision
    (tools/gen_smoke.py / quant_smoke.py)."""
    if not flag("paged_flash"):
        return False
    if not (backend == "tpu" if backend else _device.on_tpu()):
        return False
    if head_dim is not None and head_dim % _at.SUBLANE != 0:
        return False
    if page_size is not None and page_size % _at.SUBLANE != 0:
        return False
    return _at.mesh_admits_kernels()
