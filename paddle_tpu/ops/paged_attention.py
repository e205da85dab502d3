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
  take in and hand back, so nothing re-orders the pool between them;
* grid ``(B, H/bh, G)`` with the page dim innermost/sequential — each
  grid step streams ONE physical page of K/V, the ``bh*hd`` lanes of
  ``bh`` heads of it, straight from the pool into VMEM, located by a
  scalar-prefetched i32 page table (``PrefetchScalarGridSpec`` — index
  maps stay SMEM lookups, which Mosaic lowers directly; the
  splash-attention pattern shared with flash_attention.py's triangle
  grid).  Inside the block a head is a STATIC ``hd``-lane slice of the
  ``[page, bh*hd]`` tile: the same per-head products as a per-head
  block, every head, every key;
* at the decode width (one query token a slot, float pages) the heads
  ARE the query rows: the query is laid out block-diagonal, ``[H, H*hd]``
  with row h holding ``q_h`` in head h's own lanes and zeros elsewhere,
  and swept as ONE head of width ``H*hd`` — one ``[H, H*hd] x [H*hd,
  page]`` product gives every head's scores (the other heads' lanes add
  exact zeros), one ``[H, page] x [page, H*hd]`` product their contexts,
  of which row h keeps its own lanes.  Same kernel body, same products
  in the same f32 accumulation; 2 products a page instead of 2H;
* flash-style online softmax: running max / normalizer / output
  accumulator ride VMEM scratch across the sequential page sweep, so
  attention memory is O(page), never O(C);
* quantized pools (int8 / fp8-e4m3) dequantize PER PAGE inside the
  inner loop — ``k_f32 = k_q * k_scale`` on the [page, hd] slice that
  is already in VMEM.  A float KV view is never materialized in HBM;
  the pool bytes crossing the memory bus per step are the quantized
  bytes (the whole point of a quantized pool);
* masking is the host-computed validity mask the gather path already
  uses (causality, ragged page counts, the write-drop page, and the
  speculative ``1+k`` verify width all fold into it — the pool is
  scattered BEFORE attention, so intra-step draft causality is just
  ``kp <= qp``).  Unmapped table entries are pre-clipped to page 0 and
  carry mask 0; fully-masked rows (query padding) emit zeros.

Equivalence: same math as the gather-then-attend reference modulo
float reassociation (online softmax accumulates in f32); the reference
path stays the bit-identical CPU/fallback — ``paged_flash_eligible``
gates dispatch exactly like ``fused_epilogues_eligible`` does for the
other epilogues (TPU backend, one-device mesh, aligned dims).

Tile parameters resolve through ``ops.autotune`` (kernel name
``"paged_decode"``): ``block_h`` — heads per grid step, i.e. how many
lanes of a page one step fetches — trades grid steps (and, below H,
H/bh fetches of each page row) against VMEM residency, which the query
width sets: a verify width takes all heads, so a page is fetched once;
a 768-token admission block takes two (the decode width over float
pages has nothing to tune, see above).  Candidates are the
divisors of H whose ``bh*hd`` lanes are whole lane tiles (or all of
``H*hd``) and that fit the VMEM budget; per-candidate equivalence is
tested in tests/test_paged_attention.py.
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

__all__ = ["paged_flash_decode", "paged_flash_eligible"]

# mask fill; exp(_NEG - m) underflows to exactly 0.0 in f32.  Typed f32:
# under the package's global x64 a bare Python float reaches ``jnp.where``
# as an f64 scalar, which Mosaic cannot legalize.
_NEG = np.float32(-1e30)
_ZERO = np.float32(0.0)


def _kernel(tab_ref, q_ref, k_ref, v_ref, mask_ref, *refs,
            block_h: int, sm_scale: float, quantized: bool):
    """One (slot, head-block, page) step of the online-softmax sweep."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_s, l_s, acc_s = refs
    g = pl.program_id(2)
    g_steps = pl.num_programs(2)
    hd = q_ref.shape[-1]

    @pl.when(g == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    mask = mask_ref[0, 0]  # [Tp, page] 0/1 f32
    if quantized:
        # a page's scales for all heads, [page, H], indexed like the
        # values; this block's heads are picked out by lane below
        ks_all, vs_all = ks_ref[0], vs_ref[0]
        head_of = jax.lax.broadcasted_iota(jnp.int32, ks_all.shape, 1)
        h0 = pl.program_id(1) * block_h  # first head of this block (i32)
    for h in range(block_h):  # static unroll: 2-D MXU dots per head
        lanes = slice(h * hd, (h + 1) * hd)  # this head's lanes of a row
        q = q_ref[0, h].astype(jnp.float32)          # [Tp, hd]
        k = k_ref[0, :, lanes].astype(jnp.float32)   # [page, hd]
        v = v_ref[0, :, lanes].astype(jnp.float32)
        if quantized:
            # fused dequant: one multiplier per (page entry, head), a
            # [page, 1] column over the head's [page, hd] tile — the f32
            # K/V never exists outside this register window
            mine = head_of == h0 + h
            k = k * jnp.sum(jnp.where(mine, ks_all, _ZERO), axis=1,
                            keepdims=True)
            v = v * jnp.sum(jnp.where(mine, vs_all, _ZERO), axis=1,
                            keepdims=True)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [Tp, page]
        s = jnp.where(mask > 0, s, _NEG)

        m_prev = m_s[h]                       # [Tp, LANE], lanes equal
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)       # [Tp, LANE]
        p = jnp.exp(s - m_new[:, :1]) * mask  # masked/padded entries -> 0
        l_s[h] = l_s[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[h] = (acc_s[h] * alpha[:, :1]
                    + jax.lax.dot_general(
                        p, v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
        m_s[h] = m_new

    @pl.when(g == g_steps - 1)
    def _flush():
        for h in range(block_h):
            l = l_s[h][:, :1]  # fully-masked rows (query padding): l == 0
            out = jnp.where(l > 0, acc_s[h] / jnp.maximum(l, 1e-30), _ZERO)
            o_ref[0, h] = out.astype(o_ref.dtype)


def _head_blocks(H: int, hd: int):
    """Head-block sizes a ``[page, H*hd]`` row can be cut into: divisors
    of H whose ``bh*hd`` lanes are whole lane tiles, and H itself (the
    whole row), largest first."""
    return [bh for bh in range(H, 0, -1)
            if H % bh == 0 and (bh == H or (bh * hd) % _at.LANE == 0)]


def _space(q, k_pool, v_pool, tables, mask, k_scale, v_scale):
    """Candidate head-block sizes (:func:`_head_blocks`) whose resident
    blocks fit the VMEM budget: the pipelined q/out/k/v/mask/scale blocks
    twice (double-buffered), the three scratch accumulators once, minor
    dims padded to whole lane tiles as VMEM holds them."""
    B, H, T, hd = q.shape
    page = k_pool.shape[1]
    Tp = -(-T // _at.SUBLANE) * _at.SUBLANE
    kv_item = np.dtype(k_pool.dtype).itemsize
    q_item = np.dtype(q.dtype).itemsize

    def lanes(n):
        return -(-n // _at.LANE) * _at.LANE

    out = []
    for bh in _head_blocks(H, hd):
        blocks = (2 * bh * Tp * lanes(hd) * q_item          # q + out
                  + 2 * page * lanes(bh * hd) * kv_item     # k + v page
                  + Tp * lanes(page) * 4)                   # mask
        if k_scale is not None:
            blocks += 2 * page * lanes(H) * 4  # scale planes, all heads
        scratch = bh * Tp * (2 * _at.LANE + lanes(hd)) * 4  # m/l/acc
        if _at.vmem_fits(2 * blocks + scratch):
            out.append({"block_h": bh})
    return out


def _heuristic(q, k_pool, v_pool, tables, mask, k_scale, v_scale):
    # the most heads that fit: the fewest grid steps, and each page row
    # fetched the fewest times (once, at the decode width)
    fits = _space(q, k_pool, v_pool, tables, mask, k_scale, v_scale)
    H, hd = q.shape[1], q.shape[3]
    return fits[0] if fits else {"block_h": _head_blocks(H, hd)[-1]}


@functools.partial(jax.jit, static_argnames=("block_h", "sm_scale"))
def _sweep(q, k_pool, v_pool, tables, mask, k_scale, v_scale, *,
           block_h: int, sm_scale: float):
    B, H, T, hd = q.shape
    P1, page, D = k_pool.shape
    G = tables.shape[1]
    if D != H * hd or v_pool.shape != k_pool.shape:
        raise InvalidArgumentError(
            f"paged_flash_decode: pool {k_pool.shape}/{v_pool.shape} vs "
            f"q {q.shape}")
    if mask.shape != (B, T, G * page):
        raise InvalidArgumentError(
            f"paged_flash_decode: mask {mask.shape} != {(B, T, G * page)}")
    bh = block_h if block_h in _head_blocks(H, hd) else H
    quantized = k_scale is not None

    # pad the verify width to the sublane tile; padded rows carry mask 0
    # everywhere, so they finalize to zeros and are sliced away below
    Tp = -(-T // _at.SUBLANE) * _at.SUBLANE
    qp = q if Tp == T else jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    maskf = mask.astype(jnp.float32)
    if Tp != T:
        maskf = jnp.pad(maskf, ((0, 0), (0, Tp - T), (0, 0)))
    # page-major so one page's [Tp, page] mask tile is the block's two
    # minor dims IN FULL — Mosaic tiles the last two dims (8, 128) and a
    # (1, page) slice of a [G, page] minor pair is not a legal block
    maskf = maskf.reshape(B, Tp, G, page).transpose(0, 2, 1, 3)
    tab = tables.astype(jnp.int32)  # [B, G] SMEM table for the index maps

    def qmap(b, h, g, t):
        return (b, h, _at.I0, _at.I0)

    def kvmap(b, h, g, t):
        return (t[b, g], _at.I0, h)

    def scmap(b, h, g, t):
        return (t[b, g], _at.I0, _at.I0)

    def mmap(b, h, g, t):
        return (b, g, _at.I0, _at.I0)

    in_specs = [
        pl.BlockSpec((1, bh, Tp, hd), qmap),
        # one page of the pool as it is stored: `page` token rows, the
        # bh*hd lanes of this head block (whole lane tiles, or the row)
        pl.BlockSpec((1, page, bh * hd), kvmap),
        pl.BlockSpec((1, page, bh * hd), kvmap),
        pl.BlockSpec((1, 1, Tp, page), mmap),
    ]
    operands = [qp, k_pool, v_pool, maskf]
    if quantized:
        # a page's scales for ALL heads: (page, H) is the operand's whole
        # minor pair (a bh-lane slice of it is not (8, 128)-tileable); the
        # kernel picks its heads' columns by lane
        in_specs += [pl.BlockSpec((1, page, H), scmap),
                     pl.BlockSpec((1, page, H), scmap)]
        operands += [k_scale, v_scale]

    kern = functools.partial(_kernel, block_h=bh, sm_scale=sm_scale,
                             quantized=quantized)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // bh, G),  # page dim innermost: sequential sweep
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bh, Tp, hd), qmap),
            scratch_shapes=[
                pltpu.VMEM((bh, Tp, _at.LANE), jnp.float32),  # running max
                pltpu.VMEM((bh, Tp, _at.LANE), jnp.float32),  # running sum
                pltpu.VMEM((bh, Tp, hd), jnp.float32),        # out accum
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not _device.on_tpu(),
        name="paged_decode",
    )(tab, *operands)
    return out[:, :, :T, :]


@_at.autotune("paged_decode", params=("block_h",), space=_space,
              heuristic=_heuristic)
def _paged_decode(q, k_pool, v_pool, tables, mask, k_scale, v_scale, *,
                  block_h: int):
    return _sweep(q, k_pool, v_pool, tables, mask, k_scale, v_scale,
                  block_h=block_h, sm_scale=1.0 / math.sqrt(q.shape[3]))


def _decode_width(q, k_pool, v_pool, tables, mask):
    """The decode width, ``q`` ``[B, H, 1, hd]`` over float pages: the
    heads become the query rows of ONE head as wide as a pool row (see
    the module docstring), so a page costs two products, not 2H."""
    B, H, _, hd = q.shape
    D = H * hd
    Hp = -(-H // _at.SUBLANE) * _at.SUBLANE
    # row h: q_h in lanes [h*hd, (h+1)*hd), zeros elsewhere
    qbd = (q[:, :, 0, None, :]
           * jnp.eye(H, dtype=q.dtype)[None, :, :, None]).reshape(B, H, D)
    qbd = jnp.pad(qbd, ((0, 0), (0, Hp - H), (0, 0)))[:, None]
    out = _sweep(qbd, k_pool, v_pool, tables,
                 jnp.broadcast_to(mask, (B, Hp, mask.shape[2])), None, None,
                 block_h=1, sm_scale=1.0 / math.sqrt(hd))  # [B, 1, Hp, D]
    # row h's own lanes are head h's context; the rest is other heads'
    # values under head h's weights, dropped
    out = out[:, 0, :H].reshape(B, H, H, hd)
    return jnp.einsum("bhhd->bhd", out)[:, :, None, :]


def paged_flash_decode(q, k_pool, v_pool, tables, mask,
                       k_scale=None, v_scale=None, *,
                       block_h: Optional[int] = None):
    """Flash decode over a paged KV pool, page walk in-kernel.

    q: ``[B, H, T, hd]`` query block (T = 1 or the speculative ``1+k``
    verify width, or an admission bucket); k_pool/v_pool: ``[P+1, page,
    H*hd]`` shared page pools in their stored order, a token's heads side
    by side in one row (float, int8 or fp8-e4m3; the last page is the
    write-drop page), ALREADY scattered with this step's K/V; tables:
    ``[B, G]`` i32 page-table rows with unmapped entries pre-clipped to
    a valid page (``jnp.maximum(table, 0)`` — their mask is 0); mask:
    ``[B, T, G*page]`` bool validity, identical to the gather path's;
    k_scale/v_scale: ``[P+1, page, H]`` f32 dequant multipliers for
    quantized pools (both or neither).

    Returns the attention context ``[B, H, T, hd]`` in q's dtype.
    ``block_h`` (heads, so ``block_h*hd`` lanes of a page row, per grid
    step) defaults to the autotuner; pass it explicitly to bypass tuning.
    At the decode width over float pages there is nothing to tune: the
    whole row is swept as one block-diagonal head (module docstring)
    unless ``block_h`` asks for the per-head form.
    """
    if (k_scale is None) != (v_scale is None):
        raise InvalidArgumentError(
            "paged_flash_decode: pass k_scale and v_scale together "
            "(or neither)")
    if q.shape[2] == 1 and k_scale is None and block_h is None:
        return _decode_width(q, k_pool, v_pool, tables, mask)
    return _paged_decode(q, k_pool, v_pool, tables, mask, k_scale, v_scale,
                         block_h=block_h)


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
