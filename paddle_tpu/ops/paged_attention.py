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

* grid ``(B, H/bh, G)`` with the page dim innermost/sequential — each
  grid step streams ONE physical page of K/V for ``bh`` heads straight
  from the pool into VMEM, located by a scalar-prefetched i32 page
  table (``PrefetchScalarGridSpec`` — index maps stay SMEM lookups,
  which Mosaic lowers directly; the splash-attention pattern shared
  with flash_attention.py's triangle grid);
* flash-style online softmax: running max / normalizer / output
  accumulator ride VMEM scratch across the sequential page sweep, so
  attention memory is O(page), never O(C);
* quantized pools (int8 / fp8-e4m3) dequantize PER PAGE inside the
  inner loop — ``k_f32 = k_q * k_scale`` on the [page, hd] block that
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
``"paged_decode"``): ``block_h`` — heads per grid step — trades grid
overhead against VMEM residency; candidates are the divisors of H
that fit the VMEM budget, per-candidate equivalence is tested in
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

    @pl.when(g == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    mask = mask_ref[0, 0]  # [Tp, page] 0/1 f32
    h0 = pl.program_id(1) * block_h  # first head of this block (i32)
    for h in range(block_h):  # static unroll: 2-D MXU dots per head
        q = q_ref[0, h].astype(jnp.float32)   # [Tp, hd]
        k = k_ref[0, h].astype(jnp.float32)   # [page, hd]
        v = v_ref[0, h].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [Tp, page]
        if quantized:
            # fused dequant: one multiplier per (page entry, head).  The
            # page axis is the LANE axis of the score tile, so the K
            # scales fold in as a [1, page] row on s and the V scales as
            # one on p — q·(k·ks)ᵀ = (q·kᵀ)·ks and p·(v·vs) = (p·vs)·v —
            # and the f32 K/V never exists outside this register window
            s = s * ks_ref[0, pl.ds(h0 + h, 1), :]
        s = jnp.where(mask > 0, s, _NEG)

        m_prev = m_s[h]                       # [Tp, LANE], lanes equal
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)       # [Tp, LANE]
        p = jnp.exp(s - m_new[:, :1]) * mask  # masked/padded entries -> 0
        l_s[h] = l_s[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, pl.ds(h0 + h, 1), :]
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


def _space(q, k_pool, v_pool, tables, mask, k_scale, v_scale):
    """Candidate head-block sizes: divisors of H whose resident blocks
    (q/k/v/mask/scale blocks + the three scratch accumulators) fit the
    VMEM budget."""
    B, H, T, hd = q.shape
    page = k_pool.shape[2]
    Tp = -(-T // _at.SUBLANE) * _at.SUBLANE
    kv_item = np.dtype(k_pool.dtype).itemsize
    q_item = np.dtype(q.dtype).itemsize
    out = []
    for bh in (1, 2, 4, 8, 16):
        if bh > H or H % bh:
            continue
        resident = (bh * Tp * hd * (q_item + 4)      # q block + out block
                    + 2 * bh * page * hd * kv_item   # k/v page blocks
                    + Tp * page * 4                  # mask block
                    + bh * Tp * (2 * _at.LANE + hd) * 4)  # m/l/acc scratch
        if k_scale is not None:
            resident += 2 * H * page * 4  # scale rows ride whole-H blocks
        if _at.vmem_fits(resident):
            out.append({"block_h": bh})
    return out


def _heuristic(q, k_pool, v_pool, tables, mask, k_scale, v_scale):
    # one head per grid step — the smallest block is always lowerable
    # and is the pre-autotuner default every backend agrees on
    return {"block_h": 1}


@_at.autotune("paged_decode", params=("block_h",), space=_space,
              heuristic=_heuristic)
@functools.partial(jax.jit, static_argnames=("block_h",))
def _paged_decode(q, k_pool, v_pool, tables, mask, k_scale, v_scale, *,
                  block_h: int):
    B, H, T, hd = q.shape
    P1, Hk, page, hdk = k_pool.shape
    G = tables.shape[1]
    if (Hk, hdk) != (H, hd) or v_pool.shape != k_pool.shape:
        raise InvalidArgumentError(
            f"paged_flash_decode: pool {k_pool.shape}/{v_pool.shape} vs "
            f"q {q.shape}")
    if mask.shape != (B, T, G * page):
        raise InvalidArgumentError(
            f"paged_flash_decode: mask {mask.shape} != {(B, T, G * page)}")
    bh = block_h if H % block_h == 0 else 1
    quantized = k_scale is not None
    sm_scale = 1.0 / math.sqrt(hd)

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
        return (t[b, g], h, _at.I0, _at.I0)

    def scmap(b, h, g, t):
        return (t[b, g], _at.I0, _at.I0)

    def mmap(b, h, g, t):
        return (b, g, _at.I0, _at.I0)

    in_specs = [
        pl.BlockSpec((1, bh, Tp, hd), qmap),
        pl.BlockSpec((1, bh, page, hd), kvmap),
        pl.BlockSpec((1, bh, page, hd), kvmap),
        pl.BlockSpec((1, 1, Tp, page), mmap),
    ]
    operands = [qp, k_pool, v_pool, maskf]
    if quantized:
        # a page's scales for ALL heads: (H, page) is the operand's whole
        # minor pair (a bh-row slice of it is not (8, 128)-tileable); the
        # kernel picks its heads' rows by dynamic sublane index
        in_specs += [pl.BlockSpec((1, H, page), scmap),
                     pl.BlockSpec((1, H, page), scmap)]
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
    )(tab, *operands)
    return out[:, :, :T, :]


def paged_flash_decode(q, k_pool, v_pool, tables, mask,
                       k_scale=None, v_scale=None, *,
                       block_h: Optional[int] = None):
    """Flash decode over a paged KV pool, page walk in-kernel.

    q: ``[B, H, T, hd]`` query block (T = 1 or the speculative ``1+k``
    verify width); k_pool/v_pool: ``[P+1, H, page, hd]`` shared page
    pools (float, int8 or fp8-e4m3; the last page is the write-drop
    page), ALREADY scattered with this step's K/V; tables: ``[B, G]``
    i32 page-table rows with unmapped entries pre-clipped to a valid
    page (``jnp.maximum(table, 0)`` — their mask is 0); mask:
    ``[B, T, G*page]`` bool validity, identical to the gather path's;
    k_scale/v_scale: ``[P+1, H, page]`` f32 dequant multipliers for
    quantized pools (both or neither).

    Returns the attention context ``[B, H, T, hd]`` in q's dtype.
    ``block_h`` defaults to the autotuner; pass it explicitly to bypass
    tuning.
    """
    if (k_scale is None) != (v_scale is None):
        raise InvalidArgumentError(
            "paged_flash_decode: pass k_scale and v_scale together "
            "(or neither)")
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
