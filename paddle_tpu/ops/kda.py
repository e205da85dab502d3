"""Kimi Delta Attention — the gated delta rule (``ops/gated_delta.py``) with
ONE DECAY A KEY CHANNEL (Kimi Linear technical report, arXiv:2510.26692).

Per head, with keys of width ``dk``, values of width ``dv`` and a float32
state ``S`` in ``R^{dk x dv}``:

    S' = Diag(e^{g_t}) S_{t-1},   g_t in R^{dk}, <= 0
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;   o_t = S_t^T q_t

With every channel of ``g_t`` equal this is ``gated_delta``'s rule.  A token
with ``g = 0`` and ``beta = 0`` is the identity on the state: padding.

Three routes, as ``gated_delta.py`` has them:

* :func:`kda_recurrent` — the recurrence as written: the oracle;
* :func:`kda_chunk` — a whole prompt from the zero state in chunks of 64.
  With ``b`` the running sum of ``g`` inside the chunk (a vector a token)
  the WY operands are ``A_ij = beta_i sum_c k_ic k_jc e^{b_ic - b_jc}``,
  ``T = (I + A)^{-1}``, ``W = T (beta k e^b)``, ``U = T (beta v)`` and the
  in-chunk scores ``P_ij = sum_c q_ic k_jc e^{b_ic - b_jc}``.  The decay no
  longer factors out of the sum, and the naive factoring ``(k e^b)(k
  e^{-b})^T`` overflows float32 once a channel's ``b`` passes -88 inside a
  chunk, so NO factor ``e^{-b}`` is ever formed: the chunk is cut into
  sub-blocks of 16 tokens, a diagonal sub-block is summed from the pairwise
  differences ``e^{b_i - b_j}`` (``i >= j``: all <= 1), and a sub-block
  below the diagonal is one matmul of ``k_i e^{b_i - b_r}`` with ``k_j
  e^{b_r - b_j}``, ``r`` the first token of the row sub-block (``j < r <=
  i``: both factors <= 1).  Those are batched over all chunks (XLA); the
  walk over a row's chunks with the state resident in VMEM is the Pallas
  kernel ``kda_chunk``.  The state's hand-over between chunks decays
  ROW-wise by ``e^{b_C}``; the kernel keeps the state transposed, ``[dv,
  dk]``, so that decay is a row broadcast over sublanes;
* :func:`kda_step` — one token a slot against the slots' stored states, in
  place: the Pallas kernel ``kda_step``.  Decay, keys and queries reach it
  as ROWS ``[heads, dk]`` (lane-dense in HBM: a ``[dk, 1]`` column is a
  128-lane tile a value there) and are turned into columns by ONE 128 x
  128 transposition a grid step.

Off the TPU (and on a mesh of several devices) both run their ``jnp``
forms.  Nothing here is searched: the heads a grid step takes are a rule of
the shape (:func:`step_heads`, :func:`walk_heads`), from the table
``tools/gated_delta_chip.py --config kimi_linear_serve`` timed on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from . import autotune as _at
from .gated_delta import (_BASE, _F32, _HI, CHUNK, _unit_lower_inverse,
                          gated_delta_eligible)

__all__ = ["kda_recurrent", "kda_chunk", "kda_step", "step_heads",
           "walk_heads"]

_NEG_INF = -jnp.inf


# -- the recurrence as written ------------------------------------------------
def _token(S, q, k, v, g, beta):
    """One token of every (row, head): ``S`` ``[..., dk, dv]``, ``g``
    ``[..., dk]``."""
    Sd = S * jnp.exp(g)[..., None]
    pred = jnp.einsum("...k,...kv->...v", k, Sd, precision=_HI)
    S = Sd + k[..., :, None] * (beta[..., None] * (v - pred))[..., None, :]
    return S, jnp.einsum("...k,...kv->...v", q, S, precision=_HI)


def kda_recurrent(q, k, v, g, beta, state=None):
    """``q``, ``k``, ``g`` ``[B, T, H, dk]``, ``v`` ``[B, T, H, dv]``,
    ``beta`` ``[B, T, H]``; ``state`` ``[B, H, dk, dv]`` (zero when None).
    Returns float32 ``o`` ``[B, T, H, dv]`` and the state after token T."""
    q, k, v, g, beta = (jnp.asarray(t, _F32) for t in (q, k, v, g, beta))
    B, _, H, dk = q.shape
    if state is None:
        state = jnp.zeros((B, H, dk, v.shape[-1]), _F32)

    def step(S, x):
        S, o = _token(S, *x)
        return S, o

    S, o = jax.lax.scan(step, jnp.asarray(state, _F32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


# -- a prompt in chunks --------------------------------------------------------
def _decayed_products(rows, k, b):
    """``X_ij = sum_c rows_ic k_jc e^{b_ic - b_jc}`` for ``i >= j`` (0
    above the diagonal) with no exponent above 0 formed (module docstring).
    ``rows`` is a tuple of R row operands ``[..., C, dk]`` that share the
    keys and the decays: one ``X`` ``[..., C, C]`` each."""
    chunk, base = CHUNK, _BASE
    nb, R = chunk // base, len(rows)
    lead = k.shape[:-2]

    def blocks(t):     # [..., C, dk] -> [..., nb, base, dk]
        return t.reshape(*lead, nb, base, t.shape[-1])

    # the R operands side by side along the ROW axis of a sub-block, so
    # that every pairwise decay below has one reader and is summed where it
    # is made (shared between two sums it would be stored: 1 GB for a
    # 4096-token row of 32 heads)
    rb = jnp.concatenate([blocks(r) for r in rows], axis=-2)
    kb, bb = blocks(k), blocks(b)
    br = jnp.concatenate([bb] * R, axis=-2)            # [..., nb, R base, dk]
    i = np.arange(base)
    low = np.tile(i[:, None] >= i[None, :], (R, 1))[..., None]
    # the diagonal sub-blocks: the pairwise differences themselves, masked
    # BEFORE the exponential (above the diagonal they are positive)
    diag = jnp.sum(rb[..., :, None, :] * kb[..., None, :, :] * jnp.exp(
        jnp.where(low, br[..., :, None, :] - bb[..., None, :, :], _NEG_INF)),
        axis=-1)                                       # [..., nb, R base, base]
    # below them: relative to the row sub-block's first token r.  Columns
    # at or past r get zero weight, so block row s is strictly block-lower
    # (taking only the sub-blocks below the diagonal, 96 of these 256
    # column rows, was timed on the chip: the slicing costs twice what it
    # saves)
    anchor = bb[..., :1, :]                            # b_r  [..., nb, 1, dk]
    first = np.arange(nb)[:, None] * base              # r of block row s
    before = (np.arange(chunk)[None, :] < first)[..., None]   # [nb, C, 1]
    cols = k[..., None, :, :] * jnp.exp(jnp.where(
        before, anchor - b[..., None, :, :], _NEG_INF))      # [..., nb, C, dk]
    off = jnp.einsum("...sid,...sjd->...sij", rb * jnp.exp(br - anchor),
                     cols, precision=_HI)              # [..., nb, R base, C]
    # each diagonal sub-block dropped into its place on the block diagonal
    eye = jnp.eye(nb, dtype=_F32)[:, None, :, None]            # [s, 1, t, 1]
    full = off.reshape(*lead, nb, R * base, nb, base) + (
        diag[..., :, :, None, :] * eye)
    return [full[..., n * base:(n + 1) * base, :, :].reshape(
        *lead, chunk, chunk) for n in range(R)]


def chunk_operands(q, k, v, g, beta):
    """What the walk over chunks reads, every chunk at once: ``qg = q e^b``,
    ``kd = k e^{b_C - b}``, ``W`` (all ``[B, H, N, C, dk]``), ``U`` ``[B, H,
    N, C, dv]``, the causal in-chunk scores ``P`` ``[B, H, N, C, C]`` and
    the chunk's whole decay ``e^{b_C}`` ``[B, H, N, 1, dk]``, a row a
    chunk.  Float32; ``T`` must be whole chunks."""
    B, T = q.shape[:2]
    chunk, N = CHUNK, T // CHUNK

    def split(t):      # [B, T, H, ...] -> [B, H, N, C, ...]
        return jnp.moveaxis(jnp.asarray(t, _F32).reshape(
            B, N, chunk, *t.shape[2:]), 3, 1)

    q, k, v, g, beta = map(split, (q, k, v, g, beta))
    # the running sum of g inside a chunk as one product with the lower
    # triangle of ones (a cumsum is a slow window reduction on the TPU)
    b = jnp.matmul(jnp.tril(jnp.ones((chunk, chunk), _F32)), g,
                   precision=_HI)                           # [B, H, N, C, dk]
    kb = k * beta[..., None]
    A, P = _decayed_products((kb, q), k, b)
    i = np.arange(chunk)
    A = jnp.where(i[:, None] > i[None, :], A, 0.0)
    Tm = _unit_lower_inverse(A)
    eb = jnp.exp(b)
    W = jnp.matmul(Tm, kb * eb, precision=_HI)
    U = jnp.matmul(Tm, v * beta[..., None], precision=_HI)
    last = b[..., -1:, :]
    return q * eb, k * jnp.exp(last - b), W, U, P, jnp.exp(last)


def _walk_jnp(qg, kd, W, U, P, dl):
    """The walk over chunks in ``jnp``: the CPU path and the kernel's
    oracle.  Returns ``o`` ``[B, H, N, C, dv]`` and the final state ``[B,
    H, dk, dv]``."""
    B, H, _, _, dk = qg.shape

    def step(S, x):
        qg, kd, W, U, P, dl = x
        vn = U - jnp.matmul(W, S, precision=_HI)
        o = (jnp.matmul(qg, S, precision=_HI)
             + jnp.matmul(P, vn, precision=_HI))
        return (jnp.swapaxes(dl, -1, -2) * S
                + jnp.einsum("...ck,...cv->...kv", kd, vn, precision=_HI)), o

    S, o = jax.lax.scan(step, jnp.zeros((B, H, dk, U.shape[-1]), _F32), tuple(
        jnp.moveaxis(t, 2, 0) for t in (qg, kd, W, U, P, dl)))
    return jnp.moveaxis(o, 0, 2), S


def _walk_kernel(qg_ref, kd_ref, w_ref, u_ref, p_ref, dl_ref, o_ref, st_ref,
                 *, block_h):
    """One chunk of ``block_h`` heads of one row.  ``st_ref`` (the final
    state's block, TRANSPOSED: ``[dv, dk]``) keeps its index over the chunk
    axis, so it IS the resident state: zeroed at the row's first chunk,
    written back after its last."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    def dot(x, y, dims):
        return jax.lax.dot_general(x, y, (dims, ((), ())),
                                   preferred_element_type=_F32, precision=_HI)

    nn, nt, tn = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
    for j in range(block_h):
        St = st_ref[0, j]                                     # [dv, dk]
        vn = u_ref[0, j, 0] - dot(w_ref[0, j, 0], St, nt)     # [C, dv]
        o_ref[0, j, 0] = (dot(qg_ref[0, j, 0], St, nt)
                          + dot(p_ref[0, j, 0], vn, nn))
        # the row decay is a [1, dk] row over the transposed state's lanes
        st_ref[0, j] = dl_ref[0, j, 0] * St + dot(vn, kd_ref[0, j, 0], tn)


def walk_heads(H: int) -> int:
    """Heads a grid step of ``kda_chunk`` walks: by the chip's table
    (``PERF.md`` section 6, PR 44: a one-row call of 32 heads of 128 x 128
    at 1536 / 4096 tokens 0.62 / 1.50 ms at one head, 0.44 / 1.06 at 8,
    0.45 / 1.03 at 16, 0.46 / 1.02 at 32) the most of 1, 2, 4, 8 that
    divide the heads: past 8 the table is flat inside 3 %."""
    return max(h for h in (1, 2, 4, 8) if H % h == 0)


def _walk_pallas(qg, kd, W, U, P, dl, *, block_h=None):
    B, H, N, C, dk = qg.shape
    dv = U.shape[-1]
    block_h = block_h or walk_heads(H)
    z = _at.I0

    def blk(*tail):
        return pl.BlockSpec((1, block_h, 1) + tail,
                            lambda b, h, n: (b, h, n, z, z))

    o, St = pl.pallas_call(
        functools.partial(_walk_kernel, block_h=block_h),
        name="kda_chunk",
        interpret=not _device.on_tpu(),
        grid=(B, H // block_h, N),
        in_specs=[blk(C, dk), blk(C, dk), blk(C, dk), blk(C, dv), blk(C, C),
                  blk(1, dk)],
        out_specs=[blk(C, dv),
                   pl.BlockSpec((1, block_h, dv, dk),
                                lambda b, h, n: (b, h, z, z))],
        out_shape=[jax.ShapeDtypeStruct((B, H, N, C, dv), _F32),
                   jax.ShapeDtypeStruct((B, H, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qg, kd, W, U, P, dl)
    return o, jnp.swapaxes(St, -1, -2)


def kda_chunk(q, k, v, g, beta):
    """A prompt from the zero state.  Shapes as :func:`kda_recurrent`;
    ``T`` is padded to whole chunks with identity tokens.  Returns float32
    ``o`` ``[B, T, H, dv]`` and the state after the last token, ``[B, H,
    dk, dv]``."""
    B, T, H = v.shape[:3]
    pad = -T % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad))
                                    + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    ops = chunk_operands(q, k, v, g, beta)
    walk = _walk_pallas if gated_delta_eligible() else _walk_jnp
    o, S = walk(*ops)
    o = jnp.moveaxis(o, 1, 3).reshape(B, T + pad, H, -1)
    return o[:, :T], S


# -- one token a slot ----------------------------------------------------------
def _step_jnp(q, k, v, g, beta, state):
    B = q.shape[0]
    S, o = _token(state[:B], q, k, v, g, beta)
    return o, jax.lax.dynamic_update_slice(state, S, (0, 0, 0, 0))


def _step_kernel(a_ref, k_ref, q_ref, v_ref, beta_ref, s_ref, o_ref, so_ref,
                 *, block_h):
    """``block_h`` heads of one slot.  The decays, keys and queries arrive
    as rows; stacked to 128 rows and transposed once they are the columns
    ``[dk, 1]`` whose products with the state are broadcasts over lanes and
    sums over sublanes.  Values, ``beta`` (broadcast over its row) and the
    output stay rows ``[1, dv]``."""
    dk = a_ref.shape[2]
    rows = jnp.concatenate(
        [a_ref[0], k_ref[0], q_ref[0],
         jnp.zeros((dk - 3 * block_h, dk), _F32)], axis=0)    # [dk, dk]
    cols = rows.T                          # column j: row j of the stack
    for j in range(block_h):
        a, k, q = (cols[:, n * block_h + j:n * block_h + j + 1]
                   for n in range(3))                          # [dk, 1]
        Sd = s_ref[0, j] * a                                   # [dk, dv]
        pred = jnp.sum(k * Sd, axis=0, keepdims=True)          # [1, dv]
        S = Sd + k * (beta_ref[0, j:j + 1] * (v_ref[0, j:j + 1] - pred))
        so_ref[0, j] = S
        o_ref[0, j:j + 1] = jnp.sum(q * S, axis=0, keepdims=True)


def step_heads(H: int, dk: int) -> int:
    """Heads a grid step of ``kda_step`` takes.  A block's rows are ``[h,
    dk]`` tiles, so ``h`` is whole sublane tiles (a multiple of 8) or all
    the heads, and its three row stacks fit one ``dk x dk`` transposition
    (``3 h <= dk``).  By the chip's table (``PERF.md`` section 6, PR 44:
    128 slots x 32 heads of 128 x 128, states donated: 0.961 ms at 8, 0.914
    at 16, 0.918 at 32; 0.656 is the HBM roofline) the most of at most 16;
    all the heads where no multiple of 8 divides them."""
    blocks = [h for h in (8, 16) if H % h == 0 and 3 * h <= dk]
    return max(blocks) if blocks else H


def _step_pallas(q, k, v, g, beta, state, *, block_h=None):
    B, H, dk = q.shape
    dv = v.shape[-1]
    block_h = block_h or step_heads(H, dk)
    if 3 * block_h > dk:
        raise ValueError(f"kda_step: the decay, key and query rows of "
                         f"{block_h} heads do not fit one {dk} x {dk} "
                         f"transposition")
    z = _at.I0

    def row(width):
        return pl.BlockSpec((1, block_h, width), lambda b, h: (b, h, z))

    mat = pl.BlockSpec((1, block_h, dk, dv), lambda b, h: (b, h, z, z))
    return pl.pallas_call(
        functools.partial(_step_kernel, block_h=block_h),
        name="kda_step",
        interpret=not _device.on_tpu(),
        grid=(B, H // block_h),
        in_specs=[row(dk), row(dk), row(dk), row(dv), row(dv), mat],
        out_specs=[row(dv), mat],
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # the stored states are updated where they lie; rows past B (the
        # write-drop row) are no block of the grid and stay as they were
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(jnp.exp(g), k, q, v, jnp.broadcast_to(beta[..., None], v.shape), state)


def kda_step(q, k, v, g, beta, state):
    """One token of each slot: ``q``, ``k``, ``g`` ``[B, H, dk]``, ``v``
    ``[B, H, dv]``, ``beta`` ``[B, H]``, ``state`` ``[>= B, H, dk, dv]``
    float32 (row ``i`` is slot ``i``; further rows are left alone).
    Returns float32 ``o`` ``[B, H, dv]`` and the updated ``state``.  A slot
    given ``g = 0``, ``beta = 0`` keeps its state bit for bit."""
    q, k, v, g, beta = (jnp.asarray(t, _F32) for t in (q, k, v, g, beta))
    step = _step_pallas if gated_delta_eligible() else _step_jnp
    return step(q, k, v, g, beta, state)
