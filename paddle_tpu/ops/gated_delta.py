"""The gated delta rule — linear attention over a decaying matrix state
that is WRITTEN by the delta rule (Gated DeltaNet, arXiv:2412.06464;
``beta`` in (0, 2) is arXiv:2411.12537's negative eigenvalues).

Per head, with keys of width ``dk``, values of width ``dv`` and a float32
state ``S`` in ``R^{dk x dv}``:

    S_t = a_t S_{t-1} + beta_t k_t (v_t - (a_t S_{t-1})^T k_t)^T,  a_t = e^{g_t}
    o_t = S_t^T q_t

i.e. decay, then replace what the state held under ``k_t`` by ``v_t`` at
strength ``beta_t``.  A token with ``g = 0`` and ``beta = 0`` is the
identity on the state: that is what padding is given.

Three routes to that function:

* :func:`gated_delta_recurrent` — the recurrence as written, one token a
  ``lax.scan`` step: the oracle of the tests and of the chip tool;
* :func:`gated_delta_chunk` — a whole prompt from the zero state, in chunks
  of 64.  Inside a chunk the WY form: with ``b`` the running sum of ``g``,
  ``A_ij = beta_i (k_i . k_j) e^{b_i - b_j}`` (``i > j``) and
  ``T = (I + A)^{-1}``, the chunk's writes are ``U - W S`` for ``W = T (beta
  k e^b)``, ``U = T (beta v)``, whatever state ``S`` the chunk starts from.
  Those operands are batched matmuls over all chunks at once (XLA); what is
  sequential, the walk over a row's chunks with the state resident in
  VMEM, is the Pallas kernel ``gated_delta_chunk``;
* :func:`gated_delta_step` — one token a slot against the slots' stored
  states: the Pallas kernel ``gated_delta_step`` reads each state once and
  writes it once, in place (``input_output_aliases``).

Off the TPU (and on a mesh of several devices) both run their ``jnp``
forms, which are also what the chip tool compares the kernels with.  The
heads a grid step takes (``block_h``) are a RULE of the shape in both
kernels (:func:`walk_heads`, :func:`step_heads`), nothing is searched.

GROUPED KEY HEADS: ``q`` and ``k`` may have fewer heads than ``v`` (``H_v =
rep * H_k``); value head ``h`` then reads key head ``h // rep``.  The state,
the gates and the output are per value head.  The chunked form repeats the
key heads where it builds its per-value-head operands (XLA fuses the
repeat into them); the step kernel indexes: a block of ``block_h`` value
heads fetches its ``block_h / rep`` key heads.
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

__all__ = ["gated_delta_recurrent", "gated_delta_chunk", "gated_delta_step",
           "gated_delta_eligible", "CHUNK"]

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
#: tokens a chunk: the WY operands are [64, 64] per chunk and head, and a
#: chunk's products fill half an MXU pass; nothing is searched here
CHUNK = 64
#: side of the diagonal blocks inverted by forward substitution
_BASE = 16


def gated_delta_eligible() -> bool:
    """Do the Pallas kernels run (a TPU, a one-device mesh)?"""
    return _at.fused_epilogues_eligible()


# -- the recurrence as written ------------------------------------------------
def _token(S, q, k, v, g, beta):
    """One token of every (row, head): ``S`` ``[..., dk, dv]``."""
    Sd = S * jnp.exp(g)[..., None, None]
    pred = jnp.einsum("...k,...kv->...v", k, Sd, precision=_HI)
    S = Sd + k[..., :, None] * (beta[..., None] * (v - pred))[..., None, :]
    return S, jnp.einsum("...k,...kv->...v", q, S, precision=_HI)


def _per_value_head(q, k, heads, axis):
    """``q`` and ``k`` of ``H_k`` heads along ``axis`` repeated to ``heads``
    value heads (value head ``h`` reads key head ``h // rep``); themselves
    where the counts are equal."""
    rep = heads // q.shape[axis]
    if rep == 1:
        return q, k
    return jnp.repeat(q, rep, axis=axis), jnp.repeat(k, rep, axis=axis)


def gated_delta_recurrent(q, k, v, g, beta, state=None):
    """``q``, ``k`` ``[B, T, H_k, dk]``, ``v`` ``[B, T, H, dv]``, ``g``,
    ``beta`` ``[B, T, H]``; ``state`` ``[B, H, dk, dv]`` (zero when None).
    Returns float32 ``o`` ``[B, T, H, dv]`` and the state after token T."""
    q, k, v, g, beta = (jnp.asarray(t, _F32) for t in (q, k, v, g, beta))
    q, k = _per_value_head(q, k, v.shape[2], axis=2)
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
def _unit_lower_inverse(A):
    """``(I + A)^{-1}`` for strictly lower-triangular ``A`` ``[..., C, C]``,
    exactly: the diagonal blocks of side 16 by forward substitution (all of
    them at once), then pairs of blocks merged by
    ``[[T1, 0], [-T2 A21 T1, T2]]``."""
    C = A.shape[-1]
    base = min(_BASE, C)
    nb = C // base
    assert nb * base == C and nb & (nb - 1) == 0, (C, base)
    D = jnp.stack([A[..., i * base:(i + 1) * base, i * base:(i + 1) * base]
                   for i in range(nb)], axis=-3)       # [..., nb, base, base]
    eye = jnp.eye(base, dtype=A.dtype)
    rows = [jnp.broadcast_to(eye[0], D.shape[:-2] + (base,))]
    for i in range(1, base):
        prev = jnp.stack(rows, axis=-2)                # [..., nb, i, base]
        rows.append(eye[i] - jnp.einsum("...j,...jc->...c", D[..., i, :i],
                                        prev, precision=_HI))
    inv = jnp.stack(rows, axis=-2)
    blocks, size = [inv[..., i, :, :] for i in range(nb)], base
    while len(blocks) > 1:
        merged = []
        for p in range(0, len(blocks), 2):
            t1, t2 = blocks[p], blocks[p + 1]
            r = p * size
            a21 = A[..., r + size:r + 2 * size, r:r + size]
            t21 = -jnp.matmul(jnp.matmul(t2, a21, precision=_HI), t1,
                              precision=_HI)
            merged.append(jnp.concatenate([
                jnp.concatenate([t1, jnp.zeros_like(t1)], axis=-1),
                jnp.concatenate([t21, t2], axis=-1)], axis=-2))
        blocks, size = merged, 2 * size
    return blocks[0]


def chunk_operands(q, k, v, g, beta, chunk=CHUNK):
    """What the walk over chunks reads, every chunk at once: ``qg = q e^b``
    ``[B, H, N, C, dk]``, ``kdT = (k e^{b_C - b})^T`` ``[B, H, N, dk, C]``,
    ``W`` ``[B, H, N, C, dk]``, ``U`` ``[B, H, N, C, dv]``, the causal
    in-chunk scores ``P_ij = (q_i . k_j) e^{b_i - b_j}`` ``[B, H, N, C, C]``
    and the chunk's whole decay ``e^{b_C}`` ``[B, H, N]``.  Float32; ``T``
    must be whole chunks."""
    B, T = q.shape[:2]
    N = T // chunk

    def split(t):      # [B, T, H, ...] -> [B, H, N, C, ...]
        return jnp.moveaxis(jnp.asarray(t, _F32).reshape(
            B, N, chunk, *t.shape[2:]), 3, 1)

    q, k, v, g, beta = map(split, (q, k, v, g, beta))
    q, k = _per_value_head(q, k, v.shape[1], axis=1)
    b = jnp.cumsum(g, axis=-1)                               # [B, H, N, C]
    i = np.arange(chunk)
    low = i[:, None] >= i[None, :]
    # masked BEFORE the exponential: above the diagonal b_i - b_j > 0 grows
    # with the chunk's decay and overflows
    decay = jnp.exp(jnp.where(low, b[..., :, None] - b[..., None, :],
                              -jnp.inf))
    kb = k * beta[..., None]
    A = jnp.where(i[:, None] > i[None, :],
                  jnp.einsum("...id,...jd->...ij", kb, k, precision=_HI)
                  * decay, 0.0)
    Tm = _unit_lower_inverse(A)
    eb = jnp.exp(b)[..., None]
    W = jnp.matmul(Tm, kb * eb, precision=_HI)
    U = jnp.matmul(Tm, v * beta[..., None], precision=_HI)
    P = jnp.einsum("...id,...jd->...ij", q, k, precision=_HI) * decay
    last = b[..., -1:]
    kdT = jnp.swapaxes(k * jnp.exp(last - b)[..., None], -1, -2)
    return q * eb, kdT, W, U, P, jnp.exp(last[..., 0])


def _walk_jnp(qg, kdT, W, U, P, dl):
    """The walk over chunks in ``jnp``: the CPU path and the kernel's
    oracle.  Returns ``o`` ``[B, H, N, C, dv]`` and the final state."""
    B, H, _, _, dk = qg.shape

    def step(S, x):
        qg, kdT, W, U, P, dl = x
        vn = U - jnp.matmul(W, S, precision=_HI)
        o = (jnp.matmul(qg, S, precision=_HI)
             + jnp.matmul(P, vn, precision=_HI))
        return dl[..., None, None] * S + jnp.matmul(kdT, vn, precision=_HI), o

    S, o = jax.lax.scan(step, jnp.zeros((B, H, dk, U.shape[-1]), _F32), tuple(
        jnp.moveaxis(t, 2, 0) for t in (qg, kdT, W, U, P, dl)))
    return jnp.moveaxis(o, 0, 2), S


def _walk_kernel(dl_ref, qg_ref, kdT_ref, w_ref, u_ref, p_ref, o_ref, s_ref,
                 *, block_h, heads, chunks):
    """One chunk of ``block_h`` heads of one row.  ``s_ref`` (the final
    state's block) keeps its index over the chunk axis, so it IS the
    resident state: zeroed at the row's first chunk, written back after its
    last."""
    i32 = jnp.int32
    b, hb, n = (pl.program_id(0).astype(i32), pl.program_id(1).astype(i32),
                pl.program_id(2).astype(i32))

    @pl.when(n == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=_F32, precision=_HI)

    for j in range(block_h):
        S = s_ref[0, j]
        vn = u_ref[0, j, 0] - dot(w_ref[0, j, 0], S)
        o_ref[0, j, 0] = dot(qg_ref[0, j, 0], S) + dot(p_ref[0, j, 0], vn)
        h = hb * i32(block_h) + i32(j)
        s_ref[0, j] = (dl_ref[(b * i32(heads) + h) * i32(chunks) + n] * S
                       + dot(kdT_ref[0, j, 0], vn))


def walk_heads(H: int) -> int:
    """Heads a grid step of ``gated_delta_chunk`` walks.  A rule of the
    shape, from the table ``tools/gated_delta_chip.py --tiles`` timed on
    the chip (``PERF.md`` section 6, PR 44): the MOST of 1, 2, 3, 5 that
    divide the heads (5 of 30 heads of 96 x 192: 0.69-1.53 ms a one-row
    call at 1536-4096 tokens against 0.78-1.82 at one; 2 of 32 over 16 key
    heads of 128 x 128: 0.41-0.79 ms a two-row call at 256-1024 against
    0.44-0.91), monotone in both tables.  It was a measured search of
    ``ops.autotune`` until PR 44; a race between candidates this close
    draws differently from one cold checkout to the next, and moved a cell
    by more than its bound with no edit to this file.  ``block_h=`` on the
    two kernels stays for that tool and for the tests that run every
    block."""
    return max(h for h in (1, 2, 3, 5) if H % h == 0)


def _walk_pallas(qg, kdT, W, U, P, dl, *, block_h=None):
    B, H, N, C, dk = qg.shape
    dv = U.shape[-1]
    block_h = block_h or walk_heads(H)
    z = _at.I0

    def blk(*tail):
        return pl.BlockSpec((1, block_h, 1) + tail,
                            lambda b, h, n, dl: (b, h, n, z, z))

    kernel = functools.partial(_walk_kernel, block_h=block_h, heads=H,
                               chunks=N)
    return pl.pallas_call(
        kernel,
        name="gated_delta_chunk",
        interpret=not _device.on_tpu(),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // block_h, N),
            in_specs=[blk(C, dk), blk(dk, C), blk(C, dk), blk(C, dv),
                      blk(C, C)],
            out_specs=[blk(C, dv),
                       pl.BlockSpec((1, block_h, dk, dv),
                                    lambda b, h, n, dl: (b, h, z, z))],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, N, C, dv), _F32),
                   jax.ShapeDtypeStruct((B, H, dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(dl.reshape(-1), qg, kdT, W, U, P)


def gated_delta_chunk(q, k, v, g, beta, chunk=CHUNK):
    """A prompt from the zero state.  Shapes as
    :func:`gated_delta_recurrent` (``q`` and ``k`` may have fewer heads
    than ``v``); ``T`` is padded to whole chunks with identity tokens.
    Returns float32 ``o`` ``[B, T, H, dv]`` and the state after the last
    token, ``[B, H, dk, dv]``."""
    B, T, H = v.shape[:3]
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad))
                                    + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    ops = chunk_operands(q, k, v, g, beta, chunk)
    walk = _walk_pallas if gated_delta_eligible() else _walk_jnp
    o, S = walk(*ops)
    o = jnp.moveaxis(o, 1, 3).reshape(B, T + pad, H, -1)
    return o[:, :T], S


# -- one token a slot ----------------------------------------------------------
def _step_jnp(q, k, v, g, beta, state):
    B = q.shape[0]
    q, k = _per_value_head(q, k, v.shape[1], axis=1)
    S, o = _token(state[:B], q, k, v, g, beta)
    return o, jax.lax.dynamic_update_slice(state, S, (0, 0, 0, 0))


def _step_kernel(a_ref, beta_ref, q_ref, k_ref, v_ref, s_ref, o_ref, so_ref):
    """``block_h`` heads of one slot: keys and queries ride as columns
    ``[dk, 1]``, values as rows ``[1, dv]``, so the products with the state
    are broadcasts and sums over sublanes.  With grouped key heads the
    block holds ``block_h / rep`` of them, each serving the ``rep`` value
    heads that follow one another."""
    rep = s_ref.shape[1] // k_ref.shape[1]
    if rep == 1:   # kept as it was written: the kernel the older cell compiled
        Sd = s_ref[0] * a_ref[0]                           # [h, dk, dv]
        k = k_ref[0]
        pred = jnp.sum(k * Sd, axis=1, keepdims=True)      # [h, 1, dv]
        S = Sd + k * (beta_ref[0] * (v_ref[0] - pred))
        so_ref[0] = S
        o_ref[0] = jnp.sum(q_ref[0] * S, axis=1, keepdims=True)
        return
    for j in range(k_ref.shape[1]):   # a [dk, 1] column over its rep heads
        hs = slice(j * rep, (j + 1) * rep)
        Sd = s_ref[0, hs] * a_ref[0, hs]                   # [rep, dk, dv]
        k = k_ref[0, j]
        pred = jnp.sum(k * Sd, axis=1, keepdims=True)
        S = Sd + k * (beta_ref[0, hs] * (v_ref[0, hs] - pred))
        so_ref[0, hs] = S
        o_ref[0, hs] = jnp.sum(q_ref[0, j] * S, axis=1, keepdims=True)


def _step_space(q, k, v, g, beta, state):
    H, dk, dv = state.shape[1:]
    rep = H // q.shape[1]
    # the state block in and out, double-buffered; whole groups of value
    # heads, so that a block's key heads are a block too
    return [{"block_h": h} for h in range(1, H + 1)
            if H % h == 0 and h % rep == 0
            and _at.vmem_fits(4 * 4 * h * dk * (-(-dv // 128) * 128))]


def step_heads(q, k, v, g, beta, state) -> int:
    """Heads a grid step of ``gated_delta_step`` takes.  A rule of the
    shape, from the table ``tools/gated_delta_chip.py --tiles`` timed on
    the chip (``PERF.md`` section 6, PR 44): the largest block of
    :func:`_step_space` of at most 10 heads (10 of 30 at 96 x 192 and 16
    slots: 0.350 ms, the table's best 0.349 at 5; 8 of 32 at 128 x 128 and
    64 slots: 0.754 ms, the best 0.744 at 16: ties inside 2 %, so the
    blocks the kernel always started from).  A measured search of
    ``ops.autotune`` until PR 44 (:func:`walk_heads` says why no more)."""
    return max(c["block_h"] for c in _step_space(q, k, v, g, beta, state)
               if c["block_h"] <= 10)


def _step_pallas(q, k, v, g, beta, state, *, block_h=None):
    B, Hk, dk = q.shape
    H, dv = v.shape[1:]
    rep = H // Hk
    block_h = block_h or step_heads(q, k, v, g, beta, state)
    z = _at.I0

    def blk(*tail, heads=block_h):
        return pl.BlockSpec((1, heads) + tail, lambda b, h: (b, h, z, z))

    def key(*tail):   # block h of the key heads serves block h of the values
        return blk(*tail, heads=block_h // rep)

    o, state = pl.pallas_call(
        _step_kernel,
        name="gated_delta_step",
        interpret=not _device.on_tpu(),
        grid=(B, H // block_h),
        in_specs=[blk(1, 1), blk(1, 1), key(dk, 1), key(dk, 1), blk(1, dv),
                  blk(dk, dv)],
        out_specs=[blk(1, dv), blk(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # the stored states are updated where they lie; rows past B (the
        # write-drop row) are no block of the grid and stay as they were
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(jnp.exp(g)[..., None, None], beta[..., None, None], q[..., None],
      k[..., None], v[:, :, None, :], state)
    return o[:, :, 0], state


def gated_delta_step(q, k, v, g, beta, state):
    """One token of each slot: ``q``, ``k`` ``[B, H_k, dk]``, ``v`` ``[B, H,
    dv]``, ``g``, ``beta`` ``[B, H]``, ``state`` ``[>= B, H, dk, dv]``
    float32 (row ``i`` is slot ``i``; further rows are left alone).
    Returns float32 ``o`` ``[B, H, dv]`` and the updated ``state``.  A slot
    given ``g = 0``, ``beta = 0`` keeps its state bit for bit."""
    q, k, v, g, beta = (jnp.asarray(t, _F32) for t in (q, k, v, g, beta))
    step = _step_pallas if gated_delta_eligible() else _step_jnp
    return step(q, k, v, g, beta, state)
