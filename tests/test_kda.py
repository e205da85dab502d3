"""``ops/kda.py``: the delta rule with one decay a key channel, its three
routes against one another on the CPU (the Pallas kernels in interpret
mode).

Tolerances.  All three routes are float32 and the suite's matmul precision
is "highest": the chunked form and the recurrence agreed to 2.9e-6 in the
state and 7e-7 in the output over these shapes (outputs of magnitude 0.4,
states up to 2), so 2e-5.  The kernels run the ``jnp`` forms' own
arithmetic on the same operands: 2e-6.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import gated_delta as gd
from paddle_tpu.ops import kda

TOL, KERNEL_TOL = 2e-5, 2e-6


def inputs(B, T, H, dk, dv, seed=0, strongest=-8.0):
    """Unit keys, scaled unit queries, ``g`` log-uniform between -0.0025 and
    ``strongest`` A CHANNEL (so some channels of some tokens decay by e^-8 a
    token: a chunk's running sum passes -88 within a dozen tokens), ``beta``
    in (0, 1).  float32."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (B, T, H, dk), jnp.float32)
            for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv), jnp.float32)
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, dk), jnp.float32, -6.0,
                                    np.log(-strongest)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H), jnp.float32))
    return q, k, v, g, beta


def padded(q, k, v, g, beta, valid):
    """Padding tokens: the identity on the state, ``g = 0`` and ``beta =
    0``."""
    valid = jnp.asarray(valid)
    return (q, k, v, jnp.where(valid[..., None, None], g, 0.0),
            jnp.where(valid[..., None], beta, 0.0))


@pytest.fixture(params=["jnp", "pallas"])
def route(request, monkeypatch):
    """Both forms of the chunk walk and of the step: the ``jnp`` one and the
    Pallas kernel (interpret mode off the TPU)."""
    monkeypatch.setattr(kda, "gated_delta_eligible",
                        lambda: request.param == "pallas")
    return request.param


@pytest.mark.parametrize("T", [150, 64, 37, 200])
def test_kda_chunk_against_the_token_by_token_recurrence(route, T):
    """T no multiple of 64, padding INSIDE the prompt (tokens 40-54 of row
    0) and after it (row 1 ends at 3 / 4 of T), decays down to e^-8 a token
    on some channels."""
    args = inputs(2, T, 3, 16, 24, seed=T)
    valid = np.ones((2, T), bool)
    valid[0, 40:55] = False
    valid[1, 3 * T // 4:] = False
    args = padded(*args, valid)
    o, S = kda.kda_chunk(*args)
    o0, S0 = kda.kda_recurrent(*args)
    assert float(jnp.abs(o0).max()) > 0.1 and float(jnp.abs(S0).max()) > 0.5
    assert float(jnp.abs(o - o0).max()) < TOL
    assert float(jnp.abs(S - S0).max()) < TOL
    # a state left of padding is the state before it: row 1's last real
    # token wrote the state the whole row ends with
    cut = tuple(t[1:, :3 * T // 4] for t in args)
    assert float(jnp.abs(kda.kda_recurrent(*cut)[1] - S[1:]).max()) < TOL


def test_the_naive_factoring_fails_where_the_sub_blocks_do_not():
    """``A_ij = sum_c (k_ic e^{b_ic}) (k_jc e^{-b_jc})`` is the scalar-decay
    chunk form carried over channel by channel.  With g = -8 on a channel
    ``e^{-b}`` passes float32's largest number after 12 tokens of a chunk:
    the product is inf * 0.  The sub-block form never forms ``e^{-b}``."""
    q, k, v, g, beta = inputs(1, 64, 2, 16, 16, seed=3)
    g = g.at[..., 0].set(-8.0)              # one channel at full strength
    b = jnp.cumsum(g[0].transpose(1, 0, 2), axis=1)          # [H, C, dk]
    kh = k[0].transpose(1, 0, 2)
    naive = jnp.einsum("hic,hjc->hij", kh * jnp.exp(b), kh * jnp.exp(-b))
    assert not bool(jnp.isfinite(naive).all())
    (A,) = kda._decayed_products((kh,), kh, b)
    low = np.tril(np.ones((64, 64), bool))
    want = jnp.sum(kh[:, :, None] * kh[:, None] * jnp.exp(jnp.where(
        low[..., None], b[:, :, None] - b[:, None], -jnp.inf)), -1)
    assert bool(jnp.isfinite(A).all())
    assert float(jnp.abs(A - want).max()) < 1e-6
    assert float(jnp.abs(jnp.where(low, 0.0, A)).max()) == 0.0
    o, S = kda.kda_chunk(q, k, v, g, beta)
    o0, S0 = kda.kda_recurrent(q, k, v, g, beta)
    assert bool(jnp.isfinite(o).all())
    assert float(jnp.abs(o - o0).max()) < TOL
    assert float(jnp.abs(S - S0).max()) < TOL


def test_kda_step_continues_a_chunks_state_and_leaves_other_rows(route):
    """Slots 0-2 decode one token from the state a prompt left; slot 1 is
    free (``g = 0``, ``beta = 0``): its state stays bit for bit, as does
    the write-drop row past the batch."""
    T = 70
    q, k, v, g, beta = inputs(3, T + 1, 4, 16, 16, seed=9)
    _, S = kda.kda_chunk(*(t[:, :T] for t in (q, k, v, g, beta)))
    state = jnp.concatenate([S, jnp.full((1,) + S.shape[1:], 7.0)])
    last = [t[:, T] for t in (q, k, v, g, beta)]
    live = jnp.asarray([True, False, True])
    last[3] = jnp.where(live[:, None, None], last[3], 0.0)
    last[4] = jnp.where(live[:, None], last[4], 0.0)
    o, new = kda.kda_step(*last, state)
    o0, S0 = kda.kda_recurrent(q, k, v, g, beta)
    assert float(jnp.abs(o[0] - o0[0, T]).max()) < TOL
    assert float(jnp.abs(new[0] - S0[0]).max()) < TOL
    assert float(jnp.abs(new[2] - S0[2]).max()) < TOL
    assert np.array_equal(np.asarray(new[1]), np.asarray(state[1]))
    assert np.array_equal(np.asarray(new[3]), np.asarray(state[3]))


@pytest.mark.parametrize("block_h", [1, 2, 4])
def test_the_step_kernel_is_the_jnp_step_at_every_head_block(block_h):
    q, k, v, g, beta = (t[:, 0] for t in inputs(5, 1, 4, 16, 24, seed=2))
    state = jax.random.normal(jax.random.PRNGKey(1), (6, 4, 16, 24),
                              jnp.float32)
    o0, s0 = kda._step_jnp(q, k, v, g, beta, state)
    o, s = kda._step_pallas(q, k, v, g, beta, state, block_h=block_h)
    assert float(jnp.abs(o - o0).max()) < KERNEL_TOL
    assert float(jnp.abs(s - s0).max()) < KERNEL_TOL


@pytest.mark.parametrize("block_h", [1, 3])
def test_the_walk_kernel_is_the_jnp_walk_at_every_head_block(block_h):
    ops = kda.chunk_operands(*inputs(2, 192, 3, 16, 24, seed=4))
    o0, S0 = kda._walk_jnp(*ops)
    o, S = kda._walk_pallas(*ops, block_h=block_h)
    assert float(jnp.abs(o - o0).max()) < KERNEL_TOL
    assert float(jnp.abs(S - S0).max()) < KERNEL_TOL


def test_the_rules_of_the_two_grids_at_the_cells_shape():
    # 32 heads of 128 x 128: what the chip's table picked (PERF.md, PR 44)
    assert kda.walk_heads(32) == 8 and kda.walk_heads(5) == 1
    assert kda.step_heads(32, 128) == 16
    # whole sublane tiles of rows, or all the heads; the three row stacks
    # of a block fit one dk x dk transposition
    assert [kda.step_heads(H, dk) for H, dk in (
        (4, 16), (30, 96), (3, 16), (8, 32), (64, 64))] == [4, 30, 3, 8, 16]
    q, k, v, g, beta = (t[:, 0] for t in inputs(2, 1, 8, 16, 16))
    with pytest.raises(ValueError, match="transposition"):
        kda._step_pallas(q, k, v, g, beta, jnp.zeros((2, 8, 16, 16)))


# -- equal channels: the scalar-decay rule ----------------------------------------
def test_equal_channels_reproduce_the_gated_delta_rule(route, monkeypatch):
    """With every channel of ``g`` equal KDA IS ``ops/gated_delta.py``'s
    rule: recurrence and one-token step agree with that file's to the
    kernels' tolerance, the chunked prompt to the chunked form's (the
    running sum of ``g`` is a product with a triangle of ones here, a
    cumsum there: 1.2e-6 apart)."""
    monkeypatch.setattr(gd, "gated_delta_eligible", lambda: route == "pallas")
    q, k, v, g, beta = inputs(2, 100, 3, 16, 24, seed=6, strongest=-1.0)
    g1 = g[..., 0]
    ge = jnp.broadcast_to(g1[..., None], g.shape)
    o, S = kda.kda_chunk(q, k, v, ge, beta)
    o0, S0 = gd.gated_delta_chunk(q, k, v, g1, beta)
    assert float(jnp.abs(o - o0).max()) < TOL
    assert float(jnp.abs(S - S0).max()) < TOL
    o, S = kda.kda_recurrent(q, k, v, ge, beta)
    o0, S0 = gd.gated_delta_recurrent(q, k, v, g1, beta)
    assert float(jnp.abs(o - o0).max()) < KERNEL_TOL
    assert float(jnp.abs(S - S0).max()) < KERNEL_TOL
    one = [t[:, 5] for t in (q, k, v, ge, beta)]
    o, s = kda.kda_step(*one, S)
    o0, s0 = gd.gated_delta_step(*one[:3], g1[:, 5], one[4], S0)
    assert float(jnp.abs(o - o0).max()) < KERNEL_TOL
    assert float(jnp.abs(s - s0).max()) < KERNEL_TOL
