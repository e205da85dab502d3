"""A decoder whose layers are of up to three kinds, by a per-layer
``layer_types`` list (any non-empty subset of them): ``linear_attention`` —
the gated delta rule (``ops/gated_delta.py``) over a per-head float32
matrix state, behind a short causal depthwise convolution —,
``full_attention`` — causal softmax attention with QK-norm — and
``sliding_attention`` — the same projections, norms and heads, but a query
at ``qp`` sees only the ``sliding_window`` keys ``qp - W + 1 .. qp``.  ONE
stack; what differs between the published decoders of this shape are
options of :class:`HybridConfig`, each with the default that leaves the
first of them (reordered norms, a dense MLP, no rotary, equal head counts)
the program it was:

* the block: ``block_norm="post"``, ``h = x + norm(Mix(x))``, ``y = h +
  norm(FFN(h))`` (the reordered norm), or ``"pre"``, ``h = x +
  Mix(norm(x))``, ``y = h + FFN(norm(h))``; ``zero_centered_norms`` stores
  the block, QK and final gains as their distance from one, ``(1 + w)``;
* the FFN of each layer, ``ffn_types``: ``dense`` (gated-SiLU MLP) or
  ``moe`` (:class:`paddle_tpu.moe.DroplessMoE` built from ``moe``, its
  keyword arguments: router kind, the experts HELD here, the shared
  expert's gate);
* full attention: ``num_kv_heads`` grouped K/V heads (query head h reads
  K/V head ``h // (H / H_kv)``; the pools' row is ``H_kv * head_dim``
  wide), ``head_dim`` other than ``hidden / heads``, ``qk_norm`` over the
  whole ``"projection"`` or over each ``"head"``, ``rope_theta`` (None: no
  rotary, positions reach the layer through the recurrent ones) with
  rotate-half pairs on the first ``partial_rotary_factor`` of each head's
  dims, ``attn_output_gate`` (the query projection is twice as wide, ``[q |
  gate]`` per head, and the context is scaled by ``sigmoid(gate)``);
* ``sliding_window`` (None: no ``sliding_attention`` layer) and
  ``rope_kinds``, WHICH of the two softmax kinds ``rope_theta`` rotates
  (both by default; a decoder whose global layers carry no positions names
  ``("sliding_attention",)``);
* linear attention: ``linear_num_key_heads`` fewer than the value heads
  (value head h reads key head ``h // rep``), ``allow_neg_eigval``.

No bias anywhere, final norm, untied head.

``linear_attention``, per value head of ``d_k`` keys and ``d_v`` values:

    [q~ | k~ | v~] = x W_qkv;  causal depthwise conv of K taps, then SiLU
    q = q' / |q'| * d_k^-1/2,  k = k' / |k'|
    beta = 2 sigmoid(x W_b)  (1 sigmoid without ``allow_neg_eigval``)
    g = -exp(A_log) softplus(x W_a + dt_bias)
    S_t = e^g S_{t-1} + beta k (v - (e^g S_{t-1})^T k)^T;  o = S_t^T q
    y = RMSNorm(o) * silu(x W_g);  y W_o

Two kinds of cache, one that grows with the context and one that does not,
so the model owns the layout of both and declares ``slot_state`` (the
serving-model protocol, ``serving/generation.py``):

* a full layer holds K and V page pools ``[P + 1, page, H_kv * hd]`` in
  ``GPTModel``'s stored order, read by the same ``paged_decode`` kernel;
* a linear layer holds, PER SLOT and not per page, ``state`` ``[B + 1, H,
  d_k, d_v]`` float32 and ``conv`` ``[B + 1, K - 1, conv_width]`` (the last
  ``K - 1`` rows of ``[q~ | k~ | v~]``); row ``B`` is the write-drop row, as
  page ``P`` is the write-drop page;
* a sliding layer holds, PER SLOT too, ``ring_k`` and ``ring_v`` ``[B + 1,
  W, H_kv * hd]``: position ``p`` lives in ring row ``p % W``, so the cache
  is ``W`` rows whatever the context.  What row ``r`` holds for a query at
  ``qp`` is arithmetic, ``p_r = qp - ((qp - r) mod W)`` (:func:`ring_positions`),
  real iff ``p_r >= 0``: a slot needs no reset and no ``pos_map``, because
  what a previous tenant left has a negative ``p_r`` or was overwritten.
  To the decode kernel a ring IS a pool of one ``W``-row page a slot
  (``paged_decode`` under the name ``window_decode``).

What ``slot_state`` brings it brings to a sliding layer too: the engine
refuses speculation and hand-off for such a model and serves a
``prefix_key`` cold (``prefix_unshared``).

An ADMISSION (``forward_paged(..., slots=[R])``) takes whole prompts from
position 0: it starts every row from the zero state, whatever its slot
held, and writes the state and the conv window after the row's last real
token (a sliding layer: the K and V of its last ``min(len, W)`` real
tokens) into slot ``slots[r]`` (``-1``: the drop row).  A DECODE call
(``slots=None``, one token a row) continues slot ``i`` in row ``i``.  A
padding token (position ``-1``) is the identity on all of them: it neither
decays nor writes the state, does not enter the conv window and writes no
ring row.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.errors import InvalidArgumentError
from ..moe import DroplessMoE
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..ops import autotune as _at
from ..ops.gated_delta import gated_delta_chunk, gated_delta_step
from ..ops.paged_attention import (key_visible, paged_attention,
                                   paged_flash_decode, paged_flash_eligible,
                                   sweep_bound)
from .latent_moe import GatedMLP, _mm

__all__ = ["HybridConfig", "HybridModel", "HybridForCausalLM",
           "rope_rotate_half", "ring_positions"]

_F32 = jnp.float32
LAYER_TYPES = ("linear_attention", "full_attention", "sliding_attention")
_SOFTMAX_KINDS = LAYER_TYPES[1:]
FFN_TYPES = ("dense", "moe")


def _kernels(head_dim=None) -> bool:
    """Gate of the admission's flash kernel (a TPU, a one-device mesh)."""
    return _at.fused_epilogues_eligible(head_dim)


def _paged_flash(head_dim, page_size) -> bool:
    """Gate of the decode's ``paged_decode`` kernel, as ``models.gpt``'s."""
    return paged_flash_eligible(head_dim, page_size)


class HybridConfig:
    def __init__(self, vocab_size, hidden_size, num_heads, intermediate_size,
                 layer_types, linear_num_heads=None, linear_key_head_dim=None,
                 linear_value_head_dim=None, linear_conv_kernel=4,
                 allow_neg_eigval=True, rms_norm_eps=1e-6, rope_theta=None,
                 max_position=4096, dtype="bfloat16", init_std=0.02,
                 num_kv_heads=None, head_dim=None, partial_rotary_factor=1.0,
                 qk_norm="projection", attn_output_gate=False,
                 linear_num_key_heads=None, block_norm="post",
                 zero_centered_norms=False, ffn_types=None, moe=None,
                 sliding_window=None, rope_kinds=_SOFTMAX_KINDS):
        bad = [t for t in layer_types if t not in LAYER_TYPES]
        if bad or not layer_types:
            raise InvalidArgumentError(
                f"layer_types must be a non-empty list of {LAYER_TYPES}, "
                f"got {bad or layer_types!r}")
        ffn_types = tuple(ffn_types or ("dense",) * len(layer_types))
        if (len(ffn_types) != len(layer_types)
                or any(t not in FFN_TYPES for t in ffn_types)
                or ("moe" in ffn_types) != bool(moe)):
            raise InvalidArgumentError(
                f"ffn_types must name one of {FFN_TYPES} a layer, and "
                f"`moe` (DroplessMoE's keyword arguments) goes with a 'moe' "
                f"among them: got {ffn_types!r}, moe={moe!r}")
        if qk_norm not in ("projection", "head") or block_norm not in (
                "post", "pre"):
            raise InvalidArgumentError(
                f"qk_norm is 'projection' or 'head' and block_norm 'post' "
                f"or 'pre', got {qk_norm!r}, {block_norm!r}")
        linear = (linear_num_heads, linear_key_head_dim,
                  linear_value_head_dim)
        if (("linear_attention" in layer_types and None in linear)
                or ("sliding_attention" in layer_types)
                != (sliding_window is not None)
                or any(k not in _SOFTMAX_KINDS for k in rope_kinds)):
            raise InvalidArgumentError(
                f"a 'linear_attention' layer needs the linear head sizes "
                f"(got {linear!r}), `sliding_window` goes with a "
                f"'sliding_attention' layer (got {sliding_window!r}), and "
                f"rope_kinds names some of {_SOFTMAX_KINDS}, got "
                f"{rope_kinds!r}")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads or num_heads)
        self.head_dim = int(head_dim or self.hidden_size // self.num_heads)
        self.intermediate_size = int(intermediate_size)
        self.layer_types = tuple(layer_types)
        self.ffn_types = ffn_types
        self.moe = dict(moe or {})
        self.linear_num_heads = int(linear_num_heads or 0)  # value heads
        self.linear_num_key_heads = int(linear_num_key_heads
                                        or self.linear_num_heads)
        self.linear_key_head_dim = int(linear_key_head_dim or 0)
        self.linear_value_head_dim = int(linear_value_head_dim or 0)
        self.linear_conv_kernel = int(linear_conv_kernel)
        self.allow_neg_eigval = bool(allow_neg_eigval)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = None if rope_theta is None else float(rope_theta)
        self.rope_kinds = tuple(rope_kinds)
        self.sliding_window = (None if sliding_window is None
                               else int(sliding_window))
        self.rotary_dim = int(self.head_dim * float(partial_rotary_factor))
        self.qk_norm, self.block_norm = qk_norm, block_norm
        self.attn_output_gate = bool(attn_output_gate)
        self.zero_centered_norms = bool(zero_centered_norms)
        self.max_position = int(max_position)
        self.dtype = dtype
        self.init_std = float(init_std)
        if (self.num_heads % self.num_kv_heads
                or (self.linear_num_heads
                    and self.linear_num_heads % self.linear_num_key_heads)
                or (self.rope_theta is not None and self.rotary_dim % 2)):
            raise InvalidArgumentError(
                f"{self.num_heads} query heads over {self.num_kv_heads} K/V "
                f"heads, {self.linear_num_heads} value heads over "
                f"{self.linear_num_key_heads} key heads, {self.rotary_dim} "
                f"rotary dims: the groups must be whole and the rotary "
                f"dims pairs")

    num_layers = property(lambda self: len(self.layer_types))

    @property
    def conv_width(self) -> int:
        """Channels under the convolution: ``[q~ | k~ | v~]``."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_heads * self.linear_value_head_dim)

    @property
    def experts_held(self) -> int:
        """Experts an expert layer holds here (0: no expert layer)."""
        if not self.moe:
            return 0
        held = self.moe.get("held")
        return int(held[1] if held else self.moe["num_experts"])


def rope_rotate_half(x, positions, theta, dims):
    """Rotary positions over the pairs ``(x[i], x[i + dims / 2])`` of the
    first ``dims`` entries of the last axis (the rotate-half form), angle
    ``pos * theta^(-2i / dims)``, in float32; the entries past ``dims`` pass
    untouched.  ``positions`` broadcasts against ``x``'s leading axes;
    negative (padding) positions rotate as position 0."""
    half = dims // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) * 2.0 / dims)
    ang = jnp.maximum(positions, 0).astype(_F32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(_F32)
    a, b = xf[..., :half], xf[..., half:dims]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            xf[..., dims:]], axis=-1).astype(x.dtype)


def ring_positions(qp, window):
    """The absolute position ring row ``r`` holds for a query at ``qp``
    (``[...]`` int32 -> ``[..., window]``): the last position ``<= qp``
    that is ``r`` modulo ``window``, or ``-1`` where that is before
    position 0 (a row this sequence has not written) or ``qp`` is padding."""
    r = jnp.arange(window, dtype=jnp.int32)
    p = qp[..., None] - jnp.mod(qp[..., None] - r, window)
    return jnp.where((p >= 0) & (qp[..., None] >= 0), p, -1)


def _norm(cfg, size, zero_centered=None):
    """A gain of ``size``; the block, QK and final norms follow
    ``zero_centered_norms``, the delta rule's output norm never does."""
    return nn.RMSNorm(size, cfg.rms_norm_eps, cfg.dtype, zero_centered=(
        cfg.zero_centered_norms if zero_centered is None else zero_centered))


def _weight(layer, *shape, dtype=None, init=None):
    cfg = layer.cfg
    return layer.create_parameter(
        shape, dtype=dtype or cfg.dtype,
        default_initializer=init or I.Normal(std=cfg.init_std))


class GatedDeltaNet(Layer):
    """The ``linear_attention`` mixer.

    THE CONTRACT OF A SUBCLASS with another gate and another rule
    (``models/kimi_linear.py:KimiDeltaAttention``).  It builds its own
    parameters (``Layer.__init__``, not this class's) and keeps the conv,
    its window, the head split with the l2 norms, and what :meth:`admit`
    and :meth:`decode` do to a slot.  Those shared parts read

    * of ``cfg``: ``linear_num_heads`` (value heads),
      ``linear_num_key_heads``, ``linear_key_head_dim``,
      ``linear_conv_kernel``, ``conv_width`` (and ``dtype``, ``init_std``
      through :func:`_weight`);
    * of the layer: ``qkv`` ``[D, conv_width]`` and ``conv`` ``[K,
      conv_width]``;
    * :meth:`_gates` ``(x, valid) -> (g, beta)`` in the shapes the rule
      takes, the identity (``g = 0``, ``beta = 0``) for a padding token, and
      :meth:`_output` ``(x, o) -> [..., D]``;
    * the class attributes ``scope`` (the ``jax.named_scope`` a traced
      metric finds the mixer by), ``_chunk(q, k, v, g, beta) -> (o, S)`` (a
      prompt from the zero state) and ``_step(q, k, v, g, beta, state) ->
      (o, state)`` (one token a slot, the states in place).

    ``tests/test_kimi_linear_model.py`` holds a subclass to all of it."""

    scope = "gdn"
    _chunk, _step = staticmethod(gated_delta_chunk), staticmethod(
        gated_delta_step)

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        D, H = cfg.hidden_size, cfg.linear_num_heads
        self.qkv = _weight(self, D, cfg.conv_width)
        self.gate = _weight(self, D, H * cfg.linear_value_head_dim)
        self.ab = _weight(self, D, 2 * H)            # [W_a | W_b]
        # Mamba-2's initialisation: A in U(1, 16), the step's softplus in
        # U(0.001, 0.1); both float32, as the decay they make
        self.A_log = _weight(self, H, dtype="float32",
                             init=I.Assign(jnp.log(jnp.linspace(1., 16., H))))
        dt = jnp.linspace(0.001, 0.1, H)
        self.dt_bias = _weight(self, H, dtype="float32",
                               init=I.Assign(dt + jnp.log(-jnp.expm1(-dt))))
        self.conv = _weight(self, cfg.linear_conv_kernel, cfg.conv_width)
        self.o_norm = _norm(cfg, cfg.linear_value_head_dim,
                            zero_centered=False)
        self.out = _weight(self, H * cfg.linear_value_head_dim, D)

    # -- the parts both calls share ----------------------------------------
    def _gates(self, x, valid):
        """Float32 ``g`` (log decay) and ``beta`` ``[B, T, H]``; a padding
        token gets the identity, ``g = 0`` and ``beta = 0``."""
        H = self.cfg.linear_num_heads
        ab = jnp.dot(x, jnp.asarray(self.ab.value),
                     preferred_element_type=_F32)
        g = -jnp.exp(self.A_log.value) * jax.nn.softplus(
            ab[..., :H] + self.dt_bias.value)
        beta = jax.nn.sigmoid(ab[..., H:])
        if self.cfg.allow_neg_eigval:
            beta = 2.0 * beta
        keep = valid[..., None]
        return jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)

    def _qkv_heads(self, y):
        """Conv output ``[..., conv_width]`` (float32) -> SiLU, the head
        split, and the l2 norms: ``q``, ``k`` ``[..., H_k, dk]``, ``v``
        ``[..., H, dv]``, float32."""
        cfg = self.cfg
        H, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        y = y * jax.nn.sigmoid(y)
        q = y[..., :H * dk].reshape(*y.shape[:-1], H, dk)
        k = y[..., H * dk:2 * H * dk].reshape(*y.shape[:-1], H, dk)
        v = y[..., 2 * H * dk:].reshape(*y.shape[:-1], cfg.linear_num_heads,
                                        -1)

        def unit(t):
            return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

        return unit(q) * dk ** -0.5, unit(k), v

    def _output(self, x, o):
        """``o`` float32 ``[B, T, H, dv]`` -> the layer's output."""
        gate = _mm(x, self.gate.value).astype(_F32).reshape(o.shape)
        y = self.o_norm(o) * (gate * jax.nn.sigmoid(gate))
        return _mm(y.reshape(*x.shape[:-1], -1).astype(x.dtype),
                   self.out.value)

    def _conv_prompt(self, pre):
        """Causal depthwise conv over ``pre`` ``[B, T, W]`` from position 0
        (zeros before it); tap ``j`` weighs the token ``K - 1 - j`` back.
        Returns the float32 conv and the zero-fronted input."""
        K, T = self.cfg.linear_conv_kernel, pre.shape[1]
        padded = jnp.pad(pre, ((0, 0), (K - 1, 0), (0, 0)))
        w = jnp.asarray(self.conv.value).astype(_F32)
        return sum(w[j] * padded[:, j:j + T].astype(_F32)
                   for j in range(K)), padded

    # -- calls ---------------------------------------------------------------
    def _prompt(self, x, positions):
        """Prompts from position 0 and the zero state: the layer's output,
        the state after each row's last real token, the zero-fronted conv
        input and which tokens are real."""
        valid = positions >= 0
        y, padded = self._conv_prompt(_mm(x, self.qkv.value))
        o, S = self._chunk(*self._qkv_heads(y), *self._gates(x, valid))
        return self._output(x, o), S, padded, valid

    def forward(self, x, positions):
        """A prompt from position 0, no cache."""
        with jax.named_scope(self.scope):
            return self._prompt(x, positions)[0]

    def admit(self, x, positions, kv, rows):
        """Prompts from position 0 into the slots' rows ``rows`` ``[R]``
        (already resolved: the drop row for an inert row)."""
        with jax.named_scope(self.scope):
            K = self.cfg.linear_conv_kernel
            out, S, padded, valid = self._prompt(x, positions)
            # the K - 1 rows before the row's end: tokens L - K + 1 .. L - 1
            # are rows L .. L + K - 2 of the zero-fronted input, zeros
            # where the prompt is shorter — never a padding token's row
            L = jnp.sum(valid, axis=1)
            idx = L[:, None] + jnp.arange(K - 1)[None, :]
            window = jnp.take_along_axis(padded, idx[..., None], axis=1)
            return out, {
                "state": kv["state"].at[rows].set(S),
                "conv": kv["conv"].at[rows].set(
                    window.astype(kv["conv"].dtype))}

    def decode(self, x, positions, kv):
        """One token of slot ``i`` in row ``i``: ``x`` ``[B, 1, D]``."""
        with jax.named_scope(self.scope):
            B = x.shape[0]
            valid = positions[:, 0] >= 0
            old = kv["conv"][:B]                         # [B, K - 1, W]
            window = jnp.concatenate(
                [old, _mm(x, self.qkv.value).astype(old.dtype)], axis=1)
            w = jnp.asarray(self.conv.value).astype(_F32)
            y = jnp.sum(w[None] * window.astype(_F32), axis=1)
            g, beta = self._gates(x[:, 0], valid)
            o, state = self._step(*self._qkv_heads(y), g, beta,
                                  kv["state"])
            conv = jax.lax.dynamic_update_slice(
                kv["conv"], jnp.where(valid[:, None, None], window[:, 1:],
                                      old), (0, 0, 0))
            return self._output(x, o[:, None]), {"state": state,
                                                 "conv": conv}


class FullAttention(Layer):
    """The ``full_attention`` mixer: ``H`` query heads over ``H_kv`` K/V
    heads of ``head_dim``, QK-norm, and by the configuration's options
    rotary on the leading dims of each head and an output gate."""

    kind = "full_attention"

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        #: None: this kind of layer carries no positions
        self.rope_theta = (cfg.rope_theta if self.kind in cfg.rope_kinds
                           else None)
        D, hd = cfg.hidden_size, cfg.head_dim
        self.q_width, self.kv_width = cfg.num_heads * hd, cfg.num_kv_heads * hd
        # [W_q (per head [q | gate] with an output gate) | W_k | W_v]
        self.qkv = _weight(self, D, (2 if cfg.attn_output_gate else 1)
                           * self.q_width + 2 * self.kv_width)
        per_head = cfg.qk_norm == "head"
        self.q_norm = _norm(cfg, hd if per_head else self.q_width)
        self.k_norm = _norm(cfg, hd if per_head else self.kv_width)
        self.out = _weight(self, self.q_width, D)

    def _qkv(self, x, positions):
        """``q`` ``[B, T, H * hd]``, ``k``, ``v`` ``[B, T, H_kv * hd]``
        normed and rotated as the cache holds them, and the output gate
        ``[B, T, H * hd]`` or None."""
        cfg, (B, T, _) = self.cfg, x.shape
        Q, KV, hd = self.q_width, self.kv_width, cfg.head_dim
        qkv = _mm(x, self.qkv.value)
        gate = None
        if cfg.attn_output_gate:
            qg = qkv[..., :2 * Q].reshape(B, T, cfg.num_heads, 2 * hd)
            q, gate = (t.reshape(B, T, Q) for t in (qg[..., :hd],
                                                    qg[..., hd:]))
            k, v = qkv[..., 2 * Q:2 * Q + KV], qkv[..., 2 * Q + KV:]
        else:
            q, k, v = qkv[..., :Q], qkv[..., Q:Q + KV], qkv[..., Q + KV:]
        if cfg.qk_norm == "head" or self.rope_theta is not None:
            q, k = q.reshape(B, T, -1, hd), k.reshape(B, T, -1, hd)
        q, k = self.q_norm(q), self.k_norm(k)
        if self.rope_theta is not None:
            q, k = (rope_rotate_half(t, positions[:, :, None],
                                     self.rope_theta, cfg.rotary_dim)
                    for t in (q, k))
        return q.reshape(B, T, Q), k.reshape(B, T, KV), v, gate

    def _heads(self, t):
        B, T, _ = t.shape
        return t.reshape(B, T, -1, self.cfg.head_dim).transpose(0, 2, 1, 3)

    def _merge(self, ctx, gate):
        B, _, T, _ = ctx.shape
        y = ctx.transpose(0, 2, 1, 3).reshape(B, T, -1)
        if gate is not None:
            y = y.astype(_F32) * jax.nn.sigmoid(gate.astype(_F32))
        return _mm(y.astype(self.cfg.dtype), self.out.value)

    @staticmethod
    def _attend(q, k, v, seen):
        """Plain softmax attention of head-major ``q`` ``[B, H, T, hd]`` over
        ``k``, ``v`` ``[B, H_kv, T, hd]`` under the ``[T, T]`` mask ``seen``
        (the path without a kernel)."""
        rep = q.shape[1] // k.shape[1]
        if rep > 1:
            k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=_F32) / math.sqrt(q.shape[3])
        s = jnp.where(seen, s, jnp.finfo(_F32).min)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                          preferred_element_type=_F32)

    def forward(self, x, positions):
        """A prompt from position 0 (causal by row), no cache."""
        with jax.named_scope("attn"):
            q, k, v, gate = self._qkv(x, positions)
            T = x.shape[1]
            return self._merge(self._attend(
                *map(self._heads, (q, k, v)),
                jnp.tril(jnp.ones((T, T), bool))), gate)

    def forward_paged(self, x, kv, write_page, write_off, gather_tab, mask,
                      walk, prompt, positions):
        """K and V rows land in the pages as ``models.gpt`` lays them; a
        prompt (``prompt``: an admission, which starts at position 0) then
        attends to its own K/V by the flash kernel where that runs, a
        decode row to its slot's pages."""
        with jax.named_scope("attn"):
            B, T, _ = x.shape
            q, k, v, gate = self._qkv(x, positions)
            pools = {n: kv[n].at[write_page, write_off].set(
                rows.reshape(B * T, self.kv_width).astype(kv[n].dtype))
                for n, rows in (("k", k), ("v", v))}
            q = self._heads(q)
            if prompt and _kernels(self.cfg.head_dim):
                from ..ops.flash_attention import flash_attention

                ctx = flash_attention(q, self._heads(k), self._heads(v),
                                      causal=True)
            else:
                ctx = paged_attention(q, pools["k"], pools["v"], gather_tab,
                                      mask, walk)  # no walk for a prompt
            return self._merge(ctx, gate), pools


class SlidingAttention(FullAttention):
    """The ``sliding_attention`` mixer: :class:`FullAttention`'s
    projections, QK-norm and heads under the window rule
    (``key_visible(kp, qp, W)``), its K and V in per-slot rings (module
    docstring)."""

    kind = "sliding_attention"

    def _prompt(self, x, positions):
        """Prompts from position 0: the context ``[B, H, T, hd]``, the K
        and V rows ``[B, T, H_kv * hd]`` as a ring holds them, the gate."""
        q, k, v, gate = self._qkv(x, positions)
        W, (qh, kh, vh) = self.cfg.sliding_window, map(self._heads, (q, k, v))
        if _kernels(self.cfg.head_dim):
            from ..ops.flash_attention import flash_attention

            return flash_attention(qh, kh, vh, causal=True,
                                   window=W), k, v, gate
        t = jnp.arange(qh.shape[2], dtype=jnp.int32)
        return self._attend(qh, kh, vh, key_visible(
            t[None, :], t[:, None], W)), k, v, gate

    def forward(self, x, positions):
        """A prompt from position 0, no cache."""
        with jax.named_scope("win"):
            ctx, _, _, gate = self._prompt(x, positions)
            return self._merge(ctx, gate)

    def admit(self, x, positions, kv, rows):
        """Prompts from position 0 into the rings of the slots' rows
        ``rows`` ``[R]``: ring row ``r`` takes the row's last real token
        at a position that is ``r`` modulo ``W`` (a ring row no token of
        the prompt maps to takes token 0's: masked at every later read,
        its position being negative then)."""
        with jax.named_scope("win"):
            ctx, k, v, gate = self._prompt(x, positions)
            last = jnp.sum(positions >= 0, axis=1) - 1        # [R]
            idx = jnp.maximum(ring_positions(jnp.maximum(last, 0),
                                             self.cfg.sliding_window), 0)
            return self._merge(ctx, gate), {
                n: kv[n].at[rows].set(jnp.take_along_axis(
                    t, idx[..., None], axis=1).astype(kv[n].dtype))
                for n, t in (("ring_k", k), ("ring_v", v))}

    def decode(self, x, positions, kv):
        """One token of slot ``i`` in row ``i``: its K and V go into ring
        row ``qp % W`` (a padding row's into the drop row), and the query
        attends to the slot's ``W`` rows, each at the position
        :func:`ring_positions` gives it."""
        with jax.named_scope("win"):
            cfg, B = self.cfg, x.shape[0]
            W, hd = cfg.sliding_window, cfg.head_dim
            q, k, v, gate = self._qkv(x, positions)
            qp = positions[:, 0]
            slot = jnp.where(qp >= 0, jnp.arange(B, dtype=jnp.int32), B)
            rings = {n: kv[n].at[slot, jnp.mod(qp, W)].set(
                t[:, 0].astype(kv[n].dtype))
                for n, t in (("ring_k", k), ("ring_v", v))}
            held = ring_positions(qp, W)                      # [B, W]
            tab = jnp.arange(B, dtype=jnp.int32)[:, None]     # slot i: page i
            q = self._heads(q)
            if _paged_flash(hd, W):
                ctx = paged_flash_decode(
                    q, rings["ring_k"], rings["ring_v"], tab, held,
                    positions, (qp >= 0).astype(jnp.int32),
                    name="window_decode")
            else:
                ctx = paged_attention(
                    q, rings["ring_k"], rings["ring_v"], tab,
                    key_visible(held[:, None, :], positions[:, :, None], W))
            return self._merge(ctx, gate), rings


_MIXERS = {"linear_attention": GatedDeltaNet, "full_attention": FullAttention,
           "sliding_attention": SlidingAttention}


class HybridBlock(Layer):
    def __init__(self, cfg: HybridConfig, kind: str, ffn: str = "dense"):
        super().__init__()
        self.kind = kind
        self.pre_norm = cfg.block_norm == "pre"
        self.mixer = _MIXERS[kind](cfg)
        self.norm1 = _norm(cfg, cfg.hidden_size)
        if ffn == "moe":
            self.mlp = DroplessMoE(cfg.hidden_size, dtype=cfg.dtype,
                                   init_std=cfg.init_std, **cfg.moe)
        else:
            self.mlp = GatedMLP(cfg.hidden_size, cfg.intermediate_size,
                                cfg.dtype, cfg.init_std)
        self.norm2 = _norm(cfg, cfg.hidden_size)

    def enter(self, x):
        """What the mixer reads."""
        return self.norm1(x) if self.pre_norm else x

    def finish(self, x, mixed):
        """The block after its mixer: the residuals, the other norm(s) and
        the FFN."""
        if self.pre_norm:
            h = x + mixed
            return h + self.mlp(self.norm2(h))
        h = x + self.norm1(mixed)
        return h + self.norm2(self.mlp(h))

    def forward(self, x, positions):
        return self.finish(x, self.mixer(self.enter(x), positions))


class HybridModel(Layer):
    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = _weight(self, cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList([
            HybridBlock(cfg, kind, ffn)
            for kind, ffn in zip(cfg.layer_types, cfg.ffn_types)])
        self.norm_f = _norm(cfg, cfg.hidden_size)

    def forward(self, input_ids):
        """``[B, S]`` ids from position 0 -> ``[B, S, D]``, causal."""
        ids = jnp.asarray(input_ids, jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32),
                               ids.shape)
        x = jnp.take(jnp.asarray(self.embed.value), ids, axis=0)
        for blk in self.blocks:
            x = blk(x, pos)
        return self.norm_f(x)

    # -- the two caches: the model owns both layouts ---------------------------
    def init_paged_cache(self, num_pages: int, page_size: int, dtype=None,
                         slots=None):
        """Per full layer K and V pools ``[P + 1, page, H_kv * hd]``; per
        linear layer ``state`` ``[slots + 1, H, dk, dv]`` float32 and
        ``conv`` ``[slots + 1, K - 1, conv_width]``; per sliding layer
        ``ring_k`` and ``ring_v`` ``[slots + 1, W, H_kv * hd]``."""
        cfg = self.cfg
        if slots is None:
            raise InvalidArgumentError(
                "a model with slot state needs init_paged_cache(slots=): "
                "the engine's batch size")
        pool = (int(num_pages) + 1, int(page_size),
                cfg.num_kv_heads * cfg.head_dim)
        rows = int(slots) + 1

        def layer(kind):
            if kind == "full_attention":
                return {"k": jnp.zeros(pool, dtype or cfg.dtype),
                        "v": jnp.zeros(pool, dtype or cfg.dtype)}
            if kind == "sliding_attention":
                ring = (rows, cfg.sliding_window, pool[2])
                return {"ring_k": jnp.zeros(ring, dtype or cfg.dtype),
                        "ring_v": jnp.zeros(ring, dtype or cfg.dtype)}
            return {"state": jnp.zeros(
                        (rows, cfg.linear_num_heads, cfg.linear_key_head_dim,
                         cfg.linear_value_head_dim), _F32),
                    "conv": jnp.zeros((rows, cfg.linear_conv_kernel - 1,
                                       cfg.conv_width), cfg.dtype)}

        return {"layers": [layer(kind) for kind in cfg.layer_types]}

    def copy_pages(self, cache, src, dst):
        """Copy whole pages ``src[i] -> dst[i]`` of every page pool (slot
        state has no pages and is left alone)."""
        src = jnp.maximum(jnp.asarray(src, jnp.int32), 0)
        dst = jnp.asarray(dst, jnp.int32)

        def copy(kv):
            if "k" not in kv:
                return kv
            to = jnp.where(dst >= 0, dst, kv["k"].shape[0] - 1)
            return {n: t.at[to].set(t[src]) for n, t in kv.items()}

        return {"layers": [copy(kv) for kv in cache["layers"]]}

    def forward_paged(self, input_ids, positions, pos_map, table, cache,
                      slots=None):
        """The contract of ``GPTModel.forward_paged`` for the page pools,
        and of the module docstring for the slot state: ``slots`` ``[R]``
        makes the call an admission of whole prompts from position 0."""
        cfg = self.cfg
        positions = jnp.asarray(positions, jnp.int32)
        pos_map = jnp.asarray(pos_map, jnp.int32)
        table = jnp.asarray(table, jnp.int32)
        prompt = slots is not None
        if not prompt and positions.shape[1] != 1:
            raise InvalidArgumentError(
                "slot state decodes one token a row: a wider step would "
                "have to roll the state back for a rejected draft")
        full = next((kv["k"] for kv in cache["layers"] if "k" in kv), None)
        x = jnp.take(jnp.asarray(self.embed.value),
                     jnp.asarray(input_ids, jnp.int32), axis=0)
        if full is not None:  # what the layers with pages share
            P, page, G = full.shape[0] - 1, full.shape[1], table.shape[1]
            C = G * page
            ring = jnp.where(positions >= 0, positions % C, -1)
            g = jnp.clip(ring // page, 0, G - 1)
            phys = jnp.take_along_axis(table, g, axis=1)
            # padding tokens and unmapped pages write into the drop page P
            phys = jnp.where((ring >= 0) & (phys >= 0), phys, P)
            mask = key_visible(pos_map[:, None, :], positions[:, :, None], C)
            walk = None
            if not prompt and _paged_flash(cfg.head_dim, page):
                walk = (pos_map, positions, sweep_bound(mask, page))
            paged = (phys.reshape(-1), jnp.clip(ring % page, 0, page - 1)
                     .reshape(-1), jnp.maximum(table, 0), mask, walk, prompt,
                     positions)
        if prompt:
            slots = jnp.asarray(slots, jnp.int32)
            # the drop row of the per-slot tensors, whichever kind has them
            drop = next((t.shape[0] - 1 for kv in cache["layers"]
                         if "k" not in kv for t in kv.values()), 0)
            rows = jnp.where(slots >= 0, slots, drop)
        layers = []
        for blk, kv in zip(self.blocks, cache["layers"]):
            y = blk.enter(x)
            if blk.kind == "full_attention":
                mixed, kv = blk.mixer.forward_paged(y, kv, *paged)
            elif prompt:
                mixed, kv = blk.mixer.admit(y, positions, kv, rows)
            else:
                mixed, kv = blk.mixer.decode(y, positions, kv)
            x = blk.finish(x, mixed)
            layers.append(kv)
        return self.norm_f(x), {"layers": layers}


class HybridForCausalLM(Layer):
    """The decoder with its untied head; answers the serving-model protocol
    and declares ``slot_state``: its cache holds per-slot rows beside the
    pages, so the engine hands ``init_paged_cache`` its batch size and
    every admission the rows' slot numbers.

    A subclass names another ``decoder`` (``models/kimi_linear.py``): a
    ``Layer`` built from ``cfg`` alone that answers ``forward(ids)``,
    ``init_paged_cache(num_pages, page_size, dtype, slots)``,
    ``copy_pages(cache, src, dst)`` and ``forward_paged(ids, positions,
    pos_map, table, cache, slots)``, each returning hidden states before
    the head.  Its ``cfg`` supplies ``hidden_size``, ``vocab_size``,
    ``max_position``, ``experts_held``, ``dtype`` and ``init_std``; the
    subclass overrides :meth:`slot_state_bytes`, which counts this
    decoder's layer kinds."""

    decoder = HybridModel

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.model = self.decoder(cfg)
        self.head = _weight(self, cfg.hidden_size, cfg.vocab_size)

    max_position = property(lambda self: self.cfg.max_position)
    #: experts the engine counts routed tokens over: those HELD here
    moe_experts = property(lambda self: self.cfg.experts_held)
    lora_capacity = 0
    slot_state = True

    def slot_state_bytes(self) -> int:
        """Bytes of slot state one slot holds over all the linear layers
        (what a decode step reads and writes for it) and all the sliding
        layers' rings."""
        cfg, item = self.cfg, jnp.dtype(self.cfg.dtype).itemsize
        kinds = cfg.layer_types
        return (kinds.count("linear_attention") * (
            4 * cfg.linear_num_heads * cfg.linear_key_head_dim
            * cfg.linear_value_head_dim
            + item * (cfg.linear_conv_kernel - 1) * cfg.conv_width)
            + kinds.count("sliding_attention") * 2 * item
            * (cfg.sliding_window or 0) * cfg.num_kv_heads * cfg.head_dim)

    def init_paged_cache(self, num_pages, page_size, dtype=None, slots=None):
        return self.model.init_paged_cache(num_pages, page_size, dtype,
                                           slots)

    def copy_pages(self, cache, src, dst):
        return self.model.copy_pages(cache, src, dst)

    def _logits(self, h):
        return jnp.dot(h, jnp.asarray(self.head.value),
                       preferred_element_type=_F32)

    def forward(self, input_ids):
        """``[B, S]`` -> float32 logits ``[B, S, V]``."""
        return self._logits(self.model(input_ids))

    def forward_paged(self, input_ids, positions, pos_map, table, cache,
                      gather_last=None, adapter_ids=None, slots=None):
        """Float32 logits ``[B, T, V]``, or ``[B, V]`` of the row
        ``gather_last - 1`` of each sequence, and the new cache."""
        del adapter_ids  # there are no adapters here
        h, cache = self.model.forward_paged(input_ids, positions, pos_map,
                                            table, cache, slots)
        if gather_last is not None:
            idx = jnp.maximum(jnp.asarray(gather_last, jnp.int32) - 1, 0)
            h = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
        return self._logits(h), cache
