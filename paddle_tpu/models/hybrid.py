"""A decoder whose layers are of two kinds, by a per-layer ``layer_types``
list: ``linear_attention`` — the gated delta rule (``ops/gated_delta.py``)
over a per-head float32 matrix state, behind a short causal depthwise
convolution — and ``full_attention`` — causal softmax attention with
QK-norm and no rotary (positions reach it through the recurrent layers).

Block, both kinds (the reordered norm): ``h = x + norm(Mix(x))``, ``y = h +
norm(MLP(h))``, gated-SiLU MLP, final norm, untied head, no bias anywhere.

``linear_attention``, per head of ``d_k`` keys and ``d_v`` values:

    [q~ | k~ | v~] = x W_qkv;  causal depthwise conv of K taps, then SiLU
    q = q' / |q'| * d_k^-1/2,  k = k' / |k'|
    beta = 2 sigmoid(x W_b)  (1 sigmoid without ``allow_neg_eigval``)
    g = -exp(A_log) softplus(x W_a + dt_bias)
    S_t = e^g S_{t-1} + beta k (v - (e^g S_{t-1})^T k)^T;  o = S_t^T q
    y = RMSNorm(o) * silu(x W_g);  y W_o

Two kinds of cache, so the model owns the layout of both and declares
``slot_state`` (the serving-model protocol, ``serving/generation.py``):

* a full layer holds K and V page pools ``[P + 1, page, H * hd]`` in
  ``GPTModel``'s stored order, read by the same ``paged_decode`` kernel;
* a linear layer holds, PER SLOT and not per page, ``state`` ``[B + 1, H,
  d_k, d_v]`` float32 and ``conv`` ``[B + 1, K - 1, conv_width]`` (the last
  ``K - 1`` rows of ``[q~ | k~ | v~]``); row ``B`` is the write-drop row, as
  page ``P`` is the write-drop page.

An ADMISSION (``forward_paged(..., slots=[R])``) takes whole prompts from
position 0: it starts every row from the zero state, whatever its slot
held, and writes the state and the conv window after the row's last real
token into slot ``slots[r]`` (``-1``: the drop row).  A DECODE call
(``slots=None``, one token a row) continues slot ``i`` in row ``i``.  A
padding token (position ``-1``) is the identity on both: it neither decays
nor writes the state and does not enter the conv window.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.errors import InvalidArgumentError
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..ops import autotune as _at
from ..ops.gated_delta import gated_delta_chunk, gated_delta_step
from ..ops.paged_attention import (key_visible, paged_attention,
                                   paged_flash_eligible, sweep_bound)
from .latent_moe import GatedMLP, _mm

__all__ = ["HybridConfig", "HybridModel", "HybridForCausalLM"]

_F32 = jnp.float32
LAYER_TYPES = ("linear_attention", "full_attention")


def _kernels(head_dim=None) -> bool:
    """Gate of the admission's flash kernel (a TPU, a one-device mesh)."""
    return _at.fused_epilogues_eligible(head_dim)


def _paged_flash(head_dim, page_size) -> bool:
    """Gate of the decode's ``paged_decode`` kernel, as ``models.gpt``'s."""
    return paged_flash_eligible(head_dim, page_size)


class HybridConfig:
    def __init__(self, vocab_size, hidden_size, num_heads, intermediate_size,
                 layer_types, linear_num_heads, linear_key_head_dim,
                 linear_value_head_dim, linear_conv_kernel=4,
                 allow_neg_eigval=True, rms_norm_eps=1e-6, rope_theta=None,
                 max_position=4096, dtype="bfloat16", init_std=0.02):
        if rope_theta is not None:
            raise InvalidArgumentError(
                "HybridConfig: rope_theta must be None — the full-attention "
                "layers carry no rotary, positions come from the recurrent "
                "layers")
        bad = [t for t in layer_types if t not in LAYER_TYPES]
        if bad or not layer_types:
            raise InvalidArgumentError(
                f"layer_types must be a non-empty list of {LAYER_TYPES}, "
                f"got {bad or layer_types!r}")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_heads = int(num_heads)
        self.intermediate_size = int(intermediate_size)
        self.layer_types = tuple(layer_types)
        self.linear_num_heads = int(linear_num_heads)
        self.linear_key_head_dim = int(linear_key_head_dim)
        self.linear_value_head_dim = int(linear_value_head_dim)
        self.linear_conv_kernel = int(linear_conv_kernel)
        self.allow_neg_eigval = bool(allow_neg_eigval)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = None
        self.max_position = int(max_position)
        self.dtype = dtype
        self.init_std = float(init_std)

    num_layers = property(lambda self: len(self.layer_types))
    head_dim = property(lambda self: self.hidden_size // self.num_heads)

    @property
    def conv_width(self) -> int:
        """Channels under the convolution: ``[q~ | k~ | v~]``."""
        return self.linear_num_heads * (2 * self.linear_key_head_dim
                                        + self.linear_value_head_dim)


def _weight(layer, *shape, dtype=None, init=None):
    cfg = layer.cfg
    return layer.create_parameter(
        shape, dtype=dtype or cfg.dtype,
        default_initializer=init or I.Normal(std=cfg.init_std))


class GatedDeltaNet(Layer):
    """The ``linear_attention`` mixer."""

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
        self.o_norm = nn.RMSNorm(cfg.linear_value_head_dim, cfg.rms_norm_eps,
                                 cfg.dtype)
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
        split, and the l2 norms: ``q``, ``k`` ``[..., H, dk]``, ``v``
        ``[..., H, dv]``, float32."""
        cfg = self.cfg
        H, dk = cfg.linear_num_heads, cfg.linear_key_head_dim
        y = y * jax.nn.sigmoid(y)
        q = y[..., :H * dk].reshape(*y.shape[:-1], H, dk)
        k = y[..., H * dk:2 * H * dk].reshape(*y.shape[:-1], H, dk)
        v = y[..., 2 * H * dk:].reshape(*y.shape[:-1], H, -1)

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
        o, S = gated_delta_chunk(*self._qkv_heads(y), *self._gates(x, valid))
        return self._output(x, o), S, padded, valid

    def forward(self, x, positions):
        """A prompt from position 0, no cache."""
        with jax.named_scope("gdn"):
            return self._prompt(x, positions)[0]

    def admit(self, x, positions, kv, rows):
        """Prompts from position 0 into the slots' rows ``rows`` ``[R]``
        (already resolved: the drop row for an inert row)."""
        with jax.named_scope("gdn"):
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
        with jax.named_scope("gdn"):
            B = x.shape[0]
            valid = positions[:, 0] >= 0
            old = kv["conv"][:B]                         # [B, K - 1, W]
            window = jnp.concatenate(
                [old, _mm(x, self.qkv.value).astype(old.dtype)], axis=1)
            w = jnp.asarray(self.conv.value).astype(_F32)
            y = jnp.sum(w[None] * window.astype(_F32), axis=1)
            g, beta = self._gates(x[:, 0], valid)
            o, state = gated_delta_step(*self._qkv_heads(y), g, beta,
                                        kv["state"])
            conv = jax.lax.dynamic_update_slice(
                kv["conv"], jnp.where(valid[:, None, None], window[:, 1:],
                                      old), (0, 0, 0))
            return self._output(x, o[:, None]), {"state": state,
                                                 "conv": conv}


class FullAttention(Layer):
    """The ``full_attention`` mixer: QK-norm over the whole projection,
    heads of ``hidden / heads``, no rotary."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        D = cfg.hidden_size
        self.qkv = _weight(self, D, 3 * D)
        self.q_norm = nn.RMSNorm(D, cfg.rms_norm_eps, cfg.dtype)
        self.k_norm = nn.RMSNorm(D, cfg.rms_norm_eps, cfg.dtype)
        self.out = _weight(self, D, D)

    def _qkv(self, x):
        D = self.cfg.hidden_size
        qkv = _mm(x, self.qkv.value)
        return (self.q_norm(qkv[..., :D]), self.k_norm(qkv[..., D:2 * D]),
                qkv[..., 2 * D:])

    def _heads(self, t):
        B, T, _ = t.shape
        return t.reshape(B, T, self.cfg.num_heads, -1).transpose(0, 2, 1, 3)

    def _merge(self, ctx):
        B, _, T, _ = ctx.shape
        return _mm(ctx.transpose(0, 2, 1, 3).reshape(B, T, -1).astype(
            self.cfg.dtype), self.out.value)

    def forward(self, x, positions):
        del positions  # a prompt from position 0: causal by row
        with jax.named_scope("attn"):
            q, k, v = map(self._heads, self._qkv(x))
            T, hd = q.shape[2], q.shape[3]
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           preferred_element_type=_F32) / math.sqrt(hd)
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s,
                          jnp.finfo(_F32).min)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            return self._merge(jnp.einsum("bhqk,bhkd->bhqd", p, v,
                                          preferred_element_type=_F32))

    def forward_paged(self, x, kv, write_page, write_off, gather_tab, mask,
                      walk, prompt):
        """K and V rows land in the pages as ``models.gpt`` lays them; a
        prompt (``prompt``: an admission, which starts at position 0) then
        attends to its own K/V by the flash kernel where that runs, a
        decode row to its slot's pages."""
        with jax.named_scope("attn"):
            B, T, D = x.shape
            q, k, v = self._qkv(x)
            pools = {n: kv[n].at[write_page, write_off].set(
                rows.reshape(B * T, D).astype(kv[n].dtype))
                for n, rows in (("k", k), ("v", v))}
            q = self._heads(q)
            if prompt and _kernels(self.cfg.head_dim):
                from ..ops.flash_attention import flash_attention

                ctx = flash_attention(q, self._heads(k), self._heads(v),
                                      causal=True)
            else:
                ctx = paged_attention(q, pools["k"], pools["v"], gather_tab,
                                      mask, walk)  # no walk for a prompt
            return self._merge(ctx), pools


class HybridBlock(Layer):
    def __init__(self, cfg: HybridConfig, kind: str):
        super().__init__()
        dt = cfg.dtype
        self.kind = kind
        self.mixer = (GatedDeltaNet(cfg) if kind == "linear_attention"
                      else FullAttention(cfg))
        self.norm1 = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, dt)
        self.mlp = GatedMLP(cfg.hidden_size, cfg.intermediate_size, dt,
                            cfg.init_std)
        self.norm2 = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, dt)

    def finish(self, x, mixed):
        """The block after its mixer: both reordered norms and the MLP."""
        h = x + self.norm1(mixed)
        return h + self.norm2(self.mlp(h))

    def forward(self, x, positions):
        return self.finish(x, self.mixer(x, positions))


class HybridModel(Layer):
    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = _weight(self, cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList([HybridBlock(cfg, kind)
                                    for kind in cfg.layer_types])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                 cfg.dtype)

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
        """Per full layer K and V pools ``[P + 1, page, hidden]``; per
        linear layer ``state`` ``[slots + 1, H, dk, dv]`` float32 and
        ``conv`` ``[slots + 1, K - 1, conv_width]``."""
        cfg = self.cfg
        if slots is None:
            raise InvalidArgumentError(
                "a model with slot state needs init_paged_cache(slots=): "
                "the engine's batch size")
        pool = (int(num_pages) + 1, int(page_size), cfg.hidden_size)
        rows = int(slots) + 1

        def layer(kind):
            if kind == "full_attention":
                return {"k": jnp.zeros(pool, dtype or cfg.dtype),
                        "v": jnp.zeros(pool, dtype or cfg.dtype)}
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
        full = next(kv for kv in cache["layers"] if "k" in kv)["k"]
        P, page, G = full.shape[0] - 1, full.shape[1], table.shape[1]
        C = G * page
        x = jnp.take(jnp.asarray(self.embed.value),
                     jnp.asarray(input_ids, jnp.int32), axis=0)
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
                 .reshape(-1), jnp.maximum(table, 0), mask, walk, prompt)
        if prompt:
            slots = jnp.asarray(slots, jnp.int32)
            drop = next(kv for kv in cache["layers"]
                        if "state" in kv)["state"].shape[0] - 1
            rows = jnp.where(slots >= 0, slots, drop)
        layers = []
        for blk, kv in zip(self.blocks, cache["layers"]):
            if blk.kind == "full_attention":
                mixed, kv = blk.mixer.forward_paged(x, kv, *paged)
            elif prompt:
                mixed, kv = blk.mixer.admit(x, positions, kv, rows)
            else:
                mixed, kv = blk.mixer.decode(x, positions, kv)
            x = blk.finish(x, mixed)
            layers.append(kv)
        return self.norm_f(x), {"layers": layers}


class HybridForCausalLM(Layer):
    """The decoder with its untied head; answers the serving-model protocol
    and declares ``slot_state``: its cache holds per-slot rows beside the
    pages, so the engine hands ``init_paged_cache`` its batch size and
    every admission the rows' slot numbers."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        self.model = HybridModel(cfg)
        self.head = _weight(self, cfg.hidden_size, cfg.vocab_size)

    max_position = property(lambda self: self.cfg.max_position)
    moe_experts = 0
    lora_capacity = 0
    slot_state = True

    def slot_state_bytes(self) -> int:
        """Bytes of slot state one slot holds over all the linear layers
        (what a decode step reads and writes for it)."""
        cfg = self.cfg
        n = sum(kind == "linear_attention" for kind in cfg.layer_types)
        return n * (4 * cfg.linear_num_heads * cfg.linear_key_head_dim
                    * cfg.linear_value_head_dim
                    + jnp.dtype(cfg.dtype).itemsize
                    * (cfg.linear_conv_kernel - 1) * cfg.conv_width)

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
