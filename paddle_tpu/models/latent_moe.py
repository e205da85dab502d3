"""A decoder assembled from parts: latent (compressed) K/V attention,
RMSNorm, interleaved rotary positions, gated-SiLU MLPs, routed experts
with a shared expert, bias-free linears and an untied head.

The block is ``h = x + Attn(norm(x)); y = h + FFN(norm(h))``; the first
``first_dense_layers`` blocks carry a dense gated MLP, the rest a
:class:`paddle_tpu.moe.DroplessMoE`.  Attention is multi-head LATENT
attention (DeepSeek-V2, arXiv:2405.04434 section 2.1): queries go through a
rank-``q_lora_rank`` bottleneck (``None``: one full-rank projection); keys
and values are expanded from ONE ``kv_lora_rank``-wide latent per token, and
one ``qk_rope_head_dim``-wide rotary key is shared by every head
(``rope_theta=None``: nothing is rotated, the shared key and the queries'
``qk_rope_head_dim`` dims are used as projected).  So the cache holds, per
token and layer, ``kv_lora_rank + qk_rope_head_dim`` values (the normed
latent and the shared key) instead of ``heads x (qk + v)``.

Two formulations of the same attention:

* expanded (prefill, ``T > 1``): K and V are expanded from the latents
  the slot's pages hold and a blocked attention runs over them: on a TPU
  the flash kernel of ``ops/latent_attention.py``, elsewhere the same
  function over query blocks in XLA; either way its temporaries do not
  grow with the prompt bucket;
* absorbed (decode, ``T == 1``): the K expansion is folded into the query
  (``q' = q_nope W^K``) and the V expansion is applied after the
  probabilities (``(P c) W^V``), so a step never materialises per-head
  K/V.  On a TPU it gathers no view either: the ``latent_decode`` kernel
  of ``ops/latent_attention.py`` walks each slot's page-table row up to
  its sweep bound and attends to the latent rows where they lie in the
  pool, each page fetched once (scores from a row's every lane, values
  its first ``kv_lora_rank``); elsewhere (the CPU, a mesh of several
  devices) the same products run over a gathered ``[slots, C, width]``
  view of every slot's whole window, the reference the kernel is held to.

The model owns its page layout: :meth:`LatentMoEModel.init_paged_cache`
returns ``{"layers": [{"latent": [P + 1, page, page_width]}]}`` and the page
ops are written over the cache's leaves, whatever they are.  The serving
engine (``serving/generation.py``) talks to :class:`LatentMoEForCausalLM`
through the same protocol ``GPTForCausalLM`` answers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..moe import DroplessMoE
from ..moe.layer import gated_mlp
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..ops.latent_attention import latent_decode, latent_decode_eligible
from ..ops.paged_attention import key_visible, sweep_bound

__all__ = ["LatentMoEConfig", "LatentMoEModel", "LatentMoEForCausalLM",
           "rope_interleaved"]

_F32 = jnp.float32
#: query rows per block of the expanded attention: the [B, H, block, C]
#: float32 scores are its largest temporary
_Q_BLOCK = 256


class LatentMoEConfig:
    def __init__(self, vocab_size, hidden_size, num_layers, num_heads,
                 q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, intermediate_size,
                 moe_intermediate_size, num_experts, experts_per_token,
                 shared_experts=1, first_dense_layers=1,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 rms_norm_eps=1e-6, rope_theta=10000.0, max_position=4096,
                 dtype="bfloat16", init_std=0.02):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.q_lora_rank = None if q_lora_rank is None else int(q_lora_rank)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_experts = int(num_experts)
        self.experts_per_token = int(experts_per_token)
        self.shared_experts = int(shared_experts)
        self.first_dense_layers = int(first_dense_layers)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = None if rope_theta is None else float(rope_theta)
        self.max_position = int(max_position)
        self.dtype = dtype
        self.init_std = float(init_std)

    @property
    def latent_width(self) -> int:
        """Values cached per token and layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def page_width(self) -> int:
        """A token's row in a page: the latent, padded to whole 128-lane
        tiles.  With a ragged last tile (576 = 4.5 x 128) the TPU compiler
        stores the pool pages-minor-most to save the padding and every
        program relayouts the whole pool on the way in and out."""
        return -(-self.latent_width // 128) * 128


def _mm(x, w):
    """``x @ w`` accumulated in float32, rounded to ``x``'s dtype."""
    return jnp.dot(x, jnp.asarray(w),
                   preferred_element_type=_F32).astype(x.dtype)


def rope_interleaved(x, positions, theta):
    """Rotary positions over ADJACENT pairs ``(x[2i], x[2i+1])`` of the
    last axis (``rope_interleave``), angle ``pos * theta^(-2i/d)``, in
    float32.  ``positions`` broadcasts against ``x``'s leading axes;
    negative (padding) positions rotate as position 0."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)
    ang = jnp.maximum(positions, 0).astype(_F32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(_F32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _walks_pages(pool, T) -> bool:
    """Does a paged call of ``T`` tokens a row attend through the
    ``latent_decode`` kernel?  One indirection so tests can steer it."""
    return latent_decode_eligible(pool, T)


def _visible(kpos, qpos, ring):
    """``[B, Tq, Tk]``: key position is written, not after the query, and
    inside the ring window (the paged mask of ``GPTModel.forward_paged``)."""
    return key_visible(kpos[:, None, :], qpos[:, :, None], ring)


class GatedMLP(Layer):
    """``(silu(x W_gate) * (x W_up)) W_down``, no biases."""

    def __init__(self, hidden_size, width, dtype, init_std=0.02):
        super().__init__()
        init = I.Normal(std=init_std)
        self.gate = self.create_parameter((hidden_size, width), dtype=dtype,
                                          default_initializer=init)
        self.up = self.create_parameter((hidden_size, width), dtype=dtype,
                                        default_initializer=init)
        self.down = self.create_parameter((width, hidden_size), dtype=dtype,
                                          default_initializer=init)

    def forward(self, x):
        with jax.named_scope("dense_mlp"):
            return gated_mlp(x, self.gate.value, self.up.value,
                             self.down.value).astype(x.dtype)


class LatentAttention(Layer):
    """Reads of ``cfg`` (a :class:`LatentMoEConfig`, or another model's
    config with these names, as ``models/kimi_linear.py``'s):
    ``hidden_size``, ``num_heads``, ``q_lora_rank`` (``None``: no
    bottleneck), ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta`` (``None``: no
    rotation), ``rms_norm_eps``, ``dtype``, ``init_std`` and the two
    derived widths ``latent_width`` and ``page_width``."""

    def __init__(self, cfg: LatentMoEConfig):
        super().__init__()
        self.cfg = cfg
        D, H, dt = cfg.hidden_size, cfg.num_heads, cfg.dtype
        init = I.Normal(std=cfg.init_std)

        def p(*shape):
            return self.create_parameter(shape, dtype=dt,
                                         default_initializer=init)

        q_width = H * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        if cfg.q_lora_rank is None:
            self.q = p(D, q_width)
        else:
            self.q_a = p(D, cfg.q_lora_rank)
            self.q_norm = nn.RMSNorm(cfg.q_lora_rank, cfg.rms_norm_eps, dt)
            self.q_b = p(cfg.q_lora_rank, q_width)
        self.kv_a = p(D, cfg.latent_width)
        self.kv_norm = nn.RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps, dt)
        self.kv_b = p(cfg.kv_lora_rank,
                      H * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.out = p(H * cfg.v_head_dim, D)
        self.scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim
                                     + cfg.qk_rope_head_dim)

    # -- the two halves every formulation shares ---------------------------
    def _rotate(self, t, positions):
        theta = self.cfg.rope_theta
        return t if theta is None else rope_interleaved(t, positions, theta)

    def _queries(self, x, positions):
        cfg = self.cfg
        B, T, _ = x.shape
        if cfg.q_lora_rank is None:
            q = _mm(x, self.q.value)
        else:
            q = _mm(self.q_norm(_mm(x, self.q_a.value)), self.q_b.value)
        q = q.reshape(B, T, cfg.num_heads, -1)
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_rope = self._rotate(q[..., cfg.qk_nope_head_dim:],
                              positions[:, :, None])
        return q_nope, q_rope

    def _latent(self, x, positions):
        """What the cache holds of each token: ``[c_kv | k_rope]``."""
        r = self.cfg.kv_lora_rank
        kva = _mm(x, self.kv_a.value)
        return jnp.concatenate(
            [self.kv_norm(kva[..., :r]),
             self._rotate(kva[..., r:], positions)], axis=-1)

    def _kv_b_heads(self):
        cfg = self.cfg
        w = jnp.asarray(self.kv_b.value).reshape(
            cfg.kv_lora_rank, cfg.num_heads, -1)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    # -- expanded: K and V per head from the latents -------------------------
    def expanded(self, q_nope, q_rope, latent, qpos, kpos, ring):
        """``latent`` ``[B, S, width]`` at key positions ``kpos`` ``[B, S]``;
        queries ``[B, T, H, .]`` at ``qpos`` ``[B, T]``.  Returns ``[B, T,
        H * v]``.  Blocked over queries: a block's scores are the only
        ``T x S``-shaped temporary, and ``T`` is the block."""
        cfg = self.cfg
        B, T, H, dn = q_nope.shape
        r, dv = cfg.kv_lora_rank, cfg.v_head_dim
        kv = _mm(latent[..., :r], self.kv_b.value).reshape(
            B, -1, H, dn + dv)
        k_nope, v, k_rope = kv[..., :dn], kv[..., dn:], latent[..., r:]
        from ..ops.latent_attention import (latent_prefill_attention,
                                            latent_prefill_eligible)

        if latent_prefill_eligible(dn, cfg.qk_rope_head_dim, dv, T,
                                   latent.shape[1]):
            # TPU: the flash kernel keeps a tile of scores in VMEM and
            # skips the tiles no query of it can see
            def heads(t):
                return t.transpose(0, 2, 1, 3)

            ctx = latent_prefill_attention(
                heads(q_nope), heads(q_rope), heads(k_nope), k_rope,
                heads(v), qpos, kpos, ring, self.scale)
            return heads(ctx).reshape(B, T, H * dv)
        bq = math.gcd(T, _Q_BLOCK)
        nb = T // bq

        def block(args):
            qn, qr, qp = args
            s = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope,
                            preferred_element_type=_F32)
                 + jnp.einsum("bqhr,bkr->bhqk", qr, k_rope,
                              preferred_element_type=_F32)) * self.scale
            s = jnp.where(_visible(kpos, qp, ring)[:, None], s,
                          jnp.finfo(_F32).min)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                              preferred_element_type=_F32).astype(v.dtype)

        def split(t):
            return jnp.moveaxis(t.reshape(B, nb, bq, *t.shape[2:]), 1, 0)

        if nb == 1:
            ctx = block((q_nope, q_rope, qpos))
        else:
            ctx = jnp.moveaxis(jax.lax.map(
                block, (split(q_nope), split(q_rope), split(qpos))), 0, 1)
        return ctx.reshape(B, T, H * dv)

    # -- absorbed: one query row against the latents themselves ---------------
    def _fold_keys(self, q_nope, dtype):
        """``q' = q_nope W^K``: the K expansion folded into the one query
        token of each row, ``[B, H, kv_lora_rank]``."""
        return jnp.einsum("bhd,chd->bhc", q_nope[:, 0], self._kv_b_heads()[0],
                          preferred_element_type=_F32).astype(dtype)

    def _expand_values(self, o):
        """``(P c) W^V``: the V expansion after the probabilities,
        ``[B, H, kv_lora_rank]`` -> ``[B, 1, H * v]``."""
        o = jnp.einsum("bhc,chv->bhv", o, self._kv_b_heads()[1],
                       preferred_element_type=_F32).astype(o.dtype)
        return o.reshape(o.shape[0], 1, -1)

    def absorbed(self, q_nope, q_rope, latent, qpos, kpos, ring):
        """Same function as :meth:`expanded` for ``T == 1``: ``W^K`` is
        folded into the query and ``W^V`` applied after the probabilities,
        so the latents are read as they lie in the pages.  Over a GATHERED
        view ``latent`` ``[B, S, width]``: the CPU path of a decode step,
        and the reference :meth:`absorbed_paged` is held to."""
        r = self.cfg.kv_lora_rank
        c, k_rope = latent[..., :r], latent[..., r:]
        s = (jnp.einsum("bhc,bsc->bhs", self._fold_keys(q_nope, c.dtype), c,
                        preferred_element_type=_F32)
             + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0], k_rope,
                          preferred_element_type=_F32)) * self.scale
        s = jnp.where(_visible(kpos, qpos, ring), s, jnp.finfo(_F32).min)
        p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
        return self._expand_values(
            jnp.einsum("bhs,bsc->bhc", p, c,
                       preferred_element_type=_F32).astype(c.dtype))

    def absorbed_paged(self, q_nope, q_rope, pool, tables, qpos, kpos):
        """:meth:`absorbed` with no view: the ``latent_decode`` kernel of
        ``ops/latent_attention.py`` walks each slot's page-table row
        (``tables`` ``[B, G]``, clipped to valid pages) up to its sweep
        bound and attends to the rows of ``pool`` where they lie, each page
        fetched once.  The query rows are laid out as a page row is,
        ``[q_nope W^K | q_rope | 0]``."""
        cfg, page = self.cfg, pool.shape[1]
        q = jnp.concatenate([self._fold_keys(q_nope, pool.dtype),
                             q_rope[:, 0].astype(pool.dtype)], axis=-1)
        q = jnp.pad(q, ((0, 0), (0, 0),
                        (0, cfg.page_width - cfg.latent_width)))
        seen = _visible(kpos, qpos, tables.shape[1] * page)
        o = latent_decode(q, pool, tables, kpos, qpos,
                          sweep_bound(seen, page), scale=self.scale,
                          value_width=cfg.kv_lora_rank)
        return self._expand_values(o.astype(q_nope.dtype))

    def forward(self, x, positions):
        """Causal attention over the sequence itself, no cache."""
        with jax.named_scope("mla"):
            q_nope, q_rope = self._queries(x, positions)
            latent = self._latent(x, positions)
            ctx = self.expanded(q_nope, q_rope, latent, positions, positions,
                                ring=jnp.iinfo(jnp.int32).max)
            return _mm(ctx, self.out.value)

    def forward_paged(self, x, kv, write_page, write_off, gather_tab,
                      positions, pos_map):
        with jax.named_scope("mla"):
            B, T, _ = x.shape
            q_nope, q_rope = self._queries(x, positions)
            pool, cfg = kv["latent"], self.cfg
            lat = self._latent(x, positions).reshape(B * T, -1)
            lat = jnp.pad(lat, ((0, 0),
                                (0, cfg.page_width - cfg.latent_width)))
            pool = pool.at[write_page, write_off].set(lat.astype(pool.dtype))
            # the table is clipped to valid pages by the caller
            if _walks_pages(pool, T):
                # TPU, a decode step: no view, the kernel walks the pages
                ctx = self.absorbed_paged(q_nope, q_rope, pool, gather_tab,
                                          positions, pos_map)
            else:
                C = gather_tab.shape[1] * pool.shape[1]
                view = pool.at[gather_tab].get(mode="promise_in_bounds")
                view = view.reshape(B, C, -1)[..., :cfg.latent_width]
                view = view.astype(x.dtype)
                attend = self.absorbed if T == 1 else self.expanded
                ctx = attend(q_nope, q_rope, view, positions, pos_map, C)
            return _mm(ctx, self.out.value), {"latent": pool}


class DecoderBlock(Layer):
    def __init__(self, cfg: LatentMoEConfig, dense: bool):
        super().__init__()
        dt = cfg.dtype
        self.norm1 = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, dt)
        self.attn = LatentAttention(cfg)
        self.norm2 = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, dt)
        if dense:
            self.mlp = GatedMLP(cfg.hidden_size, cfg.intermediate_size, dt,
                                cfg.init_std)
        else:
            self.mlp = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.experts_per_token, shared_experts=cfg.shared_experts,
                routed_scale=cfg.routed_scaling_factor,
                norm_topk=cfg.norm_topk_prob, dtype=dt,
                init_std=cfg.init_std)

    def forward(self, x, positions):
        x = x + self.attn(self.norm1(x), positions)
        return x + self.mlp(self.norm2(x))

    def forward_paged(self, x, kv, *paged):
        a, kv = self.attn.forward_paged(self.norm1(x), kv, *paged)
        x = x + a
        return x + self.mlp(self.norm2(x)), kv


class LatentMoEModel(Layer):
    def __init__(self, cfg: LatentMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.dtype,
            default_initializer=I.Normal(std=cfg.init_std))
        self.blocks = nn.LayerList([
            DecoderBlock(cfg, dense=i < cfg.first_dense_layers)
            for i in range(cfg.num_layers)])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                 cfg.dtype)

    def forward(self, input_ids):
        """``[B, S]`` ids -> ``[B, S, D]`` final hidden states, causal."""
        ids = jnp.asarray(input_ids, jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32),
                               ids.shape)
        x = jnp.take(jnp.asarray(self.embed.value), ids, axis=0)
        for blk in self.blocks:
            x = blk(x, pos)
        return self.norm_f(x)

    # -- the paged pool: the model owns the layout --------------------------
    def init_paged_cache(self, num_pages: int, page_size: int, dtype=None):
        """Per layer one ``[P + 1, page, page_width]`` array of latents
        (``kv_lora_rank + rope`` values a token, padded to whole lane
        tiles) shared by all slots; page ``P`` is the write-drop page
        (see ``GPTModel.init_paged_cache`` for the page-table contract)."""
        cfg = self.cfg
        shape = (int(num_pages) + 1, int(page_size), cfg.page_width)
        return {"layers": [{"latent": jnp.zeros(shape, dtype or cfg.dtype)}
                           for _ in range(cfg.num_layers)]}

    @staticmethod
    def _pages(cache):
        return jax.tree_util.tree_leaves(cache)[0].shape[0] - 1

    def copy_pages(self, cache, src, dst):
        """Copy whole pages ``src[i] -> dst[i]`` in every leaf (copy on
        write); ``-1`` entries land in the write-drop page."""
        src = jnp.maximum(jnp.asarray(src, jnp.int32), 0)
        dst = jnp.asarray(dst, jnp.int32)
        dst = jnp.where(dst >= 0, dst, self._pages(cache))
        return jax.tree_util.tree_map(lambda t: t.at[dst].set(t[src]), cache)

    def gather_pages(self, cache, idx):
        """The pages ``idx`` ``[K]`` of every layer, stacked ``[L, K, page,
        width]`` (``-1`` reads the write-drop page): the export half of the
        prefill -> decode hand-off."""
        idx = jnp.asarray(idx, jnp.int32)
        idx = jnp.where(idx >= 0, idx, self._pages(cache))
        return jnp.stack([l["latent"][idx] for l in cache["layers"]])

    def scatter_pages(self, cache, kv, dst):
        """Write a :meth:`gather_pages` payload into the pages ``dst``."""
        kv = jnp.asarray(kv)
        dst = jnp.asarray(dst, jnp.int32)
        dst = jnp.where(dst >= 0, dst, self._pages(cache))
        return {"layers": [
            {"latent": l["latent"].at[dst].set(
                kv[i].astype(l["latent"].dtype))}
            for i, l in enumerate(cache["layers"])]}

    def forward_paged(self, input_ids, positions, pos_map, table, cache,
                      adapter_ids=None):
        """Prefill or decode over the paged latent pool; the contract of
        ``GPTModel.forward_paged`` (host-owned ``table`` and ``pos_map``,
        ``-1`` = padding, static shapes).  There are no adapters here."""
        del adapter_ids
        positions = jnp.asarray(positions, jnp.int32)
        pos_map = jnp.asarray(pos_map, jnp.int32)
        table = jnp.asarray(table, jnp.int32)
        pool0 = cache["layers"][0]["latent"]
        P, page, G = pool0.shape[0] - 1, pool0.shape[1], table.shape[1]
        C = G * page
        x = jnp.take(jnp.asarray(self.embed.value),
                     jnp.asarray(input_ids, jnp.int32), axis=0)
        slots = jnp.where(positions >= 0, positions % C, -1)
        g = jnp.clip(slots // page, 0, G - 1)
        off = jnp.clip(slots % page, 0, page - 1)
        phys = jnp.take_along_axis(table, g, axis=1)
        # padding tokens and unmapped pages write into the drop page P
        phys = jnp.where((slots >= 0) & (phys >= 0), phys, P)
        paged = (phys.reshape(-1), off.reshape(-1), jnp.maximum(table, 0),
                 positions, pos_map)
        layers = []
        for blk, kv in zip(self.blocks, cache["layers"]):
            x, kv = blk.forward_paged(x, kv, *paged)
            layers.append(kv)
        return self.norm_f(x), {"layers": layers}


class LatentMoEForCausalLM(Layer):
    """The decoder with its untied head; answers the serving-model
    protocol (``max_position``, ``moe_experts``, ``lora_capacity``, the
    paged cache and its page ops, ``forward_paged``)."""

    def __init__(self, cfg: LatentMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LatentMoEModel(cfg)
        self.head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_size), dtype=cfg.dtype,
            default_initializer=I.Normal(std=cfg.init_std))

    max_position = property(lambda self: self.cfg.max_position)
    moe_experts = property(lambda self: self.cfg.num_experts)
    lora_capacity = 0

    def init_paged_cache(self, num_pages, page_size, dtype=None):
        return self.model.init_paged_cache(num_pages, page_size, dtype)

    def copy_pages(self, cache, src, dst):
        return self.model.copy_pages(cache, src, dst)

    def gather_pages(self, cache, idx):
        return self.model.gather_pages(cache, idx)

    def scatter_pages(self, cache, kv, dst):
        return self.model.scatter_pages(cache, kv, dst)

    def handoff_zero(self, num_pages, page_size, dtype=None):
        """Zeros in the shape :meth:`gather_pages` exports."""
        cfg = self.cfg
        return np.zeros((cfg.num_layers, int(num_pages), int(page_size),
                         cfg.page_width), jnp.dtype(dtype or cfg.dtype))

    def _logits(self, h):
        return jnp.dot(h, jnp.asarray(self.head.value),
                       preferred_element_type=_F32)

    def forward(self, input_ids):
        """``[B, S]`` -> float32 logits ``[B, S, V]``."""
        return self._logits(self.model(input_ids))

    def forward_paged(self, input_ids, positions, pos_map, table, cache,
                      gather_last=None, adapter_ids=None):
        """Float32 logits ``[B, T, V]``, or ``[B, V]`` of the row
        ``gather_last - 1`` of each sequence, and the new cache."""
        h, cache = self.model.forward_paged(input_ids, positions, pos_map,
                                            table, cache)
        if gather_last is not None:
            idx = jnp.maximum(jnp.asarray(gather_last, jnp.int32) - 1, 0)
            h = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
        return self._logits(h), cache
