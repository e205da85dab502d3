"""A decoder whose layers keep TWO kinds of cache that no other model here
combines: Kimi Delta Attention layers (``ops/kda.py``: the gated delta rule
with one decay a key channel, a float32 matrix state and a conv window PER
SLOT) and NoPE latent-attention layers (``models/latent_moe.py``'s
:class:`LatentAttention` without the query bottleneck and without any
rotation: LATENT PAGES).  Kimi Linear (arXiv:2510.26692): three KDA layers
to one latent layer, the first FFN dense and the rest routed experts with a
shared expert.  Pre-norm blocks ``h = x + Mix(norm(x))``, ``y = h +
FFN(norm(h))``, no bias, a final norm, an untied head.

``kda``, per head of ``d`` keys and ``d`` values (``linear_head_dim``):

    [q~ | k~ | v~] = x W_qkv;  causal depthwise conv of K taps, then SiLU
    q = q' / |q'| * d^-1/2,  k = k' / |k'|
    g = -exp(A_log[h]) softplus((x W_fa) W_fb + dt_bias)    in R^d, <= 0
    beta = sigmoid(x W_b)
    S' = Diag(e^g) S_{t-1};  S_t = S' + beta k (v - S'^T k)^T;  o = S_t^T q
    y = RMSNorm_d(o) * sigmoid((x W_ga) W_gb);  y W_o

The mixer is ``models/hybrid.py``'s :class:`GatedDeltaNet` with other gates
and the other rule: the conv, its window ``[B + 1, K - 1, 3 H d]``, the
l2 norms, and what an admission and a decode call do to a slot are that
class's own.  ``mla``: ``q = x W_q`` per head ``[q_n | q_p]``, ``[c | k_p] =
x W_kva``, ``c = RMSNorm(c)``, ``[k_n | v] = c W_kvb``, scores ``(q_n . k_n
+ q_p . k_p) (d_n + d_p)^-1/2``; no rotation anywhere, so positions reach
these layers only through the KDA layers' state.  A page row holds ``[c |
k_p]`` in ``LatentMoEConfig.page_width``'s lanes, and both formulations
(expanded prefill; absorbed decode, on a TPU the ``latent_decode`` page
walk of ``ops/latent_attention.py`` handed the un-rotated ``q_p`` lanes,
elsewhere over a gathered view) are that class's.

The serving-model protocol (``serving/generation.py``) with ``slot_state``:
``init_paged_cache(..., slots=B)`` returns ``{"layers": [...]}`` with
``{"state", "conv"}`` for a KDA layer and ``{"latent"}`` for a latent layer;
``copy_pages`` copies the latent pools only; an ADMISSION
(``forward_paged(..., slots=[R])``) starts every KDA row from the zero
state, writes state and window after the row's last real token into slot
``slots[r]`` (``-1``: the drop row), and writes the row's latents into its
pages; a DECODE call (one token a row) continues slot ``i`` in row ``i``.  A
padding token (position ``-1``) is the identity on all of them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.errors import InvalidArgumentError
from ..moe import DroplessMoE
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..ops.kda import kda_chunk, kda_step
from .hybrid import (GatedDeltaNet, HybridConfig, HybridForCausalLM,
                     _weight)
from .latent_moe import GatedMLP, LatentAttention, LatentMoEConfig, _mm

__all__ = ["KimiLinearConfig", "KimiDeltaAttention", "KimiLinearModel",
           "KimiLinearForCausalLM"]

_F32 = jnp.float32
LAYER_TYPES = ("kda", "mla")
FFN_TYPES = ("dense", "moe")


class KimiLinearConfig:
    """``layer_types`` names ``kda`` or ``mla`` a layer, ``ffn_types``
    ``dense`` or ``moe``; ``moe`` is :class:`DroplessMoE`'s keyword
    arguments (router kind, the experts HELD here)."""

    def __init__(self, vocab_size, hidden_size, layer_types, ffn_types,
                 intermediate_size, num_heads, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 linear_num_heads, linear_head_dim, linear_conv_kernel=4,
                 moe=None, rms_norm_eps=1e-5, max_position=4096,
                 dtype="bfloat16", init_std=0.02):
        layer_types, ffn_types = tuple(layer_types), tuple(ffn_types)
        if (not layer_types or len(ffn_types) != len(layer_types)
                or any(t not in LAYER_TYPES for t in layer_types)
                or any(t not in FFN_TYPES for t in ffn_types)
                or ("moe" in ffn_types) != bool(moe)):
            raise InvalidArgumentError(
                f"layer_types names one of {LAYER_TYPES} a layer, ffn_types "
                f"one of {FFN_TYPES}, and `moe` goes with a 'moe' among "
                f"them: got {layer_types!r}, {ffn_types!r}, moe={moe!r}")
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        self.layer_types, self.ffn_types = layer_types, ffn_types
        self.intermediate_size = int(intermediate_size)
        self.moe = dict(moe or {})
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_position = int(max_position)
        self.dtype, self.init_std = dtype, float(init_std)
        # what GatedDeltaNet's shared parts read (its docstring names the
        # contract): equal key and value heads
        self.linear_num_heads = self.linear_num_key_heads = int(
            linear_num_heads)
        self.linear_key_head_dim = self.linear_value_head_dim = int(
            linear_head_dim)
        self.linear_conv_kernel = int(linear_conv_kernel)
        # what LatentAttention reads (its docstring lists the names): no
        # bottleneck on the queries (q_lora_rank), no rotation (rope_theta)
        self.num_heads = int(num_heads)
        self.q_lora_rank = self.rope_theta = None
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)

    num_layers = property(lambda self: len(self.layer_types))
    # a page row as the latent model lays it out (LatentAttention reads
    # both widths); the experts held as the hybrid stack counts them
    # (HybridForCausalLM.moe_experts)
    latent_width = LatentMoEConfig.latent_width
    page_width = LatentMoEConfig.page_width
    experts_held = HybridConfig.experts_held

    @property
    def conv_width(self) -> int:
        """Channels under the convolution: ``[q~ | k~ | v~]``."""
        return 3 * self.linear_num_heads * self.linear_key_head_dim


class KimiDeltaAttention(GatedDeltaNet):
    """The ``kda`` mixer: :class:`GatedDeltaNet`'s conv, norms and slot
    handling under a per-channel decay from a low-rank projection, ``beta``
    in (0, 1), a sigmoid output gate of low rank, and ``ops/kda.py``'s
    rule."""

    scope = "kda"
    _chunk, _step = staticmethod(kda_chunk), staticmethod(kda_step)

    def __init__(self, cfg: KimiLinearConfig):
        Layer.__init__(self)
        self.cfg = cfg
        D, H, d = cfg.hidden_size, cfg.linear_num_heads, cfg.linear_key_head_dim
        r = d    # the rank of both low-rank gates is a head's width
        self.qkv = _weight(self, D, cfg.conv_width)
        self.conv = _weight(self, cfg.linear_conv_kernel, cfg.conv_width)
        self.f_a, self.f_b = _weight(self, D, r), _weight(self, r, H * d)
        self.b = _weight(self, D, H)
        # Mamba-2's initialisation, the decay's step a CHANNEL: A in U(1,
        # 16) a head, the step's softplus in U(0.001, 0.1); both float32
        self.A_log = _weight(self, H, dtype="float32",
                             init=I.Assign(jnp.log(jnp.linspace(1., 16., H))))
        dt = jnp.linspace(0.001, 0.1, H * d)
        self.dt_bias = _weight(self, H * d, dtype="float32",
                               init=I.Assign(dt + jnp.log(-jnp.expm1(-dt))))
        self.g_a, self.g_b = _weight(self, D, r), _weight(self, r, H * d)
        self.o_norm = nn.RMSNorm(d, cfg.rms_norm_eps, cfg.dtype)
        self.out = _weight(self, H * d, D)

    def _gates(self, x, valid):
        """Float32 ``g`` (log decay) ``[..., H, d]`` and ``beta`` ``[...,
        H]``; a padding token gets the identity, ``g = 0`` and ``beta =
        0``."""
        H, d = self.cfg.linear_num_heads, self.cfg.linear_key_head_dim
        f = jnp.dot(_mm(x, self.f_a.value), jnp.asarray(self.f_b.value),
                    preferred_element_type=_F32)
        g = -jnp.exp(self.A_log.value)[:, None] * jax.nn.softplus(
            f + self.dt_bias.value).reshape(*x.shape[:-1], H, d)
        beta = jax.nn.sigmoid(jnp.dot(x, jnp.asarray(self.b.value),
                                      preferred_element_type=_F32))
        keep = valid[..., None]
        return jnp.where(keep[..., None], g, 0.0), jnp.where(keep, beta, 0.0)

    def _output(self, x, o):
        """``o`` float32 ``[B, T, H, d]`` -> the layer's output."""
        gate = jnp.dot(_mm(x, self.g_a.value), jnp.asarray(self.g_b.value),
                       preferred_element_type=_F32).reshape(o.shape)
        y = self.o_norm(o) * jax.nn.sigmoid(gate)
        return _mm(y.reshape(*x.shape[:-1], -1).astype(x.dtype),
                   self.out.value)


class KimiLinearBlock(Layer):
    def __init__(self, cfg: KimiLinearConfig, kind: str, ffn: str):
        super().__init__()
        self.kind = kind
        self.norm1 = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)
        self.mixer = (KimiDeltaAttention(cfg) if kind == "kda"
                      else LatentAttention(cfg))
        self.norm2 = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, cfg.dtype)
        if ffn == "moe":
            self.mlp = DroplessMoE(cfg.hidden_size, dtype=cfg.dtype,
                                   init_std=cfg.init_std, **cfg.moe)
        else:
            self.mlp = GatedMLP(cfg.hidden_size, cfg.intermediate_size,
                                cfg.dtype, cfg.init_std)

    def finish(self, x, mixed):
        h = x + mixed
        return h + self.mlp(self.norm2(h))

    def forward(self, x, positions):
        return self.finish(x, self.mixer(self.norm1(x), positions))


class KimiLinearModel(Layer):
    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = _weight(self, cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList([
            KimiLinearBlock(cfg, kind, ffn)
            for kind, ffn in zip(cfg.layer_types, cfg.ffn_types)])
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
        """Per latent layer one pool ``[P + 1, page, page_width]``; per KDA
        layer ``state`` ``[slots + 1, H, d, d]`` float32 and ``conv``
        ``[slots + 1, K - 1, 3 H d]``.  Page ``P`` and row ``slots`` are
        the write-drop page and row."""
        cfg = self.cfg
        if slots is None:
            raise InvalidArgumentError(
                "a model with slot state needs init_paged_cache(slots=): "
                "the engine's batch size")
        pool = (int(num_pages) + 1, int(page_size), cfg.page_width)
        rows, H, d = int(slots) + 1, cfg.linear_num_heads, (
            cfg.linear_key_head_dim)

        def layer(kind):
            if kind == "mla":
                return {"latent": jnp.zeros(pool, dtype or cfg.dtype)}
            return {"state": jnp.zeros((rows, H, d, d), _F32),
                    "conv": jnp.zeros((rows, cfg.linear_conv_kernel - 1,
                                       cfg.conv_width), cfg.dtype)}

        return {"layers": [layer(kind) for kind in cfg.layer_types]}

    def copy_pages(self, cache, src, dst):
        """Copy whole pages ``src[i] -> dst[i]`` of every latent pool (slot
        state has no pages and is left alone); ``-1`` entries land in the
        write-drop page."""
        src = jnp.maximum(jnp.asarray(src, jnp.int32), 0)
        dst = jnp.asarray(dst, jnp.int32)

        def copy(kv):
            if "latent" not in kv:
                return kv
            t = kv["latent"]
            return {"latent": t.at[jnp.where(dst >= 0, dst, t.shape[0] - 1)]
                    .set(t[src])}

        return {"layers": [copy(kv) for kv in cache["layers"]]}

    def forward_paged(self, input_ids, positions, pos_map, table, cache,
                      slots=None):
        """The contract of ``LatentMoEModel.forward_paged`` for the latent
        pools and of the module docstring for the slot state: ``slots``
        ``[R]`` makes the call an admission of whole prompts from position
        0."""
        positions = jnp.asarray(positions, jnp.int32)
        pos_map = jnp.asarray(pos_map, jnp.int32)
        table = jnp.asarray(table, jnp.int32)
        prompt = slots is not None
        if not prompt and positions.shape[1] != 1:
            raise InvalidArgumentError(
                "slot state decodes one token a row: a wider step would "
                "have to roll the state back for a rejected draft")
        x = jnp.take(jnp.asarray(self.embed.value),
                     jnp.asarray(input_ids, jnp.int32), axis=0)
        pool = next((kv["latent"] for kv in cache["layers"]
                     if "latent" in kv), None)
        if pool is not None:  # what the layers with pages share
            P, page, G = pool.shape[0] - 1, pool.shape[1], table.shape[1]
            ring = jnp.where(positions >= 0, positions % (G * page), -1)
            phys = jnp.take_along_axis(
                table, jnp.clip(ring // page, 0, G - 1), axis=1)
            # padding tokens and unmapped pages write into the drop page P
            phys = jnp.where((ring >= 0) & (phys >= 0), phys, P)
            paged = (phys.reshape(-1), jnp.clip(ring % page, 0, page - 1)
                     .reshape(-1), jnp.maximum(table, 0), positions, pos_map)
        if prompt:
            slots = jnp.asarray(slots, jnp.int32)
            drop = next((kv["state"].shape[0] - 1 for kv in cache["layers"]
                         if "state" in kv), 0)
            rows = jnp.where(slots >= 0, slots, drop)
        layers = []
        for blk, kv in zip(self.blocks, cache["layers"]):
            y = blk.norm1(x)
            if blk.kind == "mla":
                mixed, kv = blk.mixer.forward_paged(y, kv, *paged)
            elif prompt:
                mixed, kv = blk.mixer.admit(y, positions, kv, rows)
            else:
                mixed, kv = blk.mixer.decode(y, positions, kv)
            x = blk.finish(x, mixed)
            layers.append(kv)
        return self.norm_f(x), {"layers": layers}


class KimiLinearForCausalLM(HybridForCausalLM):
    """:class:`HybridForCausalLM`'s head and protocol verbs over this
    decoder: per-slot rows beside LATENT pages."""

    decoder = KimiLinearModel

    def slot_state_bytes(self) -> int:
        """Bytes of slot state one slot holds over all the KDA layers (what
        a decode step reads and writes for it): the float32 states and the
        conv windows."""
        cfg, item = self.cfg, jnp.dtype(self.cfg.dtype).itemsize
        return cfg.layer_types.count("kda") * (
            4 * cfg.linear_num_heads * cfg.linear_key_head_dim ** 2
            + item * (cfg.linear_conv_kernel - 1) * cfg.conv_width)
