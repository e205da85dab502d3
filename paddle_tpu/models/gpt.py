"""GPT — decoder-only transformer LM, tensor-parallel-ready.

The reference has no GPT (its transformer surface is the seq2seq
paddle.nn.Transformer, python/paddle/nn/layer/transformer.py); a decoder LM
is the flagship workload for the TPU framework's distributed story, and
GPT-2-small is the benchmark's serving model (``benchmarks/run.py``).

Every projection is a meta_parallel layer: on a mesh with ``model`` axis
size 1 they degenerate to plain Linears (zero overhead single-chip); with
mp>1 the weights shard megatron-style and GSPMD inserts the two
all-reduces per block.  Heads are split along the ``model`` axis, so
attention runs fully sharded between the column (qkv) and row (out)
projections.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..framework import device as _device
from ..distributed.meta_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    constrain,
)
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..ops.paged_attention import (
    key_visible,
    paged_attention,
    paged_flash_eligible,
    sweep_bound,
)

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt_tiny", "gpt_small"]


def _fused_epilogues(feature_dim=None) -> bool:
    """Gate for the fused Pallas epilogues (same shape as _use_flash's
    gate: a real TPU backend, aligned dims, a one-device mesh)."""
    from ..ops.autotune import fused_epilogues_eligible

    return fused_epilogues_eligible(feature_dim)


def _paged_flash(head_dim, page_size) -> bool:
    """Gate for the Pallas paged-flash-decode kernel (same shape as
    ``_fused_epilogues``: TPU backend, aligned dims, a one-device
    mesh).  Off-gate, ``forward_paged`` keeps the gather-then-attend
    path — the bit-identical CPU/fallback reference."""
    return paged_flash_eligible(head_dim, page_size)


def _quantize_kv(t, qdtype):
    """Quantize-on-write for paged KV: ``t`` float ``[N, H, hd]`` →
    (quantized values, ``[N, H]`` float32 dequant multipliers), one
    abs-max scale per written token per head."""
    tf = jnp.asarray(t, jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1), 1e-9)  # [N, H]
    if jnp.dtype(qdtype) == jnp.int8:
        q = jnp.clip(jnp.round(tf * (127.0 / amax[..., None])),
                     -127, 127).astype(jnp.int8)
        return q, amax / 127.0
    fp8_max = 448.0  # largest finite e4m3fn; clip BEFORE the cast
    q = jnp.clip(tf * (fp8_max / amax[..., None]),
                 -fp8_max, fp8_max).astype(jnp.float8_e4m3fn)
    return q, amax / fp8_max


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None, max_position=1024,
                 dropout=0.1, layer_norm_epsilon=1e-5, dtype="float32",
                 sequence_parallel=None, moe_experts=0, moe_top_k=2,
                 moe_capacity_factor=1.25, moe_jitter=0.01,
                 moe_balance_weight=0.01, quantization="none",
                 lora_capacity=0, lora_rank=8, lora_alpha=16.0):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position = max_position
        self.dropout = dropout
        self.layer_norm_epsilon = layer_norm_epsilon
        self.dtype = dtype
        #: None | "ring" | "ulysses" — long-sequence attention over the
        #: ``sep`` mesh axis (see distributed/sequence_parallel.py)
        self.sequence_parallel = sequence_parallel
        #: > 0 swaps every block's dense ParallelMLP for a
        #: ``moe.MoELayer`` with that many experts (paddle_tpu/moe);
        #: expert weights shard over the ``expert`` mesh axis
        self.moe_experts = moe_experts
        self.moe_top_k = moe_top_k
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_jitter = moe_jitter
        #: multiplier on the summed per-layer load-balance loss added to
        #: :meth:`GPTForCausalLM.loss`
        self.moe_balance_weight = moe_balance_weight
        #: "none" | "int8" | "fp8" — serving weight quantization: the
        #: parallel-linear hot paths store int8/fp8-e4m3 weights plus
        #: per-channel scales (``slim.quantize_weights`` runs at model
        #: init) and route through ``ops.quantized_matmul``.  "none" is
        #: bitwise-identical to the unquantized model.
        if quantization not in ("none", "int8", "fp8"):
            raise ValueError(
                f"quantization must be 'none', 'int8' or 'fp8', got "
                f"{quantization!r}")
        self.quantization = quantization
        #: > 0 registers fixed-capacity batched multi-LoRA adapter
        #: tables on every block projection (``lora.enable_lora``) —
        #: that many hot-swappable adapter slots per linear; per-slot
        #: adapter ids flow through ``forward_paged``
        #: and id -1 is bitwise the base model.  0 = no LoRA.
        if int(lora_capacity) < 0:
            raise ValueError(
                f"lora_capacity must be >= 0, got {lora_capacity!r}")
        if int(lora_capacity) > 0 and int(lora_rank) < 1:
            raise ValueError(
                f"lora_rank must be >= 1, got {lora_rank!r}")
        self.lora_capacity = int(lora_capacity)
        self.lora_rank = int(lora_rank)
        self.lora_alpha = float(lora_alpha)


def gpt_tiny(**kw):
    cfg = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
               max_position=64, dropout=0.0)
    cfg.update(kw)
    return GPTConfig(**cfg)


def gpt_small(**kw):
    return GPTConfig(**kw)


class ParallelAttention(Layer):
    """Causal (or masked) multi-head self-attention with model-sharded heads.

    With ``sequence_parallel`` set ("ring"/"ulysses") and a mesh whose
    ``sep`` axis is >1, attention runs sequence-sharded: ring attention
    rotates KV chunks over ICI (lax.ppermute) with online-softmax merging,
    Ulysses all-to-alls heads↔sequence.  Both are exact; attention-prob
    dropout is skipped on that path (the probabilities never materialize —
    same trade flash-attention kernels make).  A custom ``attn_mask`` forces
    the dense path (SP supports the built-in causal mask only).
    """

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        d, h = cfg.hidden_size, cfg.num_heads
        if d % h:
            raise ValueError(f"hidden {d} % heads {h} != 0")
        self.num_heads = h
        self.head_dim = d // h
        self.qkv = ColumnParallelLinear(d, 3 * d, gather_output=False)
        self.out = RowParallelLinear(d, d, input_is_parallel=True)
        self.drop = nn.Dropout(cfg.dropout)
        self.sequence_parallel = cfg.sequence_parallel

    def _sp_degree(self):
        from ..distributed.mesh import get_mesh

        return get_mesh().shape.get("sep", 1)

    def _qkv(self, x):
        """qkv projection → token-major ``[B,S,H,hd]`` triples (a token's
        heads side by side, as the projection wrote them)."""
        B, S, D = x.shape
        qkv = self.qkv(x)  # [B,S,3D] sharded on last dim
        qkv = qkv.reshape(B, S, 3, self.num_heads, self.head_dim)
        # heads inherit the model sharding of the projection output
        qkv = constrain(qkv, None, None, None, "model", None)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    def _heads(self, x):
        """qkv projection → per-head ``[B,H,S,hd]`` triples."""
        q, k, v = self._qkv(x)
        return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3))

    def forward(self, x, attn_mask=None):
        B, S, D = x.shape
        q, k, v = self._heads(x)
        ctx = None
        if (self.sequence_parallel and attn_mask is None
                and self._sp_degree() > 1):
            ctx = self._sp_attention(q, k, v)  # [B,H,S,hd]
        elif self._use_flash(S, attn_mask):
            # long-context path: the Pallas flash kernel buys O(S)
            # attention memory at speed parity with XLA's fused attention
            # (see _use_flash for the measured gate)
            from ..ops.flash_attention import flash_attention

            ctx = flash_attention(q, k, v, causal=True)
        if ctx is None:
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(self.head_dim)
            causal = jnp.tril(jnp.ones((S, S), bool))
            scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
            if attn_mask is not None:
                scores = scores + attn_mask.astype(scores.dtype)
            probs = jax.nn.softmax(scores, axis=-1)
            probs = self.drop(probs)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, D)
        ctx = constrain(ctx, None, None, "model")
        return self.out(ctx)

    def _use_flash(self, S, attn_mask) -> bool:
        """Flash engages where measured not to lose: XLA's fused bf16
        attention is flash-class on TPU (measured in-model on v5e: dense
        wins below seq 4096, parity at 4096-8192 — the kernel's advantage
        is O(S) attention memory, not speed).  Also requires: no extra
        mask (the kernel handles the causal one), no probs-dropout in
        effect, MXU-friendly head dim, a real TPU backend, and a mesh
        that admits kernels (``autotune.mesh_admits_kernels`` — the dense
        einsum partitions naturally; multi-chip meshes keep it)."""
        from ..ops.autotune import mesh_admits_kernels

        return (attn_mask is None and S >= 4096 and S % 128 == 0
                and self.head_dim in (64, 128, 256)
                and (self.drop.p == 0.0 or not self.training)
                and _device.on_tpu() and mesh_admits_kernels())

    def _sp_attention(self, q, k, v):
        from jax.sharding import PartitionSpec as P

        from ..distributed.collective import shard_map
        from ..distributed.mesh import data_axes, get_mesh
        from ..distributed.sequence_parallel import (
            ring_attention,
            ulysses_attention,
        )

        mesh = get_mesh()
        batch_ax = tuple(data_axes(mesh))
        model_ax = "model" if mesh.shape.get("model", 1) > 1 else None
        spec = P(batch_ax, model_ax, "sep", None)
        fn = (ulysses_attention if self.sequence_parallel == "ulysses"
              else ring_attention)

        def local(ql, kl, vl):
            return fn(ql, kl, vl, axis_name="sep", causal=True)

        return shard_map(local, mesh, (spec, spec, spec), spec)(q, k, v)

    def forward_paged(self, x, kv, write_page, write_off, gather_tab, mask,
                      walk=None):
        """One attention step over a PAGED KV pool — the serving decode
        path (paddle_tpu/serving/generation.py; see
        :meth:`GPTModel.init_paged_cache`).  Every step has the same
        shapes, so the jitted step never retraces and costs O(C) instead
        of re-running the O(S²) prefix; attention-prob dropout is skipped
        (decode is inference).

        The pool stores one token's K (or V) for ALL heads as one
        contiguous row: ``[P+1, page, H*hd]``.  The new tokens' rows come
        from the fused projection before any head split and are scattered
        at host-resolved physical coordinates (``write_page``/
        ``write_off``, flattened ``[B*T]``; the pool's last page is the
        write-drop page for padding).  On the TPU the ``paged_decode``
        kernel then reads the pool in that same order, walking each slot's
        pages up to its sweep bound (``walk``: ``(pos_map, positions,
        bound)``, what the kernel rebuilds ``mask`` from and how far it
        has to look; given by :meth:`GPTModel.forward_paged` when the
        kernel's gate is open); elsewhere each
        slot's logical cache view is gathered back through its page-table
        row (``gather_tab`` ``[B,G]``, entries pre-clipped to valid pages)
        and plain masked attention runs over the gathered ``[B,H,C,hd]``
        view.  ``mask``: ``[B,T,C]`` attention validity computed from the
        host-owned slot→position map.
        """
        B, T, D = x.shape
        H, hd = self.num_heads, self.head_dim
        q, k, v = self._qkv(x)  # [B,T,H,hd]: a token's heads side by side
        q = q.transpose(0, 2, 1, 3)  # [B,H,T,hd]
        quantized = "k_scale" in kv  # static: pool dtype fixed at init
        out = {}
        for name, rows in (("k", k), ("v", v)):
            rows = rows.reshape(B * T, H, hd)
            if quantized:
                rows, scale = _quantize_kv(rows, kv[name].dtype)
                out[name + "_scale"] = kv[name + "_scale"].at[
                    write_page, write_off].set(scale)
            # a token is one row of the pool: [B*T, H*hd] lands at
            # (page, offset) whole, no per-head pieces
            out[name] = kv[name].at[write_page, write_off].set(
                rows.reshape(B * T, H * hd).astype(kv[name].dtype))
        new_k, new_v = out["k"], out["v"]
        # the kernel on the TPU (``walk`` given), the gathered view elsewhere:
        # ops.paged_attention.paged_attention, shared with models/hybrid.py.
        # The scatter above is identical on both paths, so the cache state
        # stays bit-identical.
        ctx = paged_attention(q, new_k, new_v, gather_tab, mask, walk,
                              out.get("k_scale"), out.get("v_scale"))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, D)
        ctx = constrain(ctx, None, None, "model")
        return self.out(ctx), out


class ParallelMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.fc1 = ColumnParallelLinear(cfg.hidden_size, cfg.intermediate_size,
                                        gather_output=False)
        self.fc2 = RowParallelLinear(cfg.intermediate_size, cfg.hidden_size,
                                     input_is_parallel=True)
        self.act = nn.GELU()
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x):
        return self.drop(self.fc2(self.act(self.fc1(x))))


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.attn = ParallelAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        if getattr(cfg, "moe_experts", 0):
            from ..moe import MoELayer

            self.mlp = MoELayer(cfg)
        else:
            self.mlp = ParallelMLP(cfg)

    def forward(self, x, attn_mask=None):
        if _fused_epilogues(x.shape[-1]):
            # fused residual+LN epilogue (ops/fused_layernorm.py): the
            # attn-output residual add and ln2 run in one HBM pass; the
            # kernel returns both the residual stream and the normalized
            # activations the MLP consumes
            from ..ops.fused_layernorm import layernorm_residual

            a = self.attn(self.ln1(x), attn_mask)
            s, h = layernorm_residual(a, x, self.ln2.weight.value,
                                      self.ln2.bias.value,
                                      epsilon=self.ln2.epsilon)
            return s + self.mlp(h)
        x = x + self.attn(self.ln1(x), attn_mask)
        x = x + self.mlp(self.ln2(x))
        return x

    def forward_paged(self, x, kv, write_page, write_off, gather_tab, mask,
                      walk=None):
        from ..distributed.collective import (
            get_overlap_schedule,
            overlap_schedule,
        )

        a, new_kv = self.attn.forward_paged(self.ln1(x), kv, write_page,
                                            write_off, gather_tab, mask,
                                            walk)
        x = x + a
        if get_overlap_schedule().get("mlp_collective_split"):
            # overlap dial: trace the MLP with its row-parallel reduce
            # deferred, then pin the reduce AFTER the residual add — the
            # model-axis all-reduce and the add can overlap (the "split
            # around the MLP" schedule; value unchanged, GSPMD resolves
            # the partial sums at the constrain).  Searched by
            # tuning.plan_space.tune_decode_schedule on real decode steps.
            with overlap_schedule(defer_row_reduce=1):
                m = self.mlp(self.ln2(x))
            x = constrain(x + m, *([None] * x.ndim))
        else:
            x = x + self.mlp(self.ln2(x))
        return x, new_kv


class GPTModel(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_position, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=I.Normal(std=0.02)))
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        #: microbatch count for the pipeline schedule (None → pp); set by
        #: Model.prepare from strategy.pipeline_configs["accumulate_steps"]
        self.pipeline_microbatches = None
        if getattr(cfg, "quantization", "none") != "none":
            # quantize the parallel-linear weights in place (int8/fp8 +
            # per-channel scale buffers); their forwards dispatch on the
            # weight dtype, so no layer swap is needed.  Lazy import:
            # slim ↔ models would otherwise cycle.
            from ..slim.quantization import quantize_weights

            quantize_weights(self, cfg.quantization)
        if getattr(cfg, "lora_capacity", 0) > 0:
            # register zero-initialized batched multi-LoRA adapter tables
            # on every block projection; zero tables + id -1 keep the
            # enabled model bitwise the base model.  Lazy import for the
            # same cycle reason as slim above.
            from ..lora.batched import enable_lora

            enable_lora(self, cfg.lora_capacity, cfg.lora_rank,
                        cfg.lora_alpha, dtype=cfg.dtype)

    def forward(self, input_ids, attn_mask=None):
        from ..distributed.pipeline_parallel import (
            pipeline_blocks,
            pipeline_degree,
        )

        B, S = input_ids.shape
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        pp = pipeline_degree()
        if pp > 1:
            # embedding/head run replicated over `pipe`; the block stack is
            # the pipelined section (see distributed/pipeline_parallel.py)
            if attn_mask is not None:
                raise ValueError(
                    "pipeline parallelism supports the built-in causal mask "
                    "only (a per-batch attn_mask cannot microbatch-split)")
            if any(b.attn.sequence_parallel for b in self.blocks):
                raise ValueError(
                    "pipeline (pp>1) and sequence parallelism cannot combine "
                    "yet — ring/Ulysses attention opens its own shard_map")
            x = pipeline_blocks(
                self.blocks, x,
                num_microbatches=self.pipeline_microbatches)
        else:
            for blk in self.blocks:
                x = blk(x, attn_mask)
        return self.ln_f(x)

    # -- the serving decode path (paddle_tpu.serving): a paged KV cache
    # (vLLM-style PagedAttention; Kwon et al. 2023) ------------------------
    def init_paged_cache(self, num_pages: int, page_size: int, dtype=None):
        """Preallocate a paged KV pool: per-layer ``[P+1, page, H*hd]``
        K/V page arrays shared by ALL slots.  A token's K (or V) for all
        heads is ONE contiguous row, a page is ``page`` such rows: the one
        stored order that the scatter of :meth:`forward_paged`, the paged
        programs' entry and exit and the ``paged_decode`` kernel's blocks
        all use as it is, so no program re-orders the pool (with ``hd``
        minor, half a lane tile for GPT-2, every program copied all of it
        to another order and back).  Which physical page holds
        which slot's tokens is decided per call by a host-owned page
        table (see :meth:`forward_paged`) — the indirection that lets
        pages be allocated on demand, shared copy-on-write between slots
        (common system prompts prefill once), and returned to a free
        list at eviction.  Index ``P`` (the last page) is the write-DROP
        page: padding tokens scatter there and nothing ever gathers it,
        so every call keeps static shapes with no dynamic masking.

        ``dtype=int8`` (or ``float8_e4m3fn``) switches the pool to
        QUANTIZED KV pages: each layer additionally holds per-entry
        ``k_scale``/``v_scale`` ``[P+1, page, H]`` float32 tensors (one
        scale per written token per head, indexed like the values), K/V
        quantize on write in :meth:`forward_paged`'s scatter and
        dequantize on gather in attention — the same HBM budget holds
        ~2-4× the tokens, and the host-side page table / CoW machinery is
        untouched (table edits are dtype-blind)."""
        cfg = self.cfg
        dt = dtype or cfg.dtype
        P, pg = int(num_pages), int(page_size)
        quantized = str(jnp.dtype(dt)) in ("int8", "float8_e4m3fn")

        def layer():
            l = {"k": jnp.zeros((P + 1, pg, cfg.hidden_size), dt),
                 "v": jnp.zeros((P + 1, pg, cfg.hidden_size), dt)}
            if quantized:
                l["k_scale"] = jnp.zeros((P + 1, pg, cfg.num_heads),
                                         jnp.float32)
                l["v_scale"] = jnp.zeros((P + 1, pg, cfg.num_heads),
                                         jnp.float32)
            return l

        return {"layers": [layer() for _ in range(cfg.num_layers)]}

    def copy_pages(self, cache, src, dst):
        """Copy whole pages ``src[i] → dst[i]`` inside the pool — the
        copy-on-write op: before a slot's first divergent write into a
        page whose refcount is >1, the host allocates a fresh page and
        dispatches this copy, so siblings sharing the original page are
        never perturbed.  ``src``/``dst`` are fixed-size ``[K]`` int32
        vectors; ``-1`` entries are no-ops (the copy lands in the
        write-drop page), so the op always runs at one static shape."""
        src = jnp.maximum(jnp.asarray(src, jnp.int32), 0)
        dst = jnp.asarray(dst, jnp.int32)
        P = cache["layers"][0]["k"].shape[0] - 1
        dst = jnp.where(dst >= 0, dst, P)
        # every per-layer tensor is page-major, so one indexed copy per
        # key covers quantized pools' k_scale/v_scale for free
        return {
            "layers": [
                {key: t.at[dst].set(t[src]) for key, t in l.items()}
                for l in cache["layers"]
            ],
        }

    def gather_pages(self, cache, idx):
        """Read whole pages out of the pool — the export half of the
        prefill→decode KV hand-off (serving/pool.py): ``idx`` is a
        fixed-size ``[K]`` int32 vector of physical page numbers (``-1``
        reads the all-zero write-drop page, so the op always runs at one
        static shape).  Returns one stacked ``[L, 2, K, page, H*hd]``
        array (layer-major, k/v interleaved, pages in the pool's stored
        order) so the hand-off rides a single host transfer instead of
        ``2L`` small ones.

        Quantized pools return ``(pages, scales)`` — the quantized
        ``[L, 2, K, page, H*hd]`` stack plus its ``[L, 2, K, page, H]``
        float32 scale stack — so a hand-off never round-trips through
        float (the adopting engine's pool stores the exact same bits)."""
        P = cache["layers"][0]["k"].shape[0] - 1
        idx = jnp.asarray(idx, jnp.int32)
        idx = jnp.where(idx >= 0, idx, P)
        out = jnp.stack([jnp.stack([l["k"][idx], l["v"][idx]])
                         for l in cache["layers"]])
        if "k_scale" in cache["layers"][0]:
            scales = jnp.stack(
                [jnp.stack([l["k_scale"][idx], l["v_scale"][idx]])
                 for l in cache["layers"]])
            return out, scales
        return out

    def scatter_pages(self, cache, kv, dst):
        """Write :meth:`gather_pages` payloads into the pool — the import
        half of the KV hand-off: ``kv`` is the ``[L, 2, K, page, H*hd]``
        export and ``dst`` the ``[K]`` int32 target pages the adopting
        host allocated (``-1`` lands in the write-drop page).  Same
        static-shape contract as :meth:`copy_pages`, so the adopting
        engine's compile set stays closed.

        For a quantized pool ``kv`` is the ``(pages, scales)`` pair
        :meth:`gather_pages` exported."""
        scales = None
        if isinstance(kv, (tuple, list)):
            kv, scales = kv
            scales = jnp.asarray(scales)
        kv = jnp.asarray(kv)
        dst = jnp.asarray(dst, jnp.int32)
        P = cache["layers"][0]["k"].shape[0] - 1
        dst = jnp.where(dst >= 0, dst, P)
        new_layers = []
        for i, l in enumerate(cache["layers"]):
            nl = {"k": l["k"].at[dst].set(kv[i, 0].astype(l["k"].dtype)),
                  "v": l["v"].at[dst].set(kv[i, 1].astype(l["v"].dtype))}
            if "k_scale" in l:
                if scales is None:
                    raise ValueError(
                        "scatter_pages: quantized pool needs the "
                        "(pages, scales) pair gather_pages exported")
                nl["k_scale"] = l["k_scale"].at[dst].set(
                    scales[i, 0].astype(jnp.float32))
                nl["v_scale"] = l["v_scale"].at[dst].set(
                    scales[i, 1].astype(jnp.float32))
            new_layers.append(nl)
        return {"layers": new_layers}

    def forward_paged(self, input_ids, positions, pos_map, table, cache,
                      adapter_ids=None):
        """Prefill/decode forward over :meth:`init_paged_cache` state.

        ``input_ids``/``positions`` are ``[B,T]`` — ``T`` is the prompt
        bucket length for prefill, 1 (or ``1 + k`` draft columns) for a
        decode step.  ``positions`` are ABSOLUTE token positions per
        sequence (``-1`` marks padding: the token writes nothing and
        attends to nothing), so ragged right-padded prompts and
        per-sequence decode offsets batch together.  A key is visible iff
        its slot holds a real token, causally before (or at) the query,
        and within the last ``C`` positions (past ``C`` a slot's window
        slides).  The cache metadata is HOST-owned and passed per call:
        ``table`` ``[B,G]`` maps each slot's logical pages to physical
        pool pages (``-1`` = unmapped), and ``pos_map``
        ``[B,C]`` (``C = G*page``) is the slot→absolute-position map
        *after this call's writes* (the host knows exactly which
        positions it is writing, so it marks them up front; stale or
        rejected-draft entries stay ``-1`` and are invisible).  All
        shapes are static, so the jitted step compiles once.  Returns
        ``(hidden [B,T,D], new_cache)``.
        """
        positions = jnp.asarray(positions, jnp.int32)
        pos_map = jnp.asarray(pos_map, jnp.int32)
        table = jnp.asarray(table, jnp.int32)
        P = cache["layers"][0]["k"].shape[0] - 1
        page = cache["layers"][0]["k"].shape[1]
        G = table.shape[1]
        C = G * page
        x = self.wte(input_ids) + self.wpe(jnp.maximum(positions, 0))
        x = self.drop(x)
        slots = jnp.where(positions >= 0, positions % C, -1)
        g = jnp.clip(slots // page, 0, G - 1)
        off = jnp.clip(slots % page, 0, page - 1)
        phys = jnp.take_along_axis(table, g, axis=1)  # [B,T]
        # padding tokens and unmapped pages write into the drop page P
        phys = jnp.where((slots >= 0) & (phys >= 0), phys, P)
        write_page = phys.reshape(-1)
        write_off = off.reshape(-1)
        mask = key_visible(pos_map[:, None, :], positions[:, :, None],
                           C)  # [B,T,C]
        gather_tab = jnp.maximum(table, 0)  # unmapped → page 0; mask hides it
        # the kernel's side of the same rule, once for all the layers: what
        # it rebuilds the mask from, and how many key blocks of each slot
        # hold a key that some row of this call can see (0: a free slot, a
        # padding row; the whole window: a slot that has wrapped); at an
        # admission width, of each TILE of query rows, so that the kernel
        # walks the causal triangle and not its square
        walk = None
        if _paged_flash(self.cfg.hidden_size // self.cfg.num_heads, page):
            walk = (pos_map, positions, sweep_bound(mask, page))
        new_layers = []
        with self._lora_scope(adapter_ids):
            for blk, kv in zip(self.blocks, cache["layers"]):
                x, kv = blk.forward_paged(x, kv, write_page, write_off,
                                          gather_tab, mask, walk)
                new_layers.append(kv)
        return self.ln_f(x), {"layers": new_layers}

    def _lora_scope(self, adapter_ids):
        """Scope the ``[B]`` per-slot adapter ids around the block stack
        (inert ``nullcontext`` when the caller passed none) — the block
        projections pick them up via ``lora.runtime``; the embeddings,
        final LN and the tied LM head are outside and never adapted."""
        if adapter_ids is None:
            from contextlib import nullcontext

            return nullcontext()
        from ..lora.runtime import adapter_scope

        return adapter_scope(adapter_ids)


class GPTForCausalLM(Layer):
    """LM head ties the (vocab-sharded) input embedding."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(cfg)

    # -- the serving-model protocol (serving/generation.py): what an engine
    # asks of a model, answered here by the decoder under ``.gpt``.  A model
    # with recurrent state per slot beside its pages also declares
    # ``slot_state = True``, takes ``slots=`` in ``init_paged_cache`` and
    # in an admission's ``forward_paged`` and answers ``slot_state_bytes()``
    # (models/hybrid.py); this one has pages only.  ``admit_page_walk``: an
    # admission's attention is the page walk of ops/paged_attention.py too,
    # so the loop counts the blocks it walks -------------------------------
    admit_page_walk = True
    max_position = property(lambda self: self.gpt.cfg.max_position)
    moe_experts = property(
        lambda self: int(getattr(self.gpt.cfg, "moe_experts", 0) or 0))
    lora_capacity = property(
        lambda self: int(getattr(self.gpt.cfg, "lora_capacity", 0) or 0))

    def init_paged_cache(self, num_pages, page_size, dtype=None):
        return self.gpt.init_paged_cache(num_pages, page_size, dtype=dtype)

    def copy_pages(self, cache, src, dst):
        return self.gpt.copy_pages(cache, src, dst)

    def gather_pages(self, cache, idx):
        return self.gpt.gather_pages(cache, idx)

    def scatter_pages(self, cache, kv, dst):
        return self.gpt.scatter_pages(cache, kv, dst)

    def handoff_zero(self, num_pages, page_size, dtype=None):
        """Zeros in the shape :meth:`gather_pages` exports: one ``[L, 2,
        K, page, H*hd]`` array, or for a quantized pool (``dtype`` int8 /
        fp8) the ``(pages, scales)`` pair."""
        import numpy as np

        cfg = self.gpt.cfg
        shape = (cfg.num_layers, 2, int(num_pages), int(page_size))
        if dtype is None:
            return np.zeros(shape + (cfg.hidden_size,), cfg.dtype)
        return (np.zeros(shape + (cfg.hidden_size,), np.dtype(dtype)),
                np.zeros(shape + (cfg.num_heads,), np.float32))

    def forward(self, input_ids, attn_mask=None):
        if getattr(self.gpt.cfg, "moe_experts", 0):
            # collect the blocks' load-balance losses; loss() consumes
            # the stash within the SAME trace (hapi/bench compose
            # forward+loss in one step function)
            from ..moe import stats as moe_stats

            with moe_stats.collect() as ms:
                h = self.gpt(input_ids, attn_mask)  # [B,S,D]
            self._moe_aux = ms.total_aux()
        else:
            h = self.gpt(input_ids, attn_mask)  # [B,S,D]
        logits = jnp.einsum("bsd,vd->bsv", h, jnp.asarray(self.gpt.wte.weight))
        return constrain(logits, None, None, None)

    def forward_paged(self, input_ids, positions, pos_map, table, cache,
                      gather_last=None, adapter_ids=None):
        """Paged KV forward (see :meth:`GPTModel.forward_paged`).

        With ``gather_last`` (per-sequence prompt lengths ``[B]``), only
        the hidden state at position ``length-1`` is projected to logits
        — the prefill path needs just the next-token distribution, and
        skipping the ``[B,S,V]`` projection is the bulk of the prefill
        FLOPs for large vocabularies.  Returns ``(logits, new_cache)``
        with logits ``[B,T,V]`` (or ``[B,V]`` under ``gather_last``)."""
        h, cache = self.gpt.forward_paged(input_ids, positions, pos_map,
                                          table, cache,
                                          adapter_ids=adapter_ids)
        if gather_last is not None:
            idx = jnp.maximum(jnp.asarray(gather_last, jnp.int32) - 1, 0)
            h = jnp.take_along_axis(
                h, idx[:, None, None], axis=1)[:, 0]  # [B,D]
            logits = jnp.einsum("bd,vd->bv", h,
                                jnp.asarray(self.gpt.wte.weight))
            return constrain(logits, None, None), cache
        logits = jnp.einsum("bsd,vd->bsv", h,
                            jnp.asarray(self.gpt.wte.weight))
        return constrain(logits, None, None, None), cache

    def loss(self, logits, labels):
        """Shifted next-token cross entropy (labels = input_ids), plus
        ``moe_balance_weight ×`` the summed load-balance loss the MoE
        blocks recorded during :meth:`forward` (same trace)."""
        logits = logits[:, :-1]
        labels = jnp.asarray(labels)[:, 1:]
        if labels.dtype in (jnp.int64, jnp.uint32, jnp.uint64):
            labels = labels.astype(jnp.int32)
        if _fused_epilogues():
            # fused kernel (ops/fused_softmax_xent.py): online logsumexp
            # over vocab blocks — the [B·S, V] log-prob tensor the
            # XLA path writes to HBM never materializes
            from ..ops.fused_softmax_xent import softmax_cross_entropy

            V = logits.shape[-1]
            out = softmax_cross_entropy(logits.reshape(-1, V),
                                        labels.reshape(-1)).mean()
        else:
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, labels[..., None],
                                     axis=-1)[..., 0]
            out = -ll.mean()
        aux = getattr(self, "_moe_aux", None)
        if aux is not None:
            self._moe_aux = None  # consume: never leak across traces
            out = out + jnp.asarray(self.gpt.cfg.moe_balance_weight,
                                    out.dtype) * aux
        return out

    # -- 1F1B decomposition (consumed by Model.prepare when
    #    pipeline_configs={"schedule": "1f1b"}; see hapi/model.py) ----------
    def pipeline_pre(self, input_ids):
        """Embedding prologue — the first section of the reference's cut
        program (SectionWorker stage 0 holds the embedding lookup)."""
        B, S = input_ids.shape
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        x = self.gpt.wte(input_ids) + self.gpt.wpe(pos)
        return self.gpt.drop(x)

    def pipeline_post(self, h):
        """Final norm + tied LM head — the last section (holds the loss in
        the reference's SectionWorker; here the loss_fn composes outside)."""
        h = self.gpt.ln_f(h)
        logits = jnp.einsum("bsd,vd->bsv", h, jnp.asarray(self.gpt.wte.weight))
        return constrain(logits, None, None, None)

    def pipeline_decompose(self):
        """(pre, blocks, post) for the interleaved 1F1B train step: ``pre``
        and ``post`` run replicated over ``pipe``; ``blocks`` is the
        homogeneous pipelined section."""
        return {"pre": self.pipeline_pre,
                "blocks": list(self.gpt.blocks),
                "post": self.pipeline_post}
