"""BERT — bidirectional transformer encoder, tensor-parallel-ready.

Reference workload parity: the reference ships transformer encoder layers
(python/paddle/nn/layer/transformer.py TransformerEncoder) and BERT-class
training is the north-star benchmark (``benchmarks/run.py --workload
bert_base.pretrain_s128``: BERT-base samples/s on one chip).
Reuses the GPT parallel blocks (same megatron column/row sharding) with a
bidirectional mask and BERT's token-type embeddings + pooler + MLM/NSP heads.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.meta_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    constrain,
)
from ..nn import initializer as I
from ..nn.layer_base import Layer
from .gpt import GPTConfig, ParallelMLP, _fused_epilogues

__all__ = [
    "BertConfig",
    "BertModel",
    "BertForPretraining",
    "BertForSequenceClassification",
    "BertForQuestionAnswering",
    "bert_base",
    "bert_tiny",
]


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072, max_position=512,
                 type_vocab_size=2, dropout=0.1, layer_norm_epsilon=1e-12,
                 dtype="float32", moe_experts=0, moe_top_k=2,
                 moe_capacity_factor=1.25, moe_jitter=0.01,
                 quantization="none"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.type_vocab_size = type_vocab_size
        self.dropout = dropout
        self.layer_norm_epsilon = layer_norm_epsilon
        self.dtype = dtype
        #: > 0 swaps each layer's dense MLP for a routed MoELayer with
        #: that many experts (paddle_tpu/moe; knobs mirror GPTConfig)
        self.moe_experts = moe_experts
        self.moe_top_k = moe_top_k
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_jitter = moe_jitter
        #: "none" | "int8" | "fp8" — serving weight quantization (same
        #: contract as ``GPTConfig.quantization``: parallel-linear
        #: weights quantize at init, forwards dispatch on weight dtype)
        if quantization not in ("none", "int8", "fp8"):
            raise ValueError(
                f"quantization must be 'none', 'int8' or 'fp8', got "
                f"{quantization!r}")
        self.quantization = quantization


def bert_base(**kw):
    return BertConfig(**kw)


def bert_tiny(**kw):
    cfg = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
               intermediate_size=64, max_position=64, dropout=0.0)
    cfg.update(kw)
    return BertConfig(**cfg)


class BertSelfAttention(Layer):
    """Bidirectional multi-head attention, model-axis-sharded heads."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        d, h = cfg.hidden_size, cfg.num_heads
        self.num_heads = h
        self.head_dim = d // h
        self.qkv = ColumnParallelLinear(d, 3 * d, gather_output=False)
        self.out = RowParallelLinear(d, d, input_is_parallel=True)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x, attn_mask=None):
        B, S, D = x.shape
        qkv = self.qkv(x).reshape(B, S, 3, self.num_heads, self.head_dim)
        qkv = constrain(qkv, None, None, None, "model", None)
        q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(self.head_dim)
        if attn_mask is not None:
            # keep the hot graph in the compute dtype: an f32 mask would
            # silently upcast bf16 scores (and the softmax) to f32
            scores = scores + attn_mask.astype(scores.dtype)
        probs = self.drop(jax.nn.softmax(scores, axis=-1))
        ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, D)
        ctx = constrain(ctx, None, None, "model")
        return self.out(ctx)


class BertLayer(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        gcfg = GPTConfig(hidden_size=cfg.hidden_size,
                         intermediate_size=cfg.intermediate_size,
                         dropout=cfg.dropout,
                         moe_experts=getattr(cfg, "moe_experts", 0),
                         moe_top_k=getattr(cfg, "moe_top_k", 2),
                         moe_capacity_factor=getattr(
                             cfg, "moe_capacity_factor", 1.25),
                         moe_jitter=getattr(cfg, "moe_jitter", 0.01))
        self.attn = BertSelfAttention(cfg)
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        if gcfg.moe_experts:
            from ..moe import MoELayer

            self.mlp = MoELayer(gcfg)
        else:
            self.mlp = ParallelMLP(gcfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x, attn_mask=None):
        # post-LN (original BERT): LN(x + sublayer(x))
        if _fused_epilogues(x.shape[-1]):
            from ..ops.fused_layernorm import layernorm_residual
            _, x = layernorm_residual(
                self.drop(self.attn(x, attn_mask)), x,
                self.ln1.weight.value, self.ln1.bias.value,
                epsilon=self.ln1.epsilon)
            _, x = layernorm_residual(
                self.mlp(x), x, self.ln2.weight.value, self.ln2.bias.value,
                epsilon=self.ln2.epsilon)
            return x
        x = self.ln1(x + self.drop(self.attn(x, attn_mask)))
        x = self.ln2(x + self.mlp(x))
        return x


class BertEmbeddings(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        attr = nn.ParamAttr(initializer=I.Normal(std=0.02))
        self.word = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        self.position = nn.Embedding(cfg.max_position, cfg.hidden_size, weight_attr=attr)
        self.token_type = nn.Embedding(cfg.type_vocab_size, cfg.hidden_size, weight_attr=attr)
        self.ln = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, input_ids, token_type_ids=None):
        B, S = input_ids.shape
        # i32 index math: under the x64 API surface a bare arange is i64,
        # which doubles index traffic on TPU for no benefit
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = self.word(input_ids) + self.position(pos) + self.token_type(token_type_ids)
        return self.drop(self.ln(x))


class BertModel(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        self.layers = nn.LayerList([BertLayer(cfg) for _ in range(cfg.num_layers)])
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.pooler_act = nn.Tanh()
        if getattr(cfg, "quantization", "none") != "none":
            # same init-time weight quantization as GPTModel: the
            # parallel linears (attention qkv/out + the shared
            # ParallelMLP) dispatch on weight dtype
            from ..slim.quantization import quantize_weights

            quantize_weights(self, cfg.quantization)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        """attention_mask: [B, S] with 1 = attend, 0 = pad."""
        x = self.embeddings(input_ids, token_type_ids)
        mask = None
        if attention_mask is not None:
            mask = (1.0 - jnp.asarray(attention_mask, x.dtype)) * jnp.asarray(
                -1e9, x.dtype)
            mask = mask[:, None, None, :]  # [B,1,1,S] additive
        for layer in self.layers:
            x = layer(x, mask)
        pooled = self.pooler_act(self.pooler(x[:, 0]))
        return x, pooled


class BertForPretraining(Layer):
    """MLM (tied decoder) + NSP heads."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.bert = BertModel(cfg)
        self.transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.act = nn.GELU()
        self.ln = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.nsp = nn.Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_positions=None):
        """``masked_positions`` [B, P] (int): gather only the masked tokens
        before the vocab projection — standard MLM pretraining computes the
        decoder over max_predictions_per_seq (~20) positions, not all S
        (the A100 CUDA baselines do the same; computing the full [B,S,V]
        logits would be ~6× the vocab-projection FLOPs)."""
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        if masked_positions is not None:
            idx = jnp.asarray(masked_positions, jnp.int32)
            seq = jnp.take_along_axis(seq, idx[..., None], axis=1)  # [B,P,D]
        h = self.ln(self.act(self.transform(seq)))
        mlm_logits = jnp.einsum(
            "bsd,vd->bsv", h, jnp.asarray(self.bert.embeddings.word.weight))
        return constrain(mlm_logits, None, None, None), self.nsp(pooled)

    def loss(self, mlm_logits, nsp_logits, mlm_labels, nsp_labels,
             ignore_index: int = -100):
        labels = jnp.asarray(mlm_labels)
        if labels.dtype in (jnp.int64, jnp.uint32, jnp.uint64):
            labels = labels.astype(jnp.int32)  # i32 gather on the big tensor
        safe = jnp.where(labels == ignore_index, 0, labels)
        mask32 = (labels != ignore_index).astype(jnp.float32)
        if _fused_epilogues():
            from ..ops.fused_softmax_xent import softmax_cross_entropy
            V = mlm_logits.shape[-1]
            per = softmax_cross_entropy(mlm_logits.reshape(-1, V),
                                        safe.reshape(-1))
            mlm_loss = ((per * mask32.reshape(-1)).sum()
                        / jnp.maximum(mask32.sum(), 1.0))
        else:
            logp = jax.nn.log_softmax(mlm_logits, axis=-1)
            ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
            mask = mask32.astype(logp.dtype)
            mlm_loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        nsp_logp = jax.nn.log_softmax(nsp_logits, axis=-1)
        nsp_loss = -jnp.take_along_axis(
            nsp_logp,
            jnp.asarray(nsp_labels).astype(jnp.int32).reshape(-1, 1),
            axis=-1).mean()
        return mlm_loss + nsp_loss


class BertForQuestionAnswering(Layer):
    """Extractive-QA (SQuAD) head: per-token start/end logits over the
    encoder states (BERT-base SQuAD fine-tune)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.bert = BertModel(cfg)
        self.qa_outputs = nn.Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        seq, _ = self.bert(input_ids, token_type_ids, attention_mask)
        logits = self.qa_outputs(seq)                    # [B, S, 2]
        start, end = logits[..., 0], logits[..., 1]      # [B, S] each
        return start, end

    @staticmethod
    def loss(start_logits, end_logits, start_pos, end_pos):
        """Mean of start/end cross-entropies (the SQuAD objective).
        Positions outside the sequence — answers truncated away — are
        remapped to ignore_index and skipped, the standard SQuAD recipe
        (clamping them instead would train toward the last token)."""
        from ..nn import functional as F

        S = start_logits.shape[-1]

        def prep(pos):
            pos = jnp.asarray(pos, jnp.int32).reshape(-1)
            return jnp.where((pos < 0) | (pos >= S), -100, pos)

        return 0.5 * (
            F.cross_entropy(start_logits.astype(jnp.float32),
                            prep(start_pos))
            + F.cross_entropy(end_logits.astype(jnp.float32),
                              prep(end_pos)))


class BertForSequenceClassification(Layer):
    def __init__(self, cfg: BertConfig, num_classes: int = 2):
        super().__init__()
        self.bert = BertModel(cfg)
        self.drop = nn.Dropout(cfg.dropout)
        self.classifier = nn.Linear(cfg.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        return self.classifier(self.drop(pooled))
