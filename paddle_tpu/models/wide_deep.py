"""Wide&Deep CTR model over mesh-sharded embedding tables.

This is the TPU-native replacement for the reference's parameter-server CTR
story: where the reference shards `large_scale_kv`
embedding tables across PS nodes and routes lookups through the
DistributeTranspiler's send/recv fabric
(python/paddle/fluid/transpiler/distribute_transpiler.py:256,
paddle/fluid/operators/distributed/large_scale_kv.h:773), here the tables
are ordinary jax Arrays sharded over the ``model`` mesh axis
(VocabParallelEmbedding) — GSPMD partitions each lookup's gather across the
table shards and moves rows over ICI, and ZeRO (the ``sharding`` axis)
shards the optimizer slots.  The full table never materializes on one chip,
which is the property PS mode existed to provide.

Model shape follows the classic CTR-DNN/Wide&Deep recipe (sparse id fields
+ dense features → shared embedding + MLP, plus a linear "wide" term).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.meta_parallel import VocabParallelEmbedding

__all__ = ["WideDeep", "wide_deep_tiny"]


class WideDeep(nn.Layer):
    """sparse_ids [B, F] int32 + dense [B, D] float → click logit [B, 1].

    ``vocab_size`` is the hashed id space shared by all sparse fields (the
    reference's CTR-DNN uses one table the same way).
    """

    def __init__(self, num_fields: int = 26, vocab_size: int = 10000,
                 embed_dim: int = 16, dense_dim: int = 13,
                 hidden_sizes=(64, 32), sparse: bool = False):
        super().__init__()
        self.num_fields = num_fields
        self.dense_dim = dense_dim
        # deep tower: shared vocab-sharded table.  sparse=True switches the
        # tables to SelectedRows gradients + lazy row updates — the O(k)
        # per-step cost the reference's PS lookup tables provide
        # (selected_rows.h:41); pair with Adam(lazy_mode=True).
        self.embedding = VocabParallelEmbedding(vocab_size, embed_dim,
                                                sparse=sparse)
        # wide tower: per-id scalar weight (a vocab-sharded linear term)
        self.wide = VocabParallelEmbedding(vocab_size, 1, sparse=sparse)
        layers = []
        d = dense_dim + num_fields * embed_dim
        for h in hidden_sizes:
            layers += [nn.Linear(d, h), nn.ReLU()]
            d = h
        layers.append(nn.Linear(d, 1))
        self.deep = nn.Sequential(*layers)

    def forward(self, sparse_ids, dense):
        B = sparse_ids.shape[0]
        emb = self.embedding(sparse_ids)              # [B, F, E]
        deep_in = jnp.concatenate(
            [jnp.asarray(dense, emb.dtype), emb.reshape(B, -1)], axis=1)
        deep_logit = self.deep(deep_in)               # [B, 1]
        wide_logit = self.wide(sparse_ids).sum(axis=1)  # [B, 1]
        return wide_logit + deep_logit

    def loss(self, logits, labels):
        """Sigmoid BCE-with-logits (stable form), mean over the batch."""
        labels = jnp.asarray(labels, logits.dtype).reshape(logits.shape)
        return jnp.mean(
            jnp.maximum(logits, 0) - logits * labels
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    def predict_proba(self, logits):
        return jax.nn.sigmoid(logits)


def wide_deep_tiny(**kw):
    cfg = dict(num_fields=4, vocab_size=64, embed_dim=8, dense_dim=4,
               hidden_sizes=(16,))
    cfg.update(kw)
    return WideDeep(**cfg)
