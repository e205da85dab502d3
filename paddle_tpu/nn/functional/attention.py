"""Attention ops.

``scaled_dot_product_attention`` is the public entry (paddle 2.x API parity;
the reference era predates flash attention — SURVEY §5 marks long-context as
a new capability).  On TPU the hot path routes to the Pallas flash-attention
kernel in ``paddle_tpu.ops`` when shapes/dtypes allow; otherwise an XLA
composite (softmax(QK^T)V) that the compiler fuses.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ...framework import device as _device

__all__ = ["scaled_dot_product_attention"]


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None, rng_key=None, use_pallas=None):
    """query/key/value: (batch, seq, heads, head_dim) — paddle layout.

    Routes to the Pallas TPU flash kernel for long sequences; XLA path
    otherwise.  Returns (batch, seq, heads, head_dim).
    """
    q = jnp.asarray(query)
    k = jnp.asarray(key)
    v = jnp.asarray(value)

    if use_pallas is None:
        # gate threshold measured in-model on v5e: XLA's fused bf16
        # attention is flash-class, so the kernel only engages where
        # it doesn't lose (parity at seq >= 4096, with O(S) memory)
        use_pallas = (_device.on_tpu() and attn_mask is None
                      and dropout_p == 0.0 and q.shape[1] >= 4096
                      and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0
                      and q.shape[-1] in (64, 128, 256))
    if use_pallas:
        from ...ops.flash_attention import flash_attention

        # pallas kernel uses (batch, heads, seq, dim)
        out = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=is_causal)
        return out.transpose(0, 2, 1, 3)

    scale = 1.0 / math.sqrt(q.shape[-1])
    # (b, s, h, d) → (b, h, s, d)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    logits = jnp.matmul(qt, kt.transpose(0, 1, 3, 2),
                        preferred_element_type=jnp.float32) * scale
    if is_causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        logits = jnp.where(causal_mask, logits, -1e30)
    if attn_mask is not None:
        m = jnp.asarray(attn_mask)
        if m.dtype == jnp.bool_:
            logits = jnp.where(m, logits, -1e30)
        else:
            logits = logits + m.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        from .common import dropout as _dropout

        probs = _dropout(probs, p=dropout_p, training=True, key=rng_key)
    out = jnp.matmul(probs, vt, preferred_element_type=jnp.float32).astype(q.dtype)
    return out.transpose(0, 2, 1, 3)
