"""JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash ``config.json``,
``model_type: joyai_llm_flash``; the DeepSeek-V3 block, arXiv:2412.19437
sections 2.1-2.2) forward pass in plain jax.numpy, float32, highest matmul
precision.  No cache, no kernels: the whole history is one causal forward
pass, and attention is computed in the EXPANDED form (per-head keys and
values from the latent), never the absorbed one.

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Attn: c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads of [nope | rope]
          [c_kv | k_rope] = x W_kva; c_kv = RMSNorm(c_kv)
          RoPE (adjacent pairs, theta, no scaling) on q_rope and k_rope
          [k_nope | v] = c_kv W_kvb per head; k = [k_nope | k_rope]
          softmax(q k^T / sqrt(nope + rope), causal) v, then W_o
    FFN, layer < first_k_dense_replace: (silu(x W_gate) * (x W_up)) W_down
    FFN, after: s = sigmoid(x W_g); top-k of s + b; weights s of the chosen,
          / their sum, x routed_scaling_factor; sum_i w_i E_i(x) + E_shared(x)

Departures from the published description, each for memory and none in
the function computed:
* the weights arrive in bfloat16 as they are served and are upcast where
  they are used (bf16 -> f32 is exact): a layer's matrices per layer, an
  expert's three matrices per chunk of rows, never the model at once;
* attention runs over blocks of query rows (``lax.map``) so that the
  ``[heads, S, S]`` scores never exist whole;
* the routed experts do not multiply every token by every expert: the
  (token, choice) pairs are sorted by expert and walked in chunks of rows,
  each chunk looping over the experts that occur in it;
* ``n_group = topk_group = 1``: group-limited routing is the identity and
  is not written; ``num_nextn_predict_layers`` is 0 in the configuration
  as run, so there is no multi-token-prediction block.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import numerics as nm

Q_BLOCK = 256      # query rows per attention block
ROW_CHUNK = 256    # sorted (token, choice) rows per chunk of the experts


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta):
    """Adjacent pairs ``(x[2i], x[2i+1])`` rotated by ``pos * theta^(-2i/d)``
    (``rope_interleave: true``); ``pos`` broadcasts over leading axes."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = xf[..., 0], xf[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape).astype(x.dtype)


def attention(x, w, cfg, mode):
    """``x`` [B, S, D] (already normed) -> [B, S, D]."""
    B, S, _ = x.shape
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    pos = jnp.arange(S, dtype=jnp.int32)
    c_q = rms_norm(nm.matmul(x, w["attn.q_a"], mode),
                   w["attn.q_norm.weight"], eps)
    q = nm.matmul(c_q, w["attn.q_b"], mode).reshape(B, S, H, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], pos[None, :, None], theta)], -1)
    kva = nm.matmul(x, w["attn.kv_a"], mode)
    c_kv = rms_norm(kva[..., :r], w["attn.kv_norm.weight"], eps)
    k_rope = rope(kva[..., r:], pos[None, :], theta)  # one key, all heads
    kv = nm.matmul(c_kv, w["attn.kv_b"], mode).reshape(B, S, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None, :],
                                        (B, S, H, dr))], -1)
    v = kv[..., dn:]
    bq = math.gcd(S, Q_BLOCK)

    def block(args):
        qb, pb = args                      # [B, bq, H, dn + dr], [bq]
        s = nm.einsum("bqhd,bkhd->bhqk", qb, k, mode) / math.sqrt(dn + dr)
        s = jnp.where(pos[None, :] <= pb[:, None], s.astype(jnp.float32),
                      -jnp.inf)
        p = jax.nn.softmax(s, -1).astype(x.dtype)
        return nm.einsum("bhqk,bkhd->bqhd", p, v, mode)

    ctx = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(B, S // bq, bq, H, dn + dr), 1, 0),
        pos.reshape(S // bq, bq)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, S, H * dv)
    return nm.matmul(ctx, w["attn.out"], mode)


def gated_mlp(x, w_gate, w_up, w_down, mode):
    g = nm.matmul(x, w_gate, mode)
    return nm.matmul(jax.nn.silu(g) * nm.matmul(x, w_up, mode), w_down, mode)


def route(x, w, cfg):
    """Float32 whatever the mode: ids ``[N, k]`` and weights ``[N, k]``."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), w["mlp.router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    k = cfg["num_experts_per_tok"]
    _, ids = jax.lax.top_k(s + w["mlp.score_bias"].astype(jnp.float32), k)
    wt = jnp.take_along_axis(s, ids, -1)       # s, never s + b
    if cfg.get("norm_topk_prob", 1):
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), wt * cfg["routed_scaling_factor"]


def routed_experts(x, w, cfg, mode):
    """``x`` [N, D] -> sum over each token's chosen experts of ``w_i
    E_i(x)``.  The pairs, sorted by expert, are walked ROW_CHUNK rows at a
    time; a chunk loops over the experts that occur in it and upcasts each
    one's matrices as it comes to them."""
    N, D = x.shape
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    dt = nm.compute_dtype(mode)
    ids, wt = route(x, w, cfg)
    flat = ids.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    A = N * k
    n_chunks = -(-A // ROW_CHUNK)
    pad = n_chunks * ROW_CHUNK - A
    # padding rows read the zero row N and carry the expert id E (nobody)
    tok = jnp.concatenate([order // k, jnp.full((pad,), N, jnp.int32)])
    eid = jnp.concatenate([flat[order], jnp.full((pad,), E, jnp.int32)])
    wsort = jnp.concatenate([wt.reshape(-1)[order],
                             jnp.zeros((pad,), jnp.float32)])
    x0 = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])

    def chunk(c):
        rows = jax.lax.dynamic_slice(tok, (c * ROW_CHUNK,), (ROW_CHUNK,))
        ce = jax.lax.dynamic_slice(eid, (c * ROW_CHUNK,), (ROW_CHUNK,))
        xc = x0[rows]

        def one(e, acc):
            y = gated_mlp(xc, w["mlp.expert_gate"][e].astype(dt),
                          w["mlp.expert_up"][e].astype(dt),
                          w["mlp.expert_down"][e].astype(dt), mode)
            return jnp.where((ce == e)[:, None], y, acc)

        return jax.lax.fori_loop(ce[0], jnp.minimum(ce[-1], E - 1) + 1, one,
                                 jnp.zeros((ROW_CHUNK, D), x.dtype))

    ys = jax.lax.map(chunk, jnp.arange(n_chunks, dtype=jnp.int32))
    ys = ys.reshape(-1, D).astype(jnp.float32) * wsort[:, None]
    return jnp.zeros((N + 1, D), jnp.float32).at[tok].add(ys)[:N].astype(
        x.dtype)


def layer_weights(params, i, dt):
    """Layer ``i``'s leaves by their short names; all but the stacked
    expert matrices upcast here."""
    p = f"model.blocks.{i}."
    return {k[len(p):]: (v if k[len(p):].startswith("mlp.expert_")
                         else v.astype(dt))
            for k, v in params.items() if k.startswith(p)}


def hidden_states(params, ids, cfg, mode):
    """[B, S] token ids -> [B, S, D] final hidden states (after the last
    norm)."""
    dt = nm.compute_dtype(mode)
    eps = cfg["rms_norm_eps"]
    x = params["model.embed"][ids].astype(dt)
    B, S, D = x.shape
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(params, i, dt)
        x = x + attention(rms_norm(x, w["norm1.weight"], eps), w, cfg, mode)
        h = rms_norm(x, w["norm2.weight"], eps)
        if i < cfg["first_k_dense_replace"]:
            y = gated_mlp(h, w["mlp.gate"], w["mlp.up"], w["mlp.down"], mode)
        else:
            hf = h.reshape(B * S, D)
            y = routed_experts(hf, w, cfg, mode)
            if cfg["n_shared_experts"]:
                y = y + gated_mlp(hf, w["mlp.shared_gate"],
                                  w["mlp.shared_up"], w["mlp.shared_down"],
                                  mode)                 # counted once
            y = y.reshape(B, S, D)
        x = x + y
    return rms_norm(x, params["model.norm_f.weight"].astype(dt), eps)


def logits_at(params, ids, rows, cfg, mode):
    """Float32 logits ``[B, R, V]`` at the positions ``rows`` [B, R]."""
    h = hidden_states(params, ids, cfg, mode)
    h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
    return nm.matmul(h, params["head"].astype(h.dtype),
                     mode).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def logits(params, ids, *, cfg_items):
    """Float32 logits of every position, ``[B, S, V]``: what the tests
    compare the program's forward pass with."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        rows = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32),
                                ids.shape)
        return logits_at(params, ids, rows, cfg, "f32")


@functools.partial(jax.jit, static_argnames=("cfg_items", "control_mode"))
def _gaps(params, ids, rows, toks, *, cfg_items, control_mode):
    """Weights are arguments, never constants of the compiled program, so
    one compilation serves every seed."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        ref = logits_at(params, ids, rows, cfg, "f32")
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, toks[..., None], -1)[..., 0]
        top2 = jax.lax.top_k(ref, 2)[0]
        out = {"gap": gap, "margin": top2[..., 0] - top2[..., 1]}
        if control_mode is not None:
            low = logits_at(params, ids, rows, cfg, control_mode)
            pick = jnp.argmax(low, -1)
            out["control_gap"] = best - jnp.take_along_axis(
                ref, pick[..., None], -1)[..., 0]
    return out


def served_token_gaps(params, cfg, prompts, served, control_mode=None,
                      block_requests=2, pad_len=None, pad_out=None):
    """Teacher-forced check of served tokens: the interface of
    ``reference/gpt2.py:served_token_gaps``.  ``params`` are the weights as
    served (bfloat16 leaves are upcast where they are used)."""
    hist = [np.concatenate([np.asarray(p, np.int32), np.asarray(t, np.int32)])
            for p, t in zip(prompts, served)]
    L = pad_len or -(-max(len(h) for h in hist) // 128) * 128
    n_max = pad_out or max(len(t) for t in served)
    cfg_items = nm.static_items(cfg)
    results = []
    for b0 in range(0, len(hist), block_requests):
        blk = range(b0, min(b0 + block_requests, len(hist)))
        ids = np.zeros((block_requests, L), np.int32)
        rows = np.zeros((block_requests, n_max), np.int32)
        toks = np.zeros((block_requests, n_max), np.int32)
        for j, r in enumerate(blk):
            ids[j, :len(hist[r])] = hist[r]
            k = len(served[r])
            rows[j, :k] = len(prompts[r]) - 1 + np.arange(k)
            toks[j, :k] = served[r]
        out = jax.device_get(_gaps(
            params, jnp.asarray(ids), jnp.asarray(rows), jnp.asarray(toks),
            cfg_items=cfg_items, control_mode=control_mode))
        for j, r in enumerate(blk):
            k = len(served[r])
            results.append({key: np.asarray(v[j, :k], np.float64)
                            for key, v in out.items()})
    return results
