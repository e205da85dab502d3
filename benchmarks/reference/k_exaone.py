"""K-EXAONE (LGAI-EXAONE/K-EXAONE-236B-A23B ``config.json``, ``model_type:
exaone_moe``) forward pass in plain jax.numpy, float32, highest matmul
precision.  No cache, no ring, no kernels, no paging: the whole history is
one causal forward pass, and a window layer is the same attention under a
narrower mask.

    N(x) = x / sqrt(mean(x^2) + eps) * w                  (float32)
    h = x + N1(Mix(x));  y = h + N2(FFN(h));  final N_f;  untied head

    Mix, both kinds: [q | k | v] = x W_qkv (64 query heads, 8 K/V heads of
          128); q, k through N over each head's 128; query head h reads
          K/V head h // 8; softmax of q.k / sqrt(128) in float32; ctx W_o
      sliding_attention: rotate-half rotary, theta, on all of each head's
          dims; the key at kp is visible to the query at qp iff
          qp - sliding_window < kp <= qp (sliding_window keys, its own
          among them)
      full_attention: NO rotary; every kp <= qp visible

    FFN, mlp_layer_types "dense": (silu(x W_g) * (x W_u)) W_d
    FFN, "sparse": s = sigmoid(x W_r) over ALL the router's experts,
          float32; the top-k by s + b; w_e = routed_scaling_factor * s_e /
          sum of the chosen s (norm_topk_prob)
          sum over the chosen e THAT ARE HELD of w_e E_e(x) + E_shared(x),
          E(x) = W_down(silu(W_gate x) * W_up x)

``held``: the reference is given the same share of each layer's experts as
the program, ``expert_offset .. expert_offset + num_experts - 1`` of the
router's ``published.num_experts`` (read off the router's own width), and
the same slice of the vocabulary: what the other experts would add is left
out here as there (the `model-configs` guide's section 4).

What the published config does not settle is the configuration file's
``assumed``.  Departures from the published description, each for memory or
time and none in the function computed:
* the weights arrive in bfloat16 as they are served and are upcast where
  they are used (bf16 -> f32 is exact): a layer's attention matrices a
  layer, the dense MLP a block of its width, the experts an expert at a
  time, never the model at once;
* attention runs over blocks of query rows (``lax.map``), so the ``[heads,
  S, S]`` scores never exist whole; both kinds compute every score of the
  block and mask (a window layer skips nothing);
* the dense MLP is summed over blocks of its width (the products of a block
  of columns of W_g and W_u meet the same rows of W_d);
* the experts: a loop over the HELD experts, every token through each, its
  output weighed by the token's ``w_e`` (0 where it did not choose e);
* in a control mode only the matmuls with weights and the attention's two
  products round their operands; the router stays float32, as the
  configuration states it.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import numerics as nm

Q_BLOCK = 256      # query rows per attention block
F_BLOCK = 3072     # columns of the dense MLP's width per block
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def rms_norm(x, w, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def rope(x, pos, theta):
    """Rotate-half pairs ``(x[i], x[i + d / 2])`` of the last axis by ``pos
    * theta^(-2i / d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=F32) * 2.0 / d)
    ang = pos.astype(F32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def attention(x, w, cfg, mode, window):
    """``window`` None: a ``full_attention`` layer (no rotary, every earlier
    key); else a ``sliding_attention`` layer of that many keys."""
    B, S, _ = x.shape
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qkv = nm.matmul(x, w["mixer.qkv"], mode)
    q = qkv[..., :H * hd].reshape(B, S, H, hd)
    k = qkv[..., H * hd:(H + Hkv) * hd].reshape(B, S, Hkv, hd)
    v = qkv[..., (H + Hkv) * hd:].reshape(B, S, Hkv, hd)
    q = rms_norm(q, w["mixer.q_norm.weight"], eps)
    k = rms_norm(k, w["mixer.k_norm.weight"], eps)
    pos = jnp.arange(S, dtype=jnp.int32)
    if window is not None:
        q, k = (rope(t, pos[:, None], cfg["rope_theta"]) for t in (q, k))
    # query head h reads K/V head h // (H / Hkv)
    k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
    bq = math.gcd(S, Q_BLOCK)

    def block(args):
        qb, pb = args                                  # [B, bq, H, hd], [bq]
        s = nm.einsum("bqhd,bkhd->bhqk", qb, k, mode) / math.sqrt(hd)
        seen = pos[None, :] <= pb[:, None]
        if window is not None:
            seen = seen & (pos[None, :] > pb[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, s.astype(F32), -jnp.inf), -1)
        return nm.einsum("bhqk,bkhd->bqhd", p.astype(x.dtype), v, mode)

    ctx = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(B, S // bq, bq, H, hd), 1, 0),
        pos.reshape(S // bq, bq)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, S, H * hd)
    return nm.matmul(ctx, w["mixer.out"], mode)


def gated_mlp(x, w_gate, w_up, w_down, mode):
    g = nm.matmul(x, w_gate, mode)
    return nm.matmul(jax.nn.silu(g) * nm.matmul(x, w_up, mode), w_down, mode)


def dense_mlp(x, w, mode):
    """``x`` [N, D]; the weights are still as served: a block of the width
    is upcast as it is used."""
    dt, width = nm.compute_dtype(mode), w["mlp.gate"].shape[1]
    bf = math.gcd(width, F_BLOCK)

    def one(i, acc):
        cols = lambda m: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            m, i * bf, bf, axis=1).astype(dt)
        rows = jax.lax.dynamic_slice_in_dim(w["mlp.down"], i * bf, bf,
                                            axis=0).astype(dt)
        return acc + gated_mlp(x, cols(w["mlp.gate"]), cols(w["mlp.up"]),
                               rows, mode).astype(F32)

    return jax.lax.fori_loop(0, width // bf, one,
                             jnp.zeros(x.shape, F32)).astype(x.dtype)


def route(x, w, cfg):
    """``x`` [N, D] -> (chosen experts [N, k] int32 over the router's whole
    width, their weights [N, k] float32).  Always float32: the router is
    stated so, in every mode."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(F32), w["mlp.router"].astype(F32),
                                  precision=HI))
    _, top_e = jax.lax.top_k(s + w["mlp.score_bias"].astype(F32),
                             cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_e, -1)        # s, never s + b
    if cfg.get("norm_topk_prob", 1):
        top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    return top_e.astype(jnp.int32), top_s * cfg["routed_scaling_factor"]


def moe(x, w, cfg, mode):
    """``x`` [N, D] -> the held experts' part of the sum + the shared
    expert."""
    dt = nm.compute_dtype(mode)
    first, count = cfg.get("expert_offset", 0), w["mlp.expert_gate"].shape[0]
    top_e, top_w = route(x, w, cfg)

    def one(e, acc):
        # w_e of every token: its weight for expert first + e, 0 if not chosen
        we = jnp.sum(jnp.where(top_e == first + e, top_w, 0.0), -1)
        y = gated_mlp(x, w["mlp.expert_gate"][e].astype(dt),
                      w["mlp.expert_up"][e].astype(dt),
                      w["mlp.expert_down"][e].astype(dt), mode)
        return acc + we[:, None] * y.astype(F32)

    y = jax.lax.fori_loop(0, count, one, jnp.zeros(x.shape, F32))
    shared = gated_mlp(x, w["mlp.shared_gate"].astype(dt),
                       w["mlp.shared_up"].astype(dt),
                       w["mlp.shared_down"].astype(dt), mode)
    return (y + shared.astype(F32)).astype(x.dtype)


def layer_weights(params, i, dt):
    """Layer ``i``'s leaves by their short names.  The attention matrices
    and the gains are upcast here; the FFN's matrices stay as served and
    are upcast a block or an expert at a time, where used; the float32
    score bias stays float32 in every mode."""
    p = f"model.blocks.{i}."
    return {k[len(p):]: (v if v.dtype == jnp.float32
                         or k[len(p):].startswith("mlp.") else v.astype(dt))
            for k, v in params.items() if k.startswith(p)}


def hidden_states(params, ids, cfg, mode):
    """[B, S] token ids -> [B, S, D] final hidden states (after the last
    norm)."""
    dt = nm.compute_dtype(mode)
    eps = cfg["rms_norm_eps"]
    B, S = ids.shape
    x = params["model.embed"][ids].astype(dt)
    for i, (kind, ffn) in enumerate(zip(cfg["layer_types"],
                                        cfg["mlp_layer_types"])):
        w = layer_weights(params, i, dt)
        window = (cfg["sliding_window"] if kind == "sliding_attention"
                  else None)
        x = x + rms_norm(attention(x, w, cfg, mode, window),
                         w["norm1.weight"], eps)
        flat = x.reshape(B * S, -1)
        y = (dense_mlp(flat, w, mode) if ffn == "dense"
             else moe(flat, w, cfg, mode))
        x = x + rms_norm(y.reshape(x.shape), w["norm2.weight"], eps)
    return rms_norm(x, params["model.norm_f.weight"].astype(dt), eps)


def logits_at(params, ids, rows, cfg, mode):
    """Float32 logits ``[B, R, V]`` at the positions ``rows`` [B, R]."""
    h = hidden_states(params, ids, cfg, mode)
    h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
    return nm.matmul(h, params["head"].astype(h.dtype), mode).astype(F32)


def static_items(cfg):
    """``numerics.static_items``, the rotary base out of its nested group,
    the per-layer kinds of the layers that are built and the one boolean
    that enters the equations, as 0 / 1."""
    n = cfg["num_hidden_layers"]
    return nm.static_items(cfg) + (
        ("rope_theta", cfg["rope_parameters"]["rope_theta"]),
        ("layer_types", tuple(cfg["layer_types"][:n])),
        ("mlp_layer_types", tuple(cfg["mlp_layer_types"][:n])),
        ("norm_topk_prob", int(cfg.get("norm_topk_prob", True))))


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
                      block_requests=1, pad_len=None, pad_out=None):
    """Teacher-forced check of served tokens: the interface of
    ``reference/joyai_flash.py:served_token_gaps``.  ``params`` are the
    weights as served (this chip's share of the experts and of the
    vocabulary).  Histories are right-padded with token 0: the model is
    causal, so what follows a request's last token changes nothing before
    it.  One request a block: beside 12 GB of weights a second one's
    float32 activations do not fit."""
    hist = [np.concatenate([np.asarray(p, np.int32), np.asarray(t, np.int32)])
            for p, t in zip(prompts, served)]
    L = pad_len or -(-max(len(h) for h in hist) // 128) * 128
    n_max = pad_out or max(len(t) for t in served)
    kw = dict(cfg_items=static_items(cfg), control_mode=control_mode)
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
            **kw))
        for j, r in enumerate(blk):
            k = len(served[r])
            results.append({key: np.asarray(v[j, :k], np.float64)
                            for key, v in out.items()})
    return results
