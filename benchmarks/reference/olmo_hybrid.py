"""Olmo-Hybrid (allenai/Olmo-Hybrid-7B ``config.json``, ``model_type:
olmo_hybrid``) forward pass in plain jax.numpy, float32, highest matmul
precision.  No cache, no kernels, no chunks: the whole history is one causal
forward pass and the linear layers run their recurrence ONE TOKEN AT A TIME.

    h = x + RMSNorm(Mix(x));  y = h + RMSNorm(MLP(h))      (both layer kinds)
    MLP(h) = W_down(silu(W_gate h) * W_up h);  final RMSNorm;  untied head

    full_attention:  q = RMSNorm(W_q x), k = RMSNorm(W_k x) over the whole
          projection, v = W_v x; heads of hidden / heads; causal softmax at
          head_dim^-1/2; NO rotary; W_o

    linear_attention (the gated delta rule, arXiv:2412.06464; beta in (0, 2)
          is arXiv:2411.12537), per head of d_k keys and d_v values:
          [q~ | k~ | v~] = W_qkv x; causal depthwise conv of K taps over
          time (zeros before position 0; tap j weighs the token K - 1 - j
          back), then SiLU
          q = q' / sqrt(|q'|^2 + 1e-6) * d_k^-1/2,  k = k' / sqrt(|k'|^2 + 1e-6)
          beta = 2 sigmoid(W_b x),  g = -exp(A_log) softplus(W_a x + dt_bias)
          S_t = e^g S_{t-1} + beta k (v - (e^g S_{t-1})^T k)^T,  S_0 = 0
          o = S_t^T q;  y = RMSNorm_{d_v}(o) * silu(W_g x);  W_o y

What the published config does not settle is the configuration file's
``assumed``: the block's norm placement and the QK-norm (the Olmo family's),
heads of 128, no rotary (``rope_theta`` null), the initialisations.

Departures, each for memory or time and none in the function computed:
* the weights arrive in bfloat16 as they are served and are upcast a layer
  at a time (bf16 -> f32 is exact);
* full attention runs over blocks of query rows (``lax.map``), so the
  ``[heads, S, S]`` scores never exist whole;
* the fused leaves of the program's layout (``qkv``, ``ab``) are one matmul
  here too: the columns are the same matrices side by side;
* in a control mode only the matmuls with weights and the attention's two
  products round their operands; the recurrence and the gates stay float32,
  as the configuration states them.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import numerics as nm

Q_BLOCK = 256      # query rows per attention block
F32 = jnp.float32


def rms_norm(x, w, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def full_attention(x, w, cfg, mode):
    B, S, D = x.shape
    H = cfg["num_attention_heads"]
    hd, eps = D // H, cfg["rms_norm_eps"]
    qkv = nm.matmul(x, w["mixer.qkv"], mode)
    q = rms_norm(qkv[..., :D], w["mixer.q_norm.weight"], eps)
    k = rms_norm(qkv[..., D:2 * D], w["mixer.k_norm.weight"], eps)
    q, k, v = (t.reshape(B, S, H, hd) for t in (q, k, qkv[..., 2 * D:]))
    pos = jnp.arange(S, dtype=jnp.int32)
    bq = math.gcd(S, Q_BLOCK)

    def block(args):
        qb, pb = args                                  # [B, bq, H, hd], [bq]
        s = nm.einsum("bqhd,bkhd->bhqk", qb, k, mode) / math.sqrt(hd)
        s = jnp.where(pos[None, :] <= pb[:, None], s.astype(F32), -jnp.inf)
        p = jax.nn.softmax(s, -1).astype(x.dtype)
        return nm.einsum("bhqk,bkhd->bqhd", p, v, mode)

    ctx = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(B, S // bq, bq, H, hd), 1, 0),
        pos.reshape(S // bq, bq)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, S, D)
    return nm.matmul(ctx, w["mixer.out"], mode)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token.  ``q``, ``k`` ``[B, S, H, dk]``,
    ``v`` ``[B, S, H, dv]``, ``g``, ``beta`` ``[B, S, H]``, all float32;
    returns ``o`` ``[B, S, H, dv]``."""
    B, _, H, dk = q.shape
    hi = jax.lax.Precision.HIGHEST

    def step(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[..., None, None]
        err = v - jnp.einsum("bhk,bhkv->bhv", k, S, precision=hi)
        S = S + k[..., :, None] * (beta[..., None] * err)[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q, S, precision=hi)

    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), F32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def linear_attention(x, w, cfg, mode):
    B, S, _ = x.shape
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    K = cfg["linear_conv_kernel_dim"]
    pre = nm.matmul(x, w["mixer.qkv"], mode)
    padded = jnp.pad(pre.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    taps = w["mixer.conv"].astype(F32)
    y = jax.nn.silu(sum(taps[j] * padded[:, j:j + S] for j in range(K)))
    q = y[..., :H * dk].reshape(B, S, H, dk)
    k = y[..., H * dk:2 * H * dk].reshape(B, S, H, dk)
    v = y[..., 2 * H * dk:].reshape(B, S, H, dv)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    ab = nm.matmul(x, w["mixer.ab"], mode).astype(F32)
    g = -jnp.exp(w["mixer.A_log"].astype(F32)) * jax.nn.softplus(
        ab[..., :H] + w["mixer.dt_bias"].astype(F32))
    beta = jax.nn.sigmoid(ab[..., H:])
    if cfg.get("linear_allow_neg_eigval", 1):
        beta = 2.0 * beta
    o = delta_rule(unit(q) * dk ** -0.5, unit(k), v, g, beta)
    gate = nm.matmul(x, w["mixer.gate"], mode).astype(F32).reshape(o.shape)
    y = rms_norm(o, w["mixer.o_norm.weight"], cfg["rms_norm_eps"])
    y = (y * jax.nn.silu(gate)).reshape(B, S, H * dv).astype(x.dtype)
    return nm.matmul(y, w["mixer.out"], mode)


def gated_mlp(x, w, mode):
    g = nm.matmul(x, w["mlp.gate"], mode)
    return nm.matmul(jax.nn.silu(g) * nm.matmul(x, w["mlp.up"], mode),
                     w["mlp.down"], mode)


def layer_weights(params, i, dt):
    """Layer ``i``'s leaves by their short names; the float32 leaves (the
    decay's ``A_log`` and ``dt_bias``) stay float32 in every mode."""
    p = f"model.blocks.{i}."
    return {k[len(p):]: (v if v.dtype == jnp.float32 else v.astype(dt))
            for k, v in params.items() if k.startswith(p)}


def hidden_states(params, ids, cfg, layer_types, mode):
    """[B, S] token ids -> [B, S, D] final hidden states (after the last
    norm)."""
    dt = nm.compute_dtype(mode)
    eps = cfg["rms_norm_eps"]
    x = params["model.embed"][ids].astype(dt)
    for i, kind in enumerate(layer_types):
        w = layer_weights(params, i, dt)
        mix = full_attention if kind == "full_attention" else linear_attention
        x = x + rms_norm(mix(x, w, cfg, mode), w["norm1.weight"], eps)
        x = x + rms_norm(gated_mlp(x, w, mode), w["norm2.weight"], eps)
    return rms_norm(x, params["model.norm_f.weight"].astype(dt), eps)


def logits_at(params, ids, rows, cfg, layer_types, mode):
    """Float32 logits ``[B, R, V]`` at the positions ``rows`` [B, R]."""
    h = hidden_states(params, ids, cfg, layer_types, mode)
    h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
    return nm.matmul(h, params["head"].astype(h.dtype), mode).astype(F32)


@functools.partial(jax.jit, static_argnames=("cfg_items", "layer_types"))
def logits(params, ids, *, cfg_items, layer_types):
    """Float32 logits of every position, ``[B, S, V]``: what the tests
    compare the program's forward pass with."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        rows = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32),
                                ids.shape)
        return logits_at(params, ids, rows, cfg, layer_types, "f32")


@functools.partial(jax.jit, static_argnames=("cfg_items", "layer_types",
                                             "control_mode"))
def _gaps(params, ids, rows, toks, *, cfg_items, layer_types, control_mode):
    """Weights are arguments, never constants of the compiled program, so
    one compilation serves every seed."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        ref = logits_at(params, ids, rows, cfg, layer_types, "f32")
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, toks[..., None], -1)[..., 0]
        top2 = jax.lax.top_k(ref, 2)[0]
        out = {"gap": gap, "margin": top2[..., 0] - top2[..., 1]}
        if control_mode is not None:
            low = logits_at(params, ids, rows, cfg, layer_types,
                            control_mode)
            pick = jnp.argmax(low, -1)
            out["control_gap"] = best - jnp.take_along_axis(
                ref, pick[..., None], -1)[..., 0]
    return out


def served_token_gaps(params, cfg, prompts, served, control_mode=None,
                      block_requests=2, pad_len=None, pad_out=None):
    """Teacher-forced check of served tokens: the interface of
    ``reference/joyai_flash.py:served_token_gaps``.  ``params`` are the
    weights as served.  Histories are right-padded with token 0: the model
    is causal, so what follows a request's last token changes nothing
    before it."""
    hist = [np.concatenate([np.asarray(p, np.int32), np.asarray(t, np.int32)])
            for p, t in zip(prompts, served)]
    L = pad_len or -(-max(len(h) for h in hist) // 128) * 128
    n_max = pad_out or max(len(t) for t in served)
    # static_items keeps numbers only: the one boolean that enters the
    # equations rides as 0 / 1
    kw = dict(cfg_items=nm.static_items(cfg) + ((
        "linear_allow_neg_eigval",
        int(cfg.get("linear_allow_neg_eigval", True))),),
              layer_types=tuple(
                  cfg["layer_types"][:cfg["num_hidden_layers"]]),
              control_mode=control_mode)
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
