"""Qwen3-Next (Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``,
``model_type: qwen3_next``) forward pass in plain jax.numpy, float32, highest
matmul precision.  No cache, no kernels, no chunks, no batching of the
recurrence: the whole history is one causal forward pass and the linear
layers run their recurrence ONE TOKEN AT A TIME.

    N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)           (float32)
    h = x + Mix(N1(x));  y = h + MoE(N2(h));  final N_f;  untied head

    full attention, layers with (i + 1) % full_attention_interval == 0:
          [q | gate] = W_q x per head (2 x head_dim each), k = W_k x,
          v = W_v x (num_key_value_heads heads); q, k through N over each
          head's head_dim; rotary (rotate-half pairs, theta) on the first
          partial_rotary_factor x head_dim dims of each head, the rest
          untouched; causal softmax at head_dim^-1/2, query head h reading
          K/V head h // (heads / kv heads); ctx * sigmoid(gate); W_o

    linear attention (the gated delta rule, arXiv:2412.06464), elsewhere:
          [q~ | k~ | v~] = W_qkv x, z = W_z x, [a | b] = W_ab x; causal
          depthwise conv of K taps over [q~ | k~ | v~] (zeros before
          position 0, no bias; tap j weighs the token K - 1 - j back), SiLU
          q = q' / sqrt(|q'|^2 + 1e-6) * d_k^-1/2, k = k' / sqrt(|k'|^2 + 1e-6)
          per key head; value head h reads key head h // (value / key heads)
          beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)
          S_t = e^g S_{t-1} + beta k (v - (e^g S_{t-1})^T k)^T,  S_0 = 0
          o = S_t^T q;  o / sqrt(mean(o^2) + eps) * w_o (a PLAIN gain, one
          weight shared by the heads) * silu(z);  W_out

    MoE: p = softmax(W_r x) over ALL the router's experts, float32; the
          top-k by p; w_e = p_e / sum of the k (norm_topk_prob)
          MoE(x) = sum over the chosen e THAT ARE HELD of w_e E_e(x)
                   + sigmoid(w_sg . x) E_shared(x),
          E(x) = W_down(silu(W_gate x) * W_up x)

``held``: the reference is given the same share of each layer's experts as
the program, ``expert_offset .. expert_offset + num_experts - 1`` of the
router's ``published.num_experts`` (read off the router's own width): what
the other experts would add is left out here as there, and the partial sum
goes on to the next layer (the `model-configs` guide's section 4).

What the published config does not settle is the configuration file's
``assumed``.  Departures from the published description, each for memory or
time and none in the function computed:
* the weights arrive in bfloat16 as they are served and are upcast a layer
  (the experts: an expert) at a time (bf16 -> f32 is exact);
* full attention runs over blocks of query rows (``lax.map``), so the
  ``[heads, S, S]`` scores never exist whole;
* the leaves are the program's layout (``families/qwen3_next.py``): the
  published ``in_proj_qkvz`` / ``in_proj_ba`` with their columns regrouped
  into ``[q~ | k~ | v~]``, ``z`` and ``[a | b]``, one matmul each;
* the experts: a loop over the HELD experts, every token through each, its
  output weighed by the token's ``w_e`` (0 where it did not choose e);
* in a control mode only the matmuls with weights and the attention's two
  products round their operands; the recurrence, the gates and the router
  stay float32, as the configuration states them.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import numerics as nm

Q_BLOCK = 256      # query rows per attention block
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def rms_norm(x, w, eps, zero_centered=True):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    w = w.astype(F32)
    return (y * (1.0 + w if zero_centered else w)).astype(x.dtype)


def rope(x, pos, theta, dims):
    """Rotate-half pairs ``(x[i], x[i + dims / 2])`` of the first ``dims``
    entries by ``pos * theta^(-2i / dims)``; the rest pass."""
    half = dims // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / dims)
    ang = pos.astype(F32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    a, b = xf[..., :half], xf[..., half:dims]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            xf[..., dims:]], -1).astype(x.dtype)


def full_attention(x, w, cfg, mode):
    B, S, _ = x.shape
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, rep = cfg["rms_norm_eps"], H // Hkv
    qkv = nm.matmul(x, w["mixer.qkv"], mode)
    qg = qkv[..., :2 * H * hd].reshape(B, S, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = qkv[..., 2 * H * hd:(2 * H + Hkv) * hd].reshape(B, S, Hkv, hd)
    v = qkv[..., (2 * H + Hkv) * hd:].reshape(B, S, Hkv, hd)
    pos = jnp.arange(S, dtype=jnp.int32)
    rd = int(hd * cfg["partial_rotary_factor"])
    q = rope(rms_norm(q, w["mixer.q_norm.weight"], eps), pos[:, None],
             cfg["rope_theta"], rd)
    k = rope(rms_norm(k, w["mixer.k_norm.weight"], eps), pos[:, None],
             cfg["rope_theta"], rd)
    # query head h reads K/V head h // rep
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
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
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, S, H, hd)
    ctx = (ctx.astype(F32) * jax.nn.sigmoid(gate.astype(F32))).astype(x.dtype)
    return nm.matmul(ctx.reshape(B, S, H * hd), w["mixer.out"], mode)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token.  ``q``, ``k`` ``[B, S, H, dk]``,
    ``v`` ``[B, S, H, dv]``, ``g``, ``beta`` ``[B, S, H]``, all float32;
    returns ``o`` ``[B, S, H, dv]``."""
    B, _, H, dk = q.shape

    def step(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[..., None, None]
        err = v - jnp.einsum("bhk,bhkv->bhv", k, S, precision=HI)
        S = S + k[..., :, None] * (beta[..., None] * err)[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q, S, precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), F32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def linear_attention(x, w, cfg, mode):
    B, S, _ = x.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K = cfg["linear_conv_kernel_dim"]
    pre = nm.matmul(x, w["mixer.qkv"], mode)
    padded = jnp.pad(pre.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    taps = w["mixer.conv"].astype(F32)
    y = jax.nn.silu(sum(taps[j] * padded[:, j:j + S] for j in range(K)))
    q = y[..., :Hk * dk].reshape(B, S, Hk, dk)
    k = y[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk)
    v = y[..., 2 * Hk * dk:].reshape(B, S, Hv, dv)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    # value head h reads key head h // (Hv / Hk)
    q = jnp.repeat(unit(q) * dk ** -0.5, Hv // Hk, axis=2)
    k = jnp.repeat(unit(k), Hv // Hk, axis=2)
    ab = nm.matmul(x, w["mixer.ab"], mode).astype(F32)
    g = -jnp.exp(w["mixer.A_log"].astype(F32)) * jax.nn.softplus(
        ab[..., :Hv] + w["mixer.dt_bias"].astype(F32))
    beta = jax.nn.sigmoid(ab[..., Hv:])
    o = delta_rule(q, k, v, g, beta)
    z = nm.matmul(x, w["mixer.gate"], mode).astype(F32).reshape(o.shape)
    y = rms_norm(o, w["mixer.o_norm.weight"], cfg["rms_norm_eps"],
                 zero_centered=False)
    y = (y * jax.nn.silu(z)).reshape(B, S, Hv * dv).astype(x.dtype)
    return nm.matmul(y, w["mixer.out"], mode)


def gated_mlp(x, w_gate, w_up, w_down, mode):
    g = nm.matmul(x, w_gate, mode)
    return nm.matmul(jax.nn.silu(g) * nm.matmul(x, w_up, mode), w_down, mode)


def route(x, w, cfg):
    """``x`` [N, D] -> (chosen experts [N, k] int32 over the router's whole
    width, their weights [N, k] float32).  Always float32: the router is
    stated so, in every mode."""
    p = jax.nn.softmax(jnp.matmul(x.astype(F32), w["mlp.router"].astype(F32),
                                  precision=HI), axis=-1)
    top_p, top_e = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", 1):
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return top_e.astype(jnp.int32), top_p


def moe(x, w, cfg, mode):
    """``x`` [N, D] -> the held experts' part of the sum + the gated shared
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
    shared = gated_mlp(x, w["mlp.shared_gate"], w["mlp.shared_up"],
                       w["mlp.shared_down"], mode).astype(F32)
    gate = jax.nn.sigmoid(nm.matmul(x, w["mlp.shared_gating"], mode)
                          .astype(F32))
    return (y + gate * shared).astype(x.dtype)


def layer_weights(params, i, dt):
    """Layer ``i``'s leaves by their short names; the float32 leaves (the
    decay's ``A_log`` and ``dt_bias``) stay float32 in every mode, and the
    stacked expert matrices are upcast an expert at a time, where used."""
    p = f"model.blocks.{i}."
    return {k[len(p):]: (v if v.dtype == jnp.float32
                         or k[len(p):].startswith("mlp.expert_")
                         else v.astype(dt))
            for k, v in params.items() if k.startswith(p)}


def layer_types(cfg):
    n = cfg["full_attention_interval"]
    return tuple("full_attention" if (i + 1) % n == 0 else "linear_attention"
                 for i in range(cfg["num_hidden_layers"]))


def hidden_states(params, ids, cfg, mode):
    """[B, S] token ids -> [B, S, D] final hidden states (after the last
    norm)."""
    dt = nm.compute_dtype(mode)
    eps = cfg["rms_norm_eps"]
    B, S = ids.shape
    x = params["model.embed"][ids].astype(dt)
    for i, kind in enumerate(layer_types(cfg)):
        w = layer_weights(params, i, dt)
        mix = full_attention if kind == "full_attention" else linear_attention
        x = x + mix(rms_norm(x, w["norm1.weight"], eps), w, cfg, mode)
        x = x + moe(rms_norm(x, w["norm2.weight"], eps).reshape(B * S, -1),
                    w, cfg, mode).reshape(x.shape)
    return rms_norm(x, params["model.norm_f.weight"].astype(dt), eps)


def logits_at(params, ids, rows, cfg, mode):
    """Float32 logits ``[B, R, V]`` at the positions ``rows`` [B, R]."""
    h = hidden_states(params, ids, cfg, mode)
    h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
    return nm.matmul(h, params["head"].astype(h.dtype), mode).astype(F32)


def static_items(cfg):
    """``numerics.static_items`` and the one boolean that enters the
    equations, as 0 / 1."""
    return nm.static_items(cfg) + (
        ("norm_topk_prob", int(cfg.get("norm_topk_prob", True))),)


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
    ``reference/joyai_flash.py:served_token_gaps``.  ``params`` are the
    weights as served (this chip's share of the experts).  Histories are
    right-padded with token 0: the model is causal, so what follows a
    request's last token changes nothing before it."""
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
