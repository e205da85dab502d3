"""Kimi Linear (moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``,
``model_type: kimi_linear``; arXiv:2510.26692) forward pass in plain
jax.numpy, float32, highest matmul precision.  No cache, no kernels, no
chunks, no pages: the whole history is one causal forward pass, the KDA
layers run their recurrence ONE TOKEN AT A TIME and the latent attention is
EXPANDED to per-head keys and values.

    N(x) = x / sqrt(mean(x^2) + eps) * w                  (float32)
    h = x + Mix(N1(x));  y = h + FFN(N2(h));  final N_f;  untied head

    Mix, a layer in linear_attn_config.kda_layers (1-based), per head of
          d = linear_attn_config.head_dim keys and values:
          [q~ | k~ | v~] = x W_qkv; causal depthwise conv of
          short_conv_kernel_size taps over time (zeros before position 0;
          tap j weighs the token K - 1 - j back), then SiLU
          q = q' / sqrt(|q'|^2 + 1e-6) * d^-1/2,  k = k' / sqrt(|k'|^2 + 1e-6)
          g = -exp(A_log[h]) softplus((x W_fa) W_fb + dt_bias)   in R^d
          beta = sigmoid(x W_b)
          S' = Diag(e^g) S_{t-1};  S_t = S' + beta k (v - S'^T k)^T,  S_0 = 0
          o = S_t^T q;  y = N_d(o) * sigmoid((x W_ga) W_gb);  y W_o
    Mix, a layer in full_attn_layers (latent attention, NO positions):
          q = x W_q, per head [q_n (qk_nope_head_dim) | q_p (qk_rope_head_dim)]
          [c | k_p] = x W_kva;  c = N(c);  [k_n | v] = c W_kvb per head
          softmax over kp <= qp of (q_n . k_n + q_p . k_p) / sqrt(d_n + d_p)
          in float32; ctx W_o.  Nothing is rotated (mla_use_nope).

    FFN, layer < first_k_dense_replace: (silu(x W_g) * (x W_u)) W_d
    FFN, else: s = sigmoid(x W_r) over ALL the router's experts, float32;
          the top-k by s + b; w_e = routed_scaling_factor * s_e / sum of the
          chosen s (moe_renormalize)
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
  they are used (bf16 -> f32 is exact): a layer's mixer matrices a layer,
  the dense MLP a block of its width, the experts an expert at a time;
* the three depthwise convolutions are one over ``[q~ | k~ | v~]`` (the
  columns are the same taps side by side), as the fused ``W_qkv`` is one
  matmul;
* latent attention runs over blocks of query rows (``lax.map``), so the
  ``[heads, S, S]`` scores never exist whole;
* the dense MLP is summed over blocks of its width;
* the experts: a loop over the HELD experts, every token through each, its
  output weighed by the token's ``w_e`` (0 where it did not choose e);
* in a control mode only the matmuls with weights and the attention's two
  products round their operands; the recurrence, the decay and the router
  stay float32, as the configuration states them.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import numerics as nm
# the norm and the two MLPs are that reference's own equations (N, and the
# gated-SiLU MLP whole and summed over blocks of its width)
from benchmarks.reference.k_exaone import (dense_mlp, gated_mlp,  # noqa: F401
                                           rms_norm)

Q_BLOCK = 256      # query rows per attention block
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


# -- Kimi Delta Attention ---------------------------------------------------------
def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token.  ``q``, ``k``, ``g`` ``[B, S, H,
    d]``, ``v`` ``[B, S, H, dv]``, ``beta`` ``[B, S, H]``, all float32;
    returns ``o`` ``[B, S, H, dv]``."""
    B, _, H, d = q.shape

    def step(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[..., None]           # one decay a key channel
        err = v - jnp.einsum("bhk,bhkv->bhv", k, S, precision=HI)
        S = S + k[..., :, None] * (beta[..., None] * err)[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q, S, precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((B, H, d, v.shape[-1]), F32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda(x, w, cfg, mode):
    B, S, _ = x.shape
    H, d, K = cfg["linear_num_heads"], cfg["linear_head_dim"], cfg[
        "short_conv_kernel_size"]
    pre = nm.matmul(x, w["mixer.qkv"], mode)
    padded = jnp.pad(pre.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    taps = w["mixer.conv"].astype(F32)
    y = jax.nn.silu(sum(taps[j] * padded[:, j:j + S] for j in range(K)))
    q, k, v = (y[..., n * H * d:(n + 1) * H * d].reshape(B, S, H, d)
               for n in range(3))

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    f = nm.matmul(nm.matmul(x, w["mixer.f_a"], mode), w["mixer.f_b"], mode)
    g = -jnp.exp(w["mixer.A_log"].astype(F32))[:, None] * jax.nn.softplus(
        f.astype(F32) + w["mixer.dt_bias"].astype(F32)).reshape(B, S, H, d)
    beta = jax.nn.sigmoid(nm.matmul(x, w["mixer.b"], mode).astype(F32))
    o = delta_rule(unit(q) * d ** -0.5, unit(k), v, g, beta)
    gate = nm.matmul(nm.matmul(x, w["mixer.g_a"], mode), w["mixer.g_b"],
                     mode).astype(F32).reshape(o.shape)
    y = rms_norm(o, w["mixer.o_norm.weight"], cfg["rms_norm_eps"])
    y = (y * jax.nn.sigmoid(gate)).reshape(B, S, H * d).astype(x.dtype)
    return nm.matmul(y, w["mixer.out"], mode)


# -- latent attention without positions ----------------------------------------------
def latent_attention(x, w, cfg, mode):
    B, S, _ = x.shape
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    pos = jnp.arange(S, dtype=jnp.int32)
    q = nm.matmul(x, w["mixer.q"], mode).reshape(B, S, H, dn + dr)
    kva = nm.matmul(x, w["mixer.kv_a"], mode)
    c = rms_norm(kva[..., :r], w["mixer.kv_norm.weight"], cfg["rms_norm_eps"])
    kv = nm.matmul(c, w["mixer.kv_b"], mode).reshape(B, S, H, dn + dv)
    # one unrotated key of dr dims, shared by all heads
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kva[:, :, None, r:], (B, S, H, dr))], -1)
    v = kv[..., dn:]
    bq = math.gcd(S, Q_BLOCK)

    def block(args):
        qb, pb = args                      # [B, bq, H, dn + dr], [bq]
        s = nm.einsum("bqhd,bkhd->bhqk", qb, k, mode) / math.sqrt(dn + dr)
        s = jnp.where(pos[None, :] <= pb[:, None], s.astype(F32), -jnp.inf)
        p = jax.nn.softmax(s, -1).astype(x.dtype)
        return nm.einsum("bhqk,bkhd->bqhd", p, v, mode)

    ctx = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(B, S // bq, bq, H, dn + dr), 1, 0),
        pos.reshape(S // bq, bq)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, S, H * dv)
    return nm.matmul(ctx, w["mixer.out"], mode)


# -- the FFNs ------------------------------------------------------------------------
def route(x, w, cfg):
    """``x`` [N, D] -> (chosen experts [N, k] int32 over the router's whole
    width, their weights [N, k] float32).  Always float32: the router is
    stated so, in every mode."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(F32), w["mlp.router"].astype(F32),
                                  precision=HI))
    _, top_e = jax.lax.top_k(s + w["mlp.score_bias"].astype(F32),
                             cfg["num_experts_per_token"])
    top_s = jnp.take_along_axis(s, top_e, -1)        # s, never s + b
    if cfg.get("moe_renormalize", 1):
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


# -- the model -----------------------------------------------------------------------
def layer_weights(params, i, dt):
    """Layer ``i``'s leaves by their short names.  The mixer's matrices and
    the gains are upcast here; the FFN's matrices stay as served and are
    upcast a block or an expert at a time, where used; the float32 leaves
    (``A_log``, ``dt_bias``, the score bias) stay float32 in every mode."""
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
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(params, i, dt)
        mix = latent_attention if i + 1 in cfg["full_attn_layers"] else kda
        x = x + mix(rms_norm(x, w["norm1.weight"], eps), w, cfg, mode)
        flat = rms_norm(x, w["norm2.weight"], eps).reshape(B * S, -1)
        y = (dense_mlp(flat, w, mode) if i < cfg["first_k_dense_replace"]
             else moe(flat, w, cfg, mode))
        x = x + y.reshape(x.shape)
    return rms_norm(x, params["model.norm_f.weight"].astype(dt), eps)


def logits_at(params, ids, rows, cfg, mode):
    """Float32 logits ``[B, R, V]`` at the positions ``rows`` [B, R]."""
    h = hidden_states(params, ids, cfg, mode)
    h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
    return nm.matmul(h, params["head"].astype(h.dtype), mode).astype(F32)


def static_items(cfg):
    """``numerics.static_items``, the KDA sizes out of their nested group
    (``linear_num_heads``, ``linear_head_dim``, ``short_conv_kernel_size``,
    the 1-based ``full_attn_layers``) and the one boolean that enters the
    equations, as 0 / 1."""
    lin = cfg["linear_attn_config"]
    return nm.static_items(cfg) + (
        ("linear_num_heads", lin["num_heads"]),
        ("linear_head_dim", lin["head_dim"]),
        ("short_conv_kernel_size", lin["short_conv_kernel_size"]),
        ("full_attn_layers", tuple(lin["full_attn_layers"])),
        ("moe_renormalize", int(cfg.get("moe_renormalize", True))))


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
    it.  One request a block: beside 7.5 GB of weights and 3 GB left of the
    caches a second one's float32 activations are not worth the risk."""
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
