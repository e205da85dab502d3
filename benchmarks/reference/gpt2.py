"""GPT-2 (Radford et al. 2019; openai/gpt-2 model.py) forward pass in plain
jax.numpy: learned positions, pre-LN blocks, causal attention, LM head tied
to the token embedding.  No cache: the whole history is one forward pass.

Departure from the published model, following the program under test: the
MLP's GELU is the exact erf form, not GPT-2's tanh approximation
(``gelu_new``).  The per-layer weights are stacked and the blocks are a
``lax.scan`` over layers.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import numerics as nm
from benchmarks.reference.bert import gelu, layer_norm

LAYER_KEYS = ("ln1.weight", "ln1.bias", "attn.qkv.weight", "attn.qkv.bias",
              "attn.out.weight", "attn.out.bias", "ln2.weight", "ln2.bias",
              "mlp.fc1.weight", "mlp.fc1.bias", "mlp.fc2.weight",
              "mlp.fc2.bias")
PREFIX = "gpt.blocks."


def stack_layers(params, n_layers):
    stacks = {k: jnp.stack([params[f"{PREFIX}{i}.{k}"]
                            for i in range(n_layers)]) for k in LAYER_KEYS}
    rest = {k: v for k, v in params.items() if not k.startswith(PREFIX)}
    return rest, stacks


def block(x, w, n_heads, eps, mode):
    B, S, D = x.shape
    hd = D // n_heads
    h = layer_norm(x, w["ln1.weight"], w["ln1.bias"], eps)
    qkv = nm.matmul(h, w["attn.qkv.weight"], mode) + w["attn.qkv.bias"]
    qkv = qkv.reshape(B, S, 3, n_heads, hd)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    scores = nm.einsum("bhqd,bhkd->bhqk", q, k, mode) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, -1).astype(x.dtype)
    ctx = nm.einsum("bhqk,bhkd->bhqd", probs, v, mode)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, D)
    x = x + nm.matmul(ctx, w["attn.out.weight"], mode) + w["attn.out.bias"]
    h = layer_norm(x, w["ln2.weight"], w["ln2.bias"], eps)
    h = gelu(nm.matmul(h, w["mlp.fc1.weight"], mode) + w["mlp.fc1.bias"])
    return x + nm.matmul(h, w["mlp.fc2.weight"], mode) + w["mlp.fc2.bias"]


def hidden_states(rest, stacks, ids, cfg, mode):
    """[B, S] token ids -> [B, S, D] final hidden states (after ln_f)."""
    dt = nm.compute_dtype(mode)
    rest = {k: v.astype(dt) for k, v in rest.items()}
    stacks = {k: v.astype(dt) for k, v in stacks.items()}
    S = ids.shape[1]
    x = rest["gpt.wte.weight"][ids] + rest["gpt.wpe.weight"][:S][None]

    def body(x, w):
        return block(x, w, cfg["n_head"], cfg["layer_norm_epsilon"],
                     mode), None

    x, _ = jax.lax.scan(body, x, stacks)
    return layer_norm(x, rest["gpt.ln_f.weight"], rest["gpt.ln_f.bias"],
                      cfg["layer_norm_epsilon"])


def logits_at(rest, stacks, ids, rows, cfg, mode):
    """Float32 logits ``[B, R, V]`` at the positions ``rows`` [B, R]."""
    h = hidden_states(rest, stacks, ids, cfg, mode)
    h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
    wte = rest["gpt.wte.weight"].astype(h.dtype)
    return nm.einsum("brd,vd->brv", h, wte, mode).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control_mode"))
def _gaps(rest, stacks, ids, rows, toks, *, cfg_items, control_mode):
    """Weights are arguments, never constants of the compiled program, so
    one compilation serves every seed."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        ref = logits_at(rest, stacks, ids, rows, cfg, "f32")
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, toks[..., None], -1)[..., 0]
        top2 = jax.lax.top_k(ref, 2)[0]
        out = {"gap": gap, "margin": top2[..., 0] - top2[..., 1]}
        if control_mode is not None:
            low = logits_at(rest, stacks, ids, rows, cfg, control_mode)
            pick = jnp.argmax(low, -1)
            out["control_gap"] = best - jnp.take_along_axis(
                ref, pick[..., None], -1)[..., 0]
    return out


def served_token_gaps(params, cfg, prompts, served, control_mode=None,
                      block_requests=8, pad_len=None, pad_out=None):
    """Teacher-forced check of served tokens.  For each request the
    reference runs once over prompt + served tokens; at each position that
    predicted a served token it reads how far that token's logit lies below
    the reference's best (0 where the served token is the reference's
    argmax).  With ``control_mode`` the same is read for the token that the
    lower-precision forward puts first at that position.

    Returns per request a dict of float arrays ``gap`` (and ``control_gap``)
    plus ``margin``, the reference's top-2 margin there.  ``pad_len`` and
    ``pad_out`` fix the padded history length and answer length, so that a
    cell compiles one shape whatever the sample."""
    rest, stacks = stack_layers(params, cfg["n_layer"])
    hist = [np.concatenate([np.asarray(p, np.int32), np.asarray(t, np.int32)])
            for p, t in zip(prompts, served)]
    L = pad_len or -(-max(len(h) for h in hist) // 128) * 128
    n_max = pad_out or max(len(t) for t in served)
    cfg_items = nm.static_items(cfg)

    def run(ids, rows, toks):
        return _gaps(rest, stacks, ids, rows, toks, cfg_items=cfg_items,
                     control_mode=control_mode)

    results = []
    for b0 in range(0, len(hist), block_requests):
        blk = range(b0, min(b0 + block_requests, len(hist)))
        n = block_requests
        ids = np.zeros((n, L), np.int32)
        rows = np.zeros((n, n_max), np.int32)
        toks = np.zeros((n, n_max), np.int32)
        for j, r in enumerate(blk):
            ids[j, :len(hist[r])] = hist[r]
            start = len(prompts[r]) - 1
            k = len(served[r])
            rows[j, :k] = start + np.arange(k)
            toks[j, :k] = served[r]
        out = jax.device_get(run(jnp.asarray(ids), jnp.asarray(rows),
                                 jnp.asarray(toks)))
        for j, r in enumerate(blk):
            k = len(served[r])
            results.append({key: np.asarray(v[j, :k], np.float64)
                            for key, v in out.items()})
    return results
