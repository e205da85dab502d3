"""BERT (Devlin et al. 2018; google-research/bert modeling.py) pretraining
step in plain jax.numpy: post-LN encoder, MLM head on the masked positions
with the decoder tied to the word embeddings, NSP head, AdamW.

Departures from the published model, which follow the program under test
so that the two compute the same function: the tied MLM decoder has no
output bias; weight decay is decoupled and applied to every parameter
(w <- w' - lr*wd*w', with w' the Adam-updated value); dropout is 0.

Parameters are a flat dict named as ``benchmarks/families/bert.py`` names
them.  The per-layer weights are stacked and the encoder is a ``lax.scan``
over layers, which compiles one layer instead of twelve.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import numerics as nm

LAYER_KEYS = ("attn.qkv.weight", "attn.qkv.bias", "attn.out.weight",
              "attn.out.bias", "ln1.weight", "ln1.bias", "mlp.fc1.weight",
              "mlp.fc1.bias", "mlp.fc2.weight", "mlp.fc2.bias", "ln2.weight",
              "ln2.bias")


def stack_layers(params, n_layers, prefix="bert.layers."):
    """Flat dict -> (dict of non-layer leaves, dict of [L, ...] stacks)."""
    stacks = {k: jnp.stack([params[f"{prefix}{i}.{k}"]
                            for i in range(n_layers)]) for k in LAYER_KEYS}
    rest = {k: v for k, v in params.items() if not k.startswith(prefix)}
    return rest, stacks


def unstack_layers(rest, stacks, prefix="bert.layers."):
    out = dict(rest)
    for k, v in stacks.items():
        for i in range(v.shape[0]):
            out[f"{prefix}{i}.{k}"] = v[i]
    return out


def layer_norm(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=False)


def encoder_layer(x, w, n_heads, eps, mode, mask=None):
    B, S, D = x.shape
    hd = D // n_heads
    qkv = nm.matmul(x, w["attn.qkv.weight"], mode) + w["attn.qkv.bias"]
    qkv = qkv.reshape(B, S, 3, n_heads, hd)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    scores = nm.einsum("bhqd,bhkd->bhqk", q, k, mode) / math.sqrt(hd)
    if mask is not None:
        scores = scores + mask
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(x.dtype)
    ctx = nm.einsum("bhqk,bhkd->bhqd", probs, v, mode)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, D)
    a = nm.matmul(ctx, w["attn.out.weight"], mode) + w["attn.out.bias"]
    x = layer_norm(x + a, w["ln1.weight"], w["ln1.bias"], eps)
    h = gelu(nm.matmul(x, w["mlp.fc1.weight"], mode) + w["mlp.fc1.bias"])
    h = nm.matmul(h, w["mlp.fc2.weight"], mode) + w["mlp.fc2.bias"]
    return layer_norm(x + h, w["ln2.weight"], w["ln2.bias"], eps)


def pretrain_loss_sums(rest, stacks, batch, cfg, mode):
    """Summed MLM token losses and summed NSP losses of a block of rows."""
    dt = nm.compute_dtype(mode)
    rest = {k: v.astype(dt) for k, v in rest.items()}
    stacks = {k: v.astype(dt) for k, v in stacks.items()}
    eps = cfg["layer_norm_eps"]
    ids = batch["input_ids"]
    B, S = ids.shape
    x = (rest["bert.embeddings.word.weight"][ids]
         + rest["bert.embeddings.position.weight"][:S][None]
         + rest["bert.embeddings.token_type.weight"][batch["token_type_ids"]])
    x = layer_norm(x, rest["bert.embeddings.ln.weight"],
                   rest["bert.embeddings.ln.bias"], eps)
    am = batch["attention_mask"]
    mask = ((1.0 - am.astype(jnp.float32)) * -1e9)[:, None, None, :].astype(dt)

    def body(x, w):
        return encoder_layer(x, w, cfg["num_attention_heads"], eps, mode,
                             mask), None

    x, _ = jax.lax.scan(body, x, stacks)
    pooled = jnp.tanh(nm.matmul(x[:, 0], rest["bert.pooler.weight"], mode)
                      + rest["bert.pooler.bias"])
    seq = jnp.take_along_axis(x, batch["masked_positions"][..., None], axis=1)
    h = gelu(nm.matmul(seq, rest["transform.weight"], mode)
             + rest["transform.bias"])
    h = layer_norm(h, rest["ln.weight"], rest["ln.bias"], eps)
    logits = nm.einsum("bpd,vd->bpv", h, rest["bert.embeddings.word.weight"],
                       mode).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    mlm = -jnp.take_along_axis(logp, batch["mlm_labels"][..., None], -1)
    nsp_logits = (nm.matmul(pooled, rest["nsp.weight"], mode)
                  + rest["nsp.bias"]).astype(jnp.float32)
    nsp = -jnp.take_along_axis(jax.nn.log_softmax(nsp_logits, -1),
                               batch["nsp_labels"].reshape(-1, 1), -1)
    return mlm.sum(), nsp.sum()


def loss_and_grads(params, batch, cfg, mode="f32", block_rows=32):
    """Mean MLM loss + mean NSP loss of the whole batch and its gradient,
    accumulated over blocks of rows so that the activations of one block
    are all that is alive."""
    n_layers = cfg["num_hidden_layers"]
    rest, stacks = stack_layers(params, n_layers)
    B = batch["input_ids"].shape[0]
    n_pred = B * batch["mlm_labels"].shape[1]
    rows = min(block_rows, B)
    assert B % rows == 0, (B, rows)
    blocks = {k: v.reshape(B // rows, rows, *v.shape[1:])
              for k, v in batch.items()}

    def block_loss(rs, blk):
        mlm, nsp = pretrain_loss_sums(rs[0], rs[1], blk, cfg, mode)
        return mlm / n_pred + nsp / B

    def body(acc, blk):
        l, g = jax.value_and_grad(block_loss)((rest, stacks), blk)
        return (acc[0] + l, jax.tree_util.tree_map(jnp.add, acc[1], g)), None

    zero = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), (rest, stacks))
    (loss, (g_rest, g_stacks)), _ = jax.lax.scan(
        body, (jnp.float32(0.0), zero), blocks)
    return loss, unstack_layers(g_rest, g_stacks)


def adamw_init(params):
    z = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    return {"m": z, "v": dict(z), "count": jnp.int32(0)}


def adamw_update(params, grads, state, lr, weight_decay, grad_dtype=None,
                 b1=0.9, b2=0.999, eps=1e-8):
    """Adam on f32 weights, then the decoupled decay on the updated value.
    ``grad_dtype`` rounds each gradient the way the optimizer receives it
    (a bfloat16 program hands over bfloat16 gradients)."""
    t = state["count"] + 1
    tf = t.astype(jnp.float32)
    new_p, new_m, new_v = {}, {}, {}
    for k, w in params.items():
        g = grads[k]
        if grad_dtype is not None:
            g = g.astype(grad_dtype)
        g = g.astype(jnp.float32)
        m = b1 * state["m"][k] + (1 - b1) * g
        v = b2 * state["v"][k] + (1 - b2) * jnp.square(g)
        w2 = w - lr * (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf))
                                              + eps)
        new_p[k] = w2 - lr * weight_decay * w2
        new_m[k], new_v[k] = m, v
    return new_p, {"m": new_m, "v": new_v, "count": t}


def warmup_lr(step, peak, warmup_steps):
    """Linear warm-up from 0: the rate of (0-based) optimizer step ``step``."""
    if not warmup_steps:
        return peak
    return peak * min(step, warmup_steps) / warmup_steps


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode",
                                             "served_dtype", "block_rows",
                                             "weight_decay"))
def _step(p, st, batch, lr, *, cfg_items, mode, served_dtype, block_rows,
          weight_decay):
    cfg = dict(cfg_items)
    fwd = p if served_dtype is None else {
        k: v.astype(served_dtype).astype(jnp.float32) for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        loss, g = loss_and_grads(fwd, batch, cfg, mode, block_rows)
    p2, st2 = adamw_update(p, g, st, lr, weight_decay, served_dtype)
    return loss, p2, st2


def train_steps(params, batch, cfg, n_steps, lr, weight_decay, mode="f32",
                served_dtype=None, block_rows=32, warmup_steps=0):
    """``n_steps`` optimizer steps on one fixed batch from f32 ``params``,
    the learning rate warming up linearly to ``lr`` over ``warmup_steps``.
    Where the program keeps low-precision parameters beside an f32 master
    (``served_dtype``), each step's forward pass sees the master rounded to
    that type, as the program's does.  Returns (losses, params, state).
    Weights and batch are arguments of the compiled step, never constants
    in it, so one compilation serves every seed."""
    cfg_items = nm.static_items(cfg)
    state = adamw_init(params)
    losses = []
    for s in range(n_steps):
        loss, params, state = _step(
            params, state, batch,
            jnp.float32(warmup_lr(s, lr, warmup_steps)), cfg_items=cfg_items,
            mode=mode, served_dtype=served_dtype, block_rows=block_rows,
            weight_decay=weight_decay)
        losses.append(loss)
    return [float(x) for x in losses], params, state
