"""BERT-base per-op roofline on the real chip (VERDICT r5 item 2a).

ResNet got the measured-ceiling treatment in round 4
(tools/resnet_mfu_analysis.md); this does the same for the headline BERT
workload: where do the points between the measured train MFU and the
chip's ~66% matmul ceiling go?

Methodology (same as the ResNet tool): every number comes from a
scan-chained loop on the device (data dependence through the carry so XLA
cannot hoist the body), timed around a single D2H read, so the one
dispatch and read amortize over 100+ iterations.

Stages:
  1. GEMM ceilings at BERT-base's exact shapes (qkv/proj/mlp/vocab-head).
  2. One encoder layer forward / fwd+bwd, then ablations that remove one
     bandwidth suspect at a time (softmax path, dropout, LayerNorm) —
     the deltas localize the gap.
  3. Full-model forward, full train step, optimizer-only step — the
     residue (embedding scatter, MLM gather, AdamW passes) falls out.

Run:  python tools/bert_mfu_roofline.py          (ambient TPU)
Output: one JSON line per measurement + a closing summary line.
"""
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

B, S, D, H, FF, V = 256, 128, 768, 12, 3072, 30522
DH = D // H


def _timed_chain(fn, x0, iters, *consts):
    """Run ``x = fn(x, *consts)`` ``iters`` times under one jit with a real
    data dependence, return seconds for the whole chain."""
    import jax
    from jax import lax

    @jax.jit
    def chain(x, *consts):
        def body(x, _):
            return fn(x, *consts), None

        out, _ = lax.scan(body, x, None, length=iters)
        return out

    out = chain(x0, *consts)
    _sync(out)
    t0 = time.perf_counter()
    out = chain(x0, *consts)
    _sync(out)
    return time.perf_counter() - t0


def _sync(tree):
    import jax

    leaf = jax.tree_util.tree_leaves(tree)[0]
    float(np.asarray(leaf.reshape(-1)[0]))  # D2H read truly waits


def emit(name, ms, gflop=None, note=""):
    rec = {"op": name, "ms": round(ms, 3)}
    if gflop is not None:
        tf = gflop / ms  # GFLOP / ms == TFLOP/s
        rec["tflops"] = round(tf, 1)
        from paddle_tpu.framework.device import peak_bf16_tflops

        peak = peak_bf16_tflops()  # None: a device kind with no known peak
        if peak is not None:
            rec["mfu_pct"] = round(100 * tf / peak, 1)
    if note:
        rec["note"] = note
    print(json.dumps(rec), flush=True)
    return rec


def stage1_gemms():
    import jax
    import jax.numpy as jnp

    shapes = [
        ("qkv  [BS,D]x[D,3D]", (B * S, D, 3 * D)),
        ("proj [BS,D]x[D,D]", (B * S, D, D)),
        ("mlp1 [BS,D]x[D,4D]", (B * S, D, FF)),
        ("mlp2 [BS,4D]x[4D,D]", (B * S, FF, D)),
        ("head [B*20,D]x[D,V]", (B * 20, D, V)),
        ("attn scores [S,DH]x[DH,S] batched BH",
         (S, DH, S)),  # per-(B,H) GEMM, batched below
    ]
    out = {}
    for name, (m, k, n) in shapes:
        batch = B * H if name.startswith("attn") else 1
        key = jax.random.PRNGKey(0)
        if batch > 1:
            a = jax.random.normal(key, (batch, m, k), jnp.bfloat16)
            w = jax.random.normal(key, (batch, k, n), jnp.bfloat16)
            fn = lambda x, w: jnp.einsum("bmk,bkn->bmn", x, w)  # noqa: E731
        else:
            a = jax.random.normal(key, (m, k), jnp.bfloat16)
            w = jax.random.normal(key, (k, n), jnp.bfloat16)
            fn = lambda x, w: (x @ w).astype(jnp.bfloat16)  # noqa: E731

        iters = 100
        # keep the carry shape == input shape: project back when n != k
        if m * n != m * k or batch > 1:
            proj = (jax.random.normal(key, (batch, n, k), jnp.bfloat16)
                    if batch > 1 else
                    jax.random.normal(key, (n, k), jnp.bfloat16))
            if batch > 1:
                f2 = lambda x, w, p: jnp.einsum(  # noqa: E731
                    "bmn,bnk->bmk", fn(x, w), p).astype(jnp.bfloat16)
            else:
                f2 = lambda x, w, p: (fn(x, w) @ p).astype(  # noqa: E731
                    jnp.bfloat16)
            sec = _timed_chain(f2, a, iters, w, proj)
            gflop = 2 * batch * m * k * n * 2 * iters / 1e9  # x2: the proj
        else:
            sec = _timed_chain(fn, a, iters, w)
            gflop = 2 * batch * m * k * n * iters / 1e9
        out[name] = emit(f"gemm {name}", sec * 1e3 / iters,
                         gflop / iters)
    return out


def _make_layer(dropout, attention="full", layernorm=True):
    """One BERT encoder layer as a pure function of (x, params)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig, BertLayer
    from paddle_tpu.nn.layer_base import functional_call

    paddle.seed(0)
    cfg = BertConfig(vocab_size=V, hidden_size=D, num_layers=1,
                     num_heads=H, intermediate_size=FF,
                     dropout=dropout)
    layer = BertLayer(cfg).astype("bfloat16")
    params = {k: v.value for k, v in layer.named_parameters()}

    if attention == "gemm_only":
        # replace softmax-path with a pure GEMM chain of the same matmul
        # FLOPs: qkv → (q@k^T)@v without softmax/mask/scale
        def attn_fwd(self, x, attn_mask=None):
            Bx, Sx, Dx = x.shape
            qkv = self.qkv(x).reshape(Bx, Sx, 3, H, DH)
            q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", scores.astype(q.dtype), v)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(Bx, Sx, Dx)
            return self.out(ctx)

        layer.attn.forward = attn_fwd.__get__(layer.attn)
    elif attention == "flash":
        from paddle_tpu.ops.flash_attention import flash_attention

        def attn_fwd(self, x, attn_mask=None):
            Bx, Sx, Dx = x.shape
            qkv = self.qkv(x).reshape(Bx, Sx, 3, H, DH)
            # kernel layout: [B, H, S, DH]
            q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
            ctx = flash_attention(q, k, v, causal=False)
            return self.out(ctx.transpose(0, 2, 1, 3).reshape(Bx, Sx, Dx))

        layer.attn.forward = attn_fwd.__get__(layer.attn)

    if not layernorm:
        for name in ("ln1", "ln2"):
            ln = getattr(layer, name)
            ln.forward = (lambda self, x: x).__get__(ln)

    def fwd(x, params, key):
        return functional_call(layer, params, x, rngs=key,
                               training=True).astype(jnp.bfloat16)

    return fwd, params


LAYER_GEMM_GFLOP = 2 * B * S * (3 * D * D + D * D + 2 * D * FF) / 1e9
ATTN_GEMM_GFLOP = 4 * B * H * S * S * DH / 1e9


def stage2_layer():
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(1)
    x0 = jax.random.normal(key, (B, S, D), jnp.bfloat16)
    results = {}
    variants = [
        ("layer fwd (full, p=0.1)", dict(dropout=0.1)),
        ("layer fwd (no dropout)", dict(dropout=0.0)),
        ("layer fwd (gemm-only attn)", dict(dropout=0.0,
                                            attention="gemm_only")),
        ("layer fwd (no layernorm)", dict(dropout=0.0, layernorm=False)),
        ("layer fwd (flash attn)", dict(dropout=0.0, attention="flash")),
    ]
    for name, kw in variants:
        try:
            fwd, params = _make_layer(**kw)
            iters = 50
            sec = _timed_chain(lambda x, p: fwd(x, p, jax.random.PRNGKey(2)),
                               x0, iters, params)
            gf = (LAYER_GEMM_GFLOP + ATTN_GEMM_GFLOP) * iters
            results[name] = emit(name, sec * 1e3 / iters, gf / iters)
        except Exception as e:  # flash variant may not support the shape
            print(json.dumps({"op": name, "error": str(e)[:200]}),
                  flush=True)

    # fwd+bwd on the full layer
    fwd, params = _make_layer(dropout=0.1)

    def train_like(x, params):
        import jax

        loss, grads = jax.value_and_grad(
            lambda p: fwd(x, p, jax.random.PRNGKey(2)).astype(
                jnp.float32).mean())(params)
        # fold a grad signal back into x so the chain carries dependence
        gleaf = jax.tree_util.tree_leaves(grads)[0]
        return (x + gleaf.reshape(-1)[0].astype(x.dtype) * 1e-12).astype(x.dtype)

    iters = 30
    sec = _timed_chain(train_like, x0, iters, params)
    gf = 3 * (LAYER_GEMM_GFLOP + ATTN_GEMM_GFLOP) * iters
    results["layer fwd+bwd"] = emit("layer fwd+bwd (p=0.1)",
                                    sec * 1e3 / iters, gf / iters)
    return results


def stage3_model():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as popt
    from paddle_tpu.models import BertForPretraining, bert_base

    paddle.seed(0)
    cfg = bert_base()
    net = BertForPretraining(cfg).astype("bfloat16")
    opt = popt.AdamW(learning_rate=1e-4, weight_decay=0.01,
                     multi_precision=True)
    model = paddle.Model(
        net, inputs=["input_ids", "token_type_ids", "attention_mask",
                     "masked_positions"],
        labels=["mlm_labels", "nsp_labels"])
    model.prepare(optimizer=opt, loss=net.loss)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    tt = (rng.uniform(size=(B, S)) < 0.5).astype(np.int32)
    am = np.ones((B, S), np.int32)
    pos = np.stack([np.sort(rng.choice(S, 20, replace=False))
                    for _ in range(B)]).astype(np.int32)
    mlm = np.take_along_axis(ids, pos, axis=1)
    nsp = rng.randint(0, 2, (B, 1)).astype(np.int32)

    def step():
        loss, _ = model._train_batch_device([ids, tt, am, pos], [mlm, nsp])
        return loss

    for _ in range(3):
        loss = step()
    float(np.asarray(loss))
    t0 = time.perf_counter()
    for _ in range(10):
        loss = step()
    float(np.asarray(loss))
    sec = (time.perf_counter() - t0) / 10
    from bench import BERT_TRAIN_GFLOP_PER_SEQ  # single source of truth

    emit("full train step", sec * 1e3, B * BERT_TRAIN_GFLOP_PER_SEQ,
         note=f"{B / sec:.0f} seq/s")
    return sec


def main():
    import jax

    print(json.dumps({"devices": [str(d) for d in jax.devices()]}),
          flush=True)
    g = stage1_gemms()
    l = stage2_layer()
    stage3_model()
    print(json.dumps({"summary": "see per-line records", "B": B, "S": S}),
          flush=True)


if __name__ == "__main__":
    main()
