"""The hybrid decoder's TPU-only branches on the chip, at a cell's own
shapes: ``--config olmo_hybrid_serve`` (the default) or
``qwen3_next_serve``.

``gated_delta_chunk`` / ``gated_delta_step`` (``ops/gated_delta.py``), the
admission's ``flash_attention``, the decode's ``paged_decode`` and (where the
configuration has experts) the expert kernel over a SHARE of the experts
are branches of ``models/hybrid.py`` that no CPU test takes (interpret mode
runs the kernels' code, not Mosaic's), so before a number is quoted:

    chiprun -- python tools/gated_delta_chip.py [--config <name>]

1. ``kernels``: the walk over chunks against its ``jnp`` form on the same
   WY operands, 2 rows x the widest bucket, one row ragged (the rest
   padding); the whole chunked op against the token-by-token recurrence on
   the ragged row; the decode step ``[B, 1]`` against its ``jnp`` form with
   one free slot, whose state and the write-drop row must come back bit for
   bit.  With grouped heads (fewer key heads than value heads; fewer K/V
   heads than query heads) both are the grouped forms, and ``paged_decode``
   over the grouped pool is held to the gather path; with experts, the
   expert kernel over the held share (absent pairs without rows) to its
   XLA form.
2. ``model``: one period of the model at the published widths, bfloat16: an
   admission of 2 rows (one ragged) and then three decode steps ``[B, 1]``
   with free slots, every kernel gate open, against the same calls with
   every gate shut (the ``jnp`` forms and the gather path): the widest
   logit gap and the mean.
3. ``ms``: each kernel alone, one layer's call, mean of 10 dependent calls.

One JSON object, last line; exit 1 on a disagreement.
"""
import argparse
import functools
import importlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the cells' shapes: key / value heads and their widths, slots x window,
#: the widest admission (its ragged row), the model phase's 2-row admission
SHAPES = {
    "olmo_hybrid_serve": dict(HK=30, H=30, DK=96, DV=192, B=16, C=4608,
                              T=4096, ragged=2500, admit=1536,
                              family="olmo_hybrid"),
    "qwen3_next_serve": dict(HK=16, H=32, DK=128, DV=128, B=64, C=2048,
                             T=1024, ragged=700, admit=1024,
                             family="qwen3_next"),
}
HK, H, DK, DV = 30, 30, 96, 192
B, C, PAGE = 16, 4608, 16
#: float32 kernels against float32 oracles in another summation order
KERNEL_TOL = 2e-4
#: bfloat16 model, kernels against jnp forms: logits of magnitude ~1 (the
#: flash kernel rounds its probabilities to bfloat16, the oracle does not)
MODEL_MAX, MODEL_MEAN = 0.25, 0.02


def _inputs(rng, B_, T):
    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    f = np.float32
    q = (unit(rng.normal(size=(B_, T, HK, DK))) * DK ** -0.5).astype(f)
    k = unit(rng.normal(size=(B_, T, HK, DK))).astype(f)
    v = rng.normal(size=(B_, T, H, DV)).astype(f)
    g = -rng.uniform(0.001, 1.6, size=(B_, T, H)).astype(f)
    beta = rng.uniform(0.0, 2.0, size=(B_, T, H)).astype(f)
    return q, k, v, g, beta


def _timed(fn, *args, n=10):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def kernels(shape, cfg):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta as gd

    rng = np.random.default_rng(0)
    T, ragged = shape["T"], shape["ragged"]
    q, k, v, g, beta = _inputs(rng, 2, T)
    g[1, ragged:], beta[1, ragged:] = 0.0, 0.0
    # on the device once: a timed call must not carry its inputs there
    q, k, v, g, beta = map(jnp.asarray, (q, k, v, g, beta))
    ops = jax.jit(gd.chunk_operands)(q, k, v, g, beta)
    o_k, s_k = jax.jit(gd._walk_pallas)(*ops)
    o_j, s_j = jax.jit(gd._walk_jnp)(*ops)
    out = {"walk_vs_jnp_o": float(jnp.abs(o_k - o_j).max()),
           "walk_vs_jnp_state": float(jnp.abs(s_k - s_j).max())}
    o, S = jax.jit(gd.gated_delta_chunk)(q, k, v, g, beta)
    cut = tuple(t[1:2, :ragged] for t in (q, k, v, g, beta))
    o_r, s_r = jax.jit(gd.gated_delta_recurrent)(*cut)
    out.update(chunk_vs_recurrence_o=float(jnp.abs(
        o[1, :ragged] - o_r[0]).max()),
        chunk_vs_recurrence_state=float(jnp.abs(S[1] - s_r[0]).max()))
    q1, k1, v1, g1, b1 = (t[:, 0] for t in _inputs(rng, B, 1))
    g1[5], b1[5] = 0.0, 0.0                                # a free slot
    q1, k1, v1, g1, b1 = map(jnp.asarray, (q1, k1, v1, g1, b1))
    state = jnp.asarray(rng.normal(size=(B + 1, H, DK, DV)), jnp.float32)
    o_k, n_k = jax.jit(gd._step_pallas)(q1, k1, v1, g1, b1, state)
    o_j, n_j = jax.jit(gd._step_jnp)(q1, k1, v1, g1, b1, state)
    out.update(step_vs_jnp_o=float(jnp.abs(o_k - o_j).max()),
               step_vs_jnp_state=float(jnp.abs(n_k - n_j).max()),
               free_slot_kept=bool(jnp.array_equal(n_k[5], state[5])),
               drop_row_kept=bool(jnp.array_equal(n_k[B], state[B])))
    ms = {}
    if cfg.get("num_key_value_heads", 0) != cfg["num_attention_heads"]:
        out.update(_grouped_paged_decode(rng, cfg, ms))
    if cfg.get("num_experts"):
        out.update(_held_experts(rng, cfg, ms))
    out["ok"] = bool(all(v < KERNEL_TOL for v in out.values()
                         if isinstance(v, float))
                     and all(v["ok"] for v in out.values()
                             if isinstance(v, dict))
                     and out["free_slot_kept"] and out["drop_row_kept"])
    ms = {**ms,"chunk_operands_xla": _timed(jax.jit(gd.chunk_operands), q, k, v,
                                       g, beta),
          "gated_delta_chunk_walk": _timed(jax.jit(gd._walk_pallas), *ops),
          "gated_delta_chunk_walk_jnp": _timed(jax.jit(gd._walk_jnp), *ops,
                                               n=2),
          "gated_delta_step": _timed(jax.jit(gd._step_pallas), q1, k1, v1,
                                     g1, b1, state),
          "gated_delta_step_jnp": _timed(jax.jit(gd._step_jnp), q1, k1, v1,
                                         g1, b1, state)}
    return out, ms


def _grouped_paged_decode(rng, cfg, ms):
    """``paged_decode`` at the decode width over a pool of ``H_kv`` heads
    against the gather path: bfloat16 pages, slots of ragged lengths, one
    free."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_attention as pa

    Hq, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    G = C // PAGE
    bf = jnp.bfloat16
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, hd)), bf)
    kp, vp = (jnp.asarray(rng.normal(size=(B * G + 1, PAGE, Hkv * hd)), bf)
              for _ in range(2))
    lens = rng.integers(1, C, size=B)
    lens[7] = 0
    tab = np.full((B, G), -1, np.int32)
    pm = np.full((B, C), -1, np.int32)
    pos = np.full((B, 1), -1, np.int32)
    free = list(rng.permutation(B * G))
    for b, n in enumerate(lens):
        for pg in range(-(-n // PAGE)):
            tab[b, pg] = free.pop()
        pm[b, :n] = np.arange(n)
        pos[b, 0] = n - 1
    mask = pa.key_visible(pm[:, None, :], pos[:, :, None], C)
    args = (q, kp, vp, jnp.asarray(np.maximum(tab, 0)))
    walk = (jnp.asarray(pm), jnp.asarray(pos),
            jnp.asarray(pa.sweep_bound(mask, PAGE)))
    kern = jax.jit(lambda *a: pa.paged_attention(*a[:4], None, a[4:]))
    gather = jax.jit(lambda *a: pa.paged_attention(*a))
    o_k = kern(*args, *walk).astype(jnp.float32)
    o_g = gather(*args, jnp.asarray(mask)).astype(jnp.float32)
    live = jnp.asarray(lens > 0)[:, None, None, None]
    ms["paged_decode_grouped"] = _timed(kern, *args, *walk)
    # bfloat16 contexts of magnitude ~1: their own limit, not KERNEL_TOL
    gap = float(jnp.abs(jnp.where(live, o_k - o_g, 0)).max())
    return {"paged_decode_vs_gather": {"max": gap, "limit": 0.05,
                                       "ok": gap < 0.05}}


def _held_experts(rng, cfg, ms):
    """The expert kernel over the held share against ``lax.ragged_dot``: a
    decode step's pairs and an admission's, ids over the router's whole
    width, absent pairs without rows."""
    import jax
    import jax.numpy as jnp

    gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, width = cfg["num_experts"], cfg["published"]["num_experts"]
    k = cfg["num_experts_per_tok"]
    bf = jnp.bfloat16
    wg, wu = (jnp.asarray(0.02 * rng.normal(size=(held, D, F)), bf)
              for _ in range(2))
    wd = jnp.asarray(0.02 * rng.normal(size=(held, F, D)), bf)
    out = {}
    for name, tokens, tm in (("decode", B, 16), ("admit", 2 * 1024, 128)):
        A = tokens * k
        x = jnp.asarray(rng.normal(size=(tokens, D)), bf)
        ids = jnp.asarray(rng.integers(0, width, size=A), jnp.int32)

        def fn(x, ids, kernel):
            lay = gm.ragged_layout(ids, held, tm, partial=True)
            n = lay["tiles"] * tm
            src = jnp.full((n,), tokens, jnp.int32).at[lay["dest"]].set(
                jnp.arange(A, dtype=jnp.int32) // k, mode="drop")
            xs = jnp.concatenate([x, jnp.zeros((1, D), bf)])[src]
            ys = gm.ragged_gated_mlp(xs, wg, wu, wd, lay, kernel=kernel)
            return jnp.where(lay["present"][:, None],
                             ys[jnp.minimum(lay["dest"], n - 1)], 0)

        kern = jax.jit(functools.partial(fn, kernel=True))
        y_k = kern(x, ids).astype(jnp.float32)
        y_x = jax.jit(functools.partial(fn, kernel=False))(x, ids).astype(
            jnp.float32)
        ms[f"moe_gated_mlp_tm{tm}_{name}"] = _timed(kern, x, ids)
        gap, scale = float(jnp.abs(y_k - y_x).max()), float(
            jnp.abs(y_x).max())
        # bfloat16 outputs: a rounding or two of the largest value
        out[f"experts_{name}_vs_xla"] = {
            "max": gap, "of": scale, "limit": 0.02 * scale,
            "ok": gap < 0.02 * scale,
            "local_pairs": int((ids < held).sum()), "pairs": A}
    return out


def model(shape, cfg):
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import loader
    from paddle_tpu.models import hybrid
    from paddle_tpu.nn.layer_base import functional_call
    from paddle_tpu.ops import gated_delta as gd

    gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")
    real_mlp = gm.ragged_gated_mlp
    fam = loader.load_module("families", shape["family"])
    cfg = {**cfg, "num_hidden_layers": 4}
    m = fam.build_model(cfg, fam.make_weights(cfg, 2 ** 31 + 5))
    m.eval()
    params, buffers = m.param_pytree(), m.buffer_pytree()
    rng = np.random.default_rng(1)
    T, G = shape["admit"], C // PAGE
    lens = (T, T * 2 // 3)
    ids = rng.integers(1, cfg["vocab_size"], (2, T)).astype(np.int32)
    pos = np.full((2, T), -1, np.int32)
    pm = np.full((B, C), -1, np.int32)
    tab = np.full((B, G), -1, np.int32)
    slots = (3, 9)
    free = list(rng.permutation(B * G))
    for r, (sl, n) in enumerate(zip(slots, lens)):
        pos[r, :n] = pm[sl, :n] = np.arange(n)
        for pg in range(-(-(n + 8) // PAGE)):
            tab[sl, pg] = free.pop()

    def run(gate):
        for mod, name in ((hybrid, "_kernels"), (hybrid, "_paged_flash"),
                          (gd, "gated_delta_eligible")):
            setattr(mod, name, (lambda *a: True) if gate else
                    (lambda *a: False))
        gm.ragged_gated_mlp = functools.partial(real_mlp, kernel=gate)

        # the weights are arguments: closed over, 3 GB of them would be
        # constants of the program
        def call(params, buffers, ids, pos, pm, tab, cache, slots=None,
                 last=None):
            def body(ids, pos, pm, tab, cache, slots, last):
                return m.forward_paged(ids, pos, pm, tab, cache,
                                       gather_last=last, slots=slots)
            return functional_call(m, params, ids, pos, pm, tab, cache,
                                   slots, last, buffers=buffers,
                                   training=False, call=body)

        fn = functools.partial(jax.jit(call, donate_argnames=("cache",)),
                               params, buffers)
        cache = m.init_paged_cache(B * G, PAGE, slots=B)
        logits, cache = fn(ids, pos, pm[list(slots)], tab[list(slots)],
                           cache, np.asarray(slots, np.int32),
                           np.asarray(lens, np.int32))
        got, pmd, at = [np.asarray(logits)], pm.copy(), dict(zip(slots, lens))
        for step in range(3):
            sid = np.zeros((B, 1), np.int32)
            spos = np.full((B, 1), -1, np.int32)
            for sl, n in at.items():
                sid[sl, 0] = 1 + (sl + step) % 1000
                spos[sl, 0] = pmd[sl, n] = n
                at[sl] = n + 1
            logits, cache = fn(sid, spos, pmd, tab, cache)
            got.append(np.asarray(logits)[list(slots), 0])
        return np.stack(got)

    kern, plain = run(True), run(False)
    d = np.abs(kern - plain)
    return {"logit_gap_max": float(d.max()), "logit_gap_mean": float(d.mean()),
            "logit_abs_mean": float(np.abs(plain).mean()),
            "ok": bool(d.max() < MODEL_MAX and d.mean() < MODEL_MEAN)}


def main():
    import jax

    from benchmarks.harness import loader

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="olmo_hybrid_serve",
                    choices=sorted(SHAPES))
    config = ap.parse_args().config
    shape = SHAPES[config]
    global HK, H, DK, DV, B, C
    HK, H, DK, DV, B, C = (shape[k] for k in ("HK", "H", "DK", "DV", "B",
                                              "C"))
    cfg = loader.load_json("configs", config + ".json")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no chip: {dev.platform}"}))
        return 1
    k, ms = kernels(shape, cfg)
    print(json.dumps({"kernels": k, "ms": ms}), flush=True)
    mdl = model(shape, cfg)
    ok = k["ok"] and mdl["ok"]
    print(json.dumps({"ok": ok, "kernels": k, "model": mdl, "ms": ms,
                      "device": dev.device_kind}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
