"""The hybrid decoder's TPU-only branches on the chip, at a cell's own
shapes: ``--config olmo_hybrid_serve`` (the default), ``qwen3_next_serve``
or ``kimi_linear_serve`` (``ops/kda.py``'s two kernels, one decay a key
channel: phases 1, 3 and ``--tiles``; the model against the plain reference
is the cell's own ``correct``).

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
3. ``ms``: each kernel alone, one layer's call, mean of 10 dependent calls;
   for ``kimi_linear_serve`` also ``operand_stages``: the making of the WY
   operands taken apart at one row x the widest bucket (the numbers
   ``PERF.md`` section 5 quotes for an admission's KDA layer).

``--tiles`` instead times EVERY candidate of the two delta-rule kernels'
``block_h`` (heads a grid step) at the cell's shapes: the walk at each prompt
bucket of the cell's traffic x the rows its admission call has, the step at
the cell's slots with the states donated and threaded (as the engine's step
does), each the best of three means of 10 calls.  The table behind
``ops/gated_delta.py:walk_heads`` / ``step_heads`` and ``ops/kda.py``'s
(``PERF.md`` section 6, PR 44); ``chiprun_out/gated_delta_tiles.<config>.json``
keeps it.

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
    "kimi_linear_serve": dict(HK=32, H=32, DK=128, DV=128, B=128, C=4864,
                              T=4096, ragged=2500, admit=1536,
                              family="kimi_linear", kda=True),
}
#: (traffic file, rows an admission call has) of each configuration's cell
TRAFFIC = {"olmo_hybrid_serve": ("ragdocs_closed", 1),
           "qwen3_next_serve": ("longgen_closed", 2),
           "kimi_linear_serve": ("longdoc_gen_closed", 1)}
#: heads a grid step of the walk kernel may take, the candidates ``--tiles``
#: times: ``gated_delta_chunk``'s (the kernel's rule, ``walk_heads``, picks
#: among the same four) and ``kda_chunk``'s
WALK_HEADS = {False: (1, 2, 3, 5), True: (1, 2, 4, 8, 16, 32)}
KDA = False
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
    # one decay a key channel, some strong enough to pass float32's range
    # inside a chunk (ops/kda.py), or one a head
    g = (-np.exp(rng.uniform(-6.0, np.log(8.0), size=(B_, T, H, DK)))
         if KDA else -rng.uniform(0.001, 1.6, size=(B_, T, H))).astype(f)
    beta = rng.uniform(0.0, 1.0 if KDA else 2.0, size=(B_, T, H)).astype(f)
    return q, k, v, g, beta


def _rule():
    """The module of the cell's rule and its three routes."""
    if KDA:
        from paddle_tpu.ops import kda

        return kda, kda.kda_chunk, kda.kda_recurrent
    from paddle_tpu.ops import gated_delta as gd

    return gd, gd.gated_delta_chunk, gd.gated_delta_recurrent


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

    gd, chunk, recurrent = _rule()
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
    o, S = jax.jit(chunk)(q, k, v, g, beta)
    cut = tuple(t[1:2, :ragged] for t in (q, k, v, g, beta))
    o_r, s_r = jax.jit(recurrent)(*cut)
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
    if cfg.get("num_experts") and not KDA:
        out.update(_held_experts(rng, cfg, ms))
    out["ok"] = bool(all(v < KERNEL_TOL for v in out.values()
                         if isinstance(v, float))
                     and all(v["ok"] for v in out.values()
                             if isinstance(v, dict))
                     and out["free_slot_kept"] and out["drop_row_kept"])
    if KDA:
        ms["operand_stages"] = operand_stages(q, k, v, g, beta)
    ms = {**ms,
          "chunk_operands_xla": _timed(jax.jit(gd.chunk_operands), q, k, v, g,
                                       beta),
          "gated_delta_chunk_walk": _timed(jax.jit(gd._walk_pallas), *ops),
          "gated_delta_chunk_walk_jnp": _timed(jax.jit(gd._walk_jnp), *ops,
                                               n=2),
          "gated_delta_step": _timed(jax.jit(gd._step_pallas), q1, k1, v1,
                                     g1, b1, state),
          "gated_delta_step_jnp": _timed(jax.jit(gd._step_jnp), q1, k1, v1,
                                         g1, b1, state)}
    return out, ms


def operand_stages(q, k, v, g, beta):
    """``ops/kda.py:chunk_operands`` taken apart, each stage a program of
    its own over ONE row x the widest bucket (a layer's share of an
    admission call): the running sum of ``g``, the decayed products ``A``
    and ``P``, the inverse, and the whole making beside the whole chunked
    op.  The parts do not sum to the whole (XLA fuses across them there):
    they say where to look."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta as gd
    from paddle_tpu.ops import kda

    q, k, v, g, beta = (t[:1] for t in (q, k, v, g, beta))
    Bq, T = q.shape[:2]
    Cn = kda.CHUNK

    def split(t):      # [B, T, H, ...] -> [B, H, N, C, ...]
        return jnp.moveaxis(t.reshape(Bq, T // Cn, Cn, *t.shape[2:]), 3, 1)

    qs, ks, gs, bs = map(split, (q, k, g, beta))
    tri = jnp.tril(jnp.ones((Cn, Cn), jnp.float32))
    run = jax.jit(lambda g: jnp.matmul(tri, g, precision=gd._HI))
    b = run(gs)
    kb = ks * bs[..., None]
    prod = jax.jit(lambda kb, q, k, b: kda._decayed_products((kb, q), k, b))
    A, _ = prod(kb, qs, ks, b)
    low = np.tril(np.ones((Cn, Cn), bool), -1)
    A = jnp.where(low, A, 0.0)
    return {"rows_x_tokens": f"1x{T}",
            "running_sum": _timed(run, gs),
            "running_sum_as_cumsum": _timed(
                jax.jit(lambda g: jnp.cumsum(g, axis=-2)), gs),
            "decayed_products": _timed(prod, kb, qs, ks, b),
            "inverse": _timed(jax.jit(gd._unit_lower_inverse), A),
            "chunk_operands": _timed(jax.jit(kda.chunk_operands), q, k, v,
                                     g, beta),
            "kda_chunk": _timed(jax.jit(kda.kda_chunk), q, k, v, g, beta)}


def tiles(shape, cfg, config):
    """Every candidate ``block_h`` of the walk and of the step at the
    cell's shapes: ``{kernel: {shape: {block_h: ms}}}``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import loader

    gd, _, _ = _rule()
    traffic, rows = TRAFFIC[config]
    rng = np.random.default_rng(2)

    def best(fn, *args):
        return min(_timed(fn, *args) for _ in range(3))

    out = {"walk": {}, "step": {}}
    for T in loader.load_json("traffic", traffic + ".json")["prompt_buckets"]:
        ops = jax.jit(gd.chunk_operands)(*map(jnp.asarray,
                                              _inputs(rng, rows, T)))
        space = [h for h in WALK_HEADS[KDA] if H % h == 0]
        out["walk"][f"{rows}x{T}"] = {
            h: best(jax.jit(functools.partial(gd._walk_pallas, block_h=h)),
                    *ops) for h in space}
        print(json.dumps({"walk": out["walk"]}), flush=True)
    one = [jnp.asarray(t[:, 0]) for t in _inputs(rng, B, 1)]
    state = jnp.asarray(rng.normal(size=(B + 1, H, DK, DV)), jnp.float32)
    # a KDA block's rows are [block_h, dk] tiles: whole sublane tiles
    space = ([h for h in (8, 16, 32) if H % h == 0 and 3 * h <= DK]
             if KDA else [c["block_h"] for c in gd._step_space(*one, state)])
    for h in space:
        # donated and threaded: the states are updated where they lie, as
        # in the engine's step (undonated, a copy of them rides every call)
        fn = jax.jit(functools.partial(gd._step_pallas, block_h=h),
                     donate_argnums=(5,))

        def run(n, state):
            for _ in range(n):
                _, state = fn(*one, state)
            return jax.block_until_ready(state)

        state, times = run(2, state), []
        for _ in range(3):
            t0 = time.perf_counter()
            state = run(10, state)
            times.append((time.perf_counter() - t0) / 10 * 1e3)
        out["step"][h] = min(times)
    out["step"] = {f"{B}": out["step"]}
    print(json.dumps({"step": out["step"]}), flush=True)
    return out


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
    ap.add_argument("--tiles", action="store_true")
    args = ap.parse_args()
    config = args.config
    shape = SHAPES[config]
    global HK, H, DK, DV, B, C, KDA
    HK, H, DK, DV, B, C = (shape[k] for k in ("HK", "H", "DK", "DV", "B",
                                              "C"))
    KDA = bool(shape.get("kda"))
    cfg = loader.load_json("configs", config + ".json")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no chip: {dev.platform}"}))
        return 1
    if args.tiles:
        table = {"config": config, "device": dev.device_kind,
                 **tiles(shape, cfg, config)}
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
        with open(os.path.join(root, "chiprun_out",
                               f"gated_delta_tiles.{config}.json"), "w") as f:
            json.dump(table, f, indent=1)
        print(json.dumps({"ok": True, **table}))
        return 0
    k, ms = kernels(shape, cfg)
    print(json.dumps({"kernels": k, "ms": ms}), flush=True)
    if KDA:   # the model against the reference is the cell's own `correct`
        print(json.dumps({"ok": k["ok"], "kernels": k, "ms": ms,
                          "device": dev.device_kind}))
        return 0 if k["ok"] else 1
    mdl = model(shape, cfg)
    ok = k["ok"] and mdl["ok"]
    print(json.dumps({"ok": ok, "kernels": k, "model": mdl, "ms": ms,
                      "device": dev.device_kind}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
