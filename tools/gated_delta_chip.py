"""The hybrid decoder's TPU-only branches on the chip, at the
``olmo_hybrid`` cell's own shapes.

``gated_delta_chunk`` / ``gated_delta_step`` (``ops/gated_delta.py``), the
admission's ``flash_attention`` and the decode's ``paged_decode`` over 30
heads of 128 are branches of ``models/hybrid.py`` that no CPU test takes
(interpret mode runs the kernels' code, not Mosaic's), so before a number
is quoted:

    chiprun -- python tools/gated_delta_chip.py

1. ``kernels``: the walk over chunks against its ``jnp`` form on the same
   WY operands, ``[2, 4096]`` x 30 heads, one row ragged (2500 real tokens,
   the rest padding); the whole chunked op against the token-by-token
   recurrence on the ragged row; the decode step ``[16, 1]`` against its
   ``jnp`` form with one free slot, whose state and the write-drop row must
   come back bit for bit.
2. ``model``: one period of the model (three linear layers, one full) at
   the published widths, bfloat16: an admission ``[2, 1536]`` (one row
   ragged; the gather path's scores at 4096 would not fit beside it) and
   then three decode steps ``[16, 1]`` with free slots, every
   kernel gate open, against the same calls with every gate shut (the
   ``jnp`` forms and the gather path): the widest logit gap and the mean.
3. ``ms``: each kernel alone, one layer's call, mean of 10 dependent calls.

One JSON object, last line; exit 1 on a disagreement.
"""
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

H, DK, DV = 30, 96, 192
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
    q = (unit(rng.normal(size=(B_, T, H, DK))) * DK ** -0.5).astype(f)
    k = unit(rng.normal(size=(B_, T, H, DK))).astype(f)
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


def kernels():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta as gd

    rng = np.random.default_rng(0)
    T, ragged = 4096, 2500
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
    out["ok"] = bool(all(v < KERNEL_TOL for k_, v in out.items()
                         if isinstance(v, float))
                     and out["free_slot_kept"] and out["drop_row_kept"])
    ms = {"chunk_operands_xla": _timed(jax.jit(gd.chunk_operands), q, k, v,
                                       g, beta),
          "gated_delta_chunk_walk": _timed(jax.jit(gd._walk_pallas), *ops),
          "gated_delta_chunk_walk_jnp": _timed(jax.jit(gd._walk_jnp), *ops,
                                               n=2),
          "gated_delta_step": _timed(jax.jit(gd._step_pallas), q1, k1, v1,
                                     g1, b1, state),
          "gated_delta_step_jnp": _timed(jax.jit(gd._step_jnp), q1, k1, v1,
                                         g1, b1, state)}
    return out, ms


def model():
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import loader
    from paddle_tpu.models import hybrid
    from paddle_tpu.nn.layer_base import functional_call
    from paddle_tpu.ops import gated_delta as gd

    fam = loader.load_module("families", "olmo_hybrid")
    cfg = {**loader.load_json("configs", "olmo_hybrid_serve.json"),
           "num_hidden_layers": 4}
    m = fam.build_model(cfg, fam.make_weights(cfg, 2 ** 31 + 5))
    m.eval()
    params, buffers = m.param_pytree(), m.buffer_pytree()
    rng = np.random.default_rng(1)
    T, G = 1536, C // PAGE
    lens = (T, 1000)
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

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no chip: {dev.platform}"}))
        return 1
    k, ms = kernels()
    print(json.dumps({"kernels": k, "ms": ms}), flush=True)
    mdl = model()
    ok = k["ok"] and mdl["ok"]
    print(json.dumps({"ok": ok, "kernels": k, "model": mdl, "ms": ms,
                      "device": dev.device_kind}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
