"""Which tile does each kernel of a cell's program run fastest at, ON THE CHIP?

The table behind the tile rules of the five kernels the benchmark's cells
run and that raced a measured search until PR 48 (``PERF.md`` section 6):
every candidate that search held, through the kernels' explicit ``block_*=``
keywords, at the cells' own shapes and dtypes (``benchmarks/configs``, the
engines' buckets and admission rows):

    chiprun -- python tools/tile_table_chip.py [flash] [experts] [bert]

(about 3 minutes; no part named: all three).  One JSON line a (kernel,
shape): ``{block: [mean ms, spread ms]}`` over ``ROUNDS`` rounds of ``CALLS``
back-to-back calls (spread: slowest round less fastest; a block the compiler
refuses reads ``"refused: ..."``), and the table in
``chiprun_out/tile_table.json``.  ``--rehearse`` runs cut shapes in interpret
mode on the CPU: the control flow only, its times mean nothing and are not
written.
"""
import functools
import json
import os
import sys
import time

REHEARSE = "--rehearse" in sys.argv[1:]
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.flash_attention import _pick_block, flash_attention
from paddle_tpu.ops.fused_layernorm import _ln_res_pallas, layernorm_residual
from paddle_tpu.ops.fused_softmax_xent import softmax_cross_entropy
from paddle_tpu.ops.grouped_matmul import _gated_mlp_wide, _wide_blocks

if not REHEARSE and jax.devices()[0].platform != "tpu":
    sys.exit(f"no chip: JAX reports {jax.devices()[0]}")
ROUNDS, CALLS = (1, 1) if REHEARSE else (5, 20)
CUT = 16 if REHEARSE else 1          # --rehearse: every long dimension / 16
bf16 = jnp.bfloat16
rng = np.random.default_rng(0)
table = {}


def arr(*shape, dtype=bf16):
    return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)


def timed(fn, *args):
    fn = jax.jit(fn)
    try:
        jax.block_until_ready(fn(*args))
    except Exception as e:  # noqa: BLE001  (what the compiler said)
        return "refused: " + str(e)[:120]
    rounds = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / CALLS * 1e3)
    return [round(float(np.mean(rounds)), 4),
            round(max(rounds) - min(rounds), 4)]


def row(kernel, shape, cells):
    table.setdefault(kernel, {})[shape] = cells
    print(json.dumps({"kernel": kernel, "shape": shape, "ms": cells}),
          flush=True)
    if not REHEARSE:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/tile_table.json", "w") as f:
            json.dump(table, f, indent=1)


def flash(name, rows, hq, hkv, hd, buckets):
    """Causal admission of ``rows`` prompts: square blocks, named by what
    ``_pick_block`` makes of them at the bucket."""
    for b in buckets:
        b //= CUT
        q, k, v = arr(rows, hq, b, hd), arr(rows, hkv, b, hd), arr(
            rows, hkv, b, hd)
        blocks = sorted({_pick_block(c, b) for c in (128, 256, 512, 1024)})
        row("flash_fwd", f"{name} [{rows},{hq}/{hkv},{b},{hd}]", {
            c: timed(lambda q, k, v, c=c: flash_attention(
                q, k, v, causal=True, block_q=c, block_k=c), q, k, v)
            for c in blocks})
        if name == "k_exaone":
            row("flash_fwd_window", f"{name} [{rows},{hq}/{hkv},{b},{hd}] "
                "window 128", {
                    c: timed(lambda q, k, v, c=c: flash_attention(
                        q, k, v, causal=True, window=128, block_q=c), q, k, v)
                    for c in (128, 256, 512) if c <= b})


def experts(tm, tiles, used, E=16, D=6144 // CUT, F=2048):
    """``k_exaone``'s held experts through the width-tiled kernel: ``used``
    of ``tiles`` row tiles in use, an expert's tiles side by side."""
    xs = arr(tiles * tm, D)
    wg, wu, wd = arr(E, D, F), arr(E, D, F), arr(E, F, D)
    t = jnp.arange(tiles, dtype=jnp.int32)
    last = jnp.minimum(t, used - 1)
    lay = {"tiles": tiles, "tile_m": tm, "tile_group": last * E // used,
           "tile_index": last, "used": jnp.full((1,), used, jnp.int32)}
    blocks = _wide_blocks(tm, 6144, F, 2)   # what fits at the real width
    row("moe_gated_mlp_wide", f"tile_m {tm}, {used} of {tiles} tiles, "
        f"[{E},{D},{F}]", {
            bf: timed(lambda *a, bf=bf: _gated_mlp_wide(*a, lay, bf),
                      xs, wg, wu, wd) for bf in blocks})


def bert(M=256 * 128 // CUT, D=768, P=256 * 20 // CUT, V=30522):
    x, r, g, b = arr(M, D), arr(M, D), arr(D), arr(D)
    # a training step keeps the statistics for the backward (four outputs);
    # a forward alone drops them, and the compiler books that call apart
    for form, fn in (("with its statistics", lambda *a, bm: _ln_res_pallas(
            *a, 1e-12, bm)), ("forward alone", lambda *a, bm:
                              layernorm_residual(*a, block_m=bm))):
        row("layernorm_residual", f"[{M},{D}] {form}", {
            bm: timed(functools.partial(fn, bm=bm), x, r, g, b)
            for bm in (128, 256, 512, 1024)})
    logits = arr(P, V)
    labels = jnp.asarray(rng.integers(0, V, P), jnp.int32)
    fits = [(bm, bv) for bm in (64, 128, 256, 512)
            for bv in (512, 1024, 2048, 4096, 8192) if bm * bv <= 1 << 20]
    row("softmax_xent", f"[{P},{V}]", {
        f"{bm}x{bv}": timed(lambda lg, lb, bm=bm, bv=bv:
                            softmax_cross_entropy(lg, lb, block_m=bm,
                                                  block_v=bv), logits, labels)
        for bm, bv in fits})


PARTS = {
    # rows: serving/generation.py:admit_rows (one past a bucket of 1024, two
    # up to it)
    "flash": lambda: (
        flash("olmo_hybrid", 1, 30, 30, 128, (1536, 2048, 3072, 4096)),
        flash("qwen3_next", 2, 16, 2, 256, (256, 512, 768, 1024)),
        flash("k_exaone", 1, 64, 8, 128, (1536, 2048, 3072, 4096))),
    "experts": lambda: (
        experts(16, 32, 16),   # a decode step: 32 slots x 8, an eighth held
        experts(128, 272 // CUT, 48 // CUT)),  # an admission of 4096
    "bert": bert,
}
for part in [a for a in sys.argv[1:] if a in PARTS] or PARTS:
    PARTS[part]()
