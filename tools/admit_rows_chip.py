"""The sweep behind ``serving/generation.py:_ADMIT_TOKEN_SLOTS``: what an
admission call costs at one row and at two, bucket by bucket, for the
serving cells' engines at their own sizes.

    chiprun -- python tools/admit_rows_chip.py [--cells a,b] [--rehearse]

For each cell (its configuration, its traffic's buckets, weights from
``--seed``, the engine built as the cell's runner builds it) and each bucket
``b``, the engine's own admission program is called with prompts of ``b``
tokens mapped to their slots' pages, as the loop would call it:

- ``one_of_1``: ``[1, b]``, the row real;
- ``one_of_2``: ``[2, b]``, one row real and one inert (what a closed loop
  that frees a slot at a time dispatches under the two-row rule);
- ``two_of_2``: ``[2, b]``, both rows real (against two ``[1, b]`` calls).

A time is the best of ``--repeats`` calls on the host's clock, each ended by
reading the first tokens, after one call that compiles.  One JSON line a
bucket, all of them again in ``chiprun_out/admit_rows_sweep.json``.
``--rehearse`` runs the tiny presets on the CPU, prints no time under a
device's name (``"platform": "cpu"``) and writes no file.
"""
import argparse
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CELLS = ("olmo_hybrid.ragdocs_closed", "joyai_flash.ragdocs_closed",
         "gpt2_small.docs_closed", "gpt2_small.chat_open",
         "qwen3_next.longgen_closed")


def _engine(cell, seed, rehearse):
    import jax

    from benchmarks.harness import loader
    from paddle_tpu.serving import GenerationEngine

    bench = os.path.join(REPO, "benchmarks")
    _, cfg, traffic = loader.load_cell(cell, bench)
    fam = loader.load_module("families", cfg["family"], bench)
    serve = cfg["serve"]
    if rehearse:
        cfg = {**cfg, **fam.TINY}
        traffic = {**traffic, **traffic.get("rehearse", {})}
        serve = {**serve, **cfg.get("serve_rehearse", {})}
    cfg = {**cfg, "serve": serve}
    weights = fam.make_weights(cfg, seed)
    jax.block_until_ready(weights)
    model = fam.build_model(cfg, weights)
    model.eval()
    return int(cfg["vocab_size"]), GenerationEngine(
        model, prompt_buckets=list(traffic["prompt_buckets"]),
        batch_size=serve["batch_size"], cache_len=serve.get("cache_len"),
        kv_page_size=serve["kv_page_size"], speculative_k=0,
        eos_token_id=None, name="sweep")


def _call(eng, vocab, cache, rows, real, bucket, rng):
    """One admission call of ``rows`` rows, the first ``real`` of them a
    prompt of ``bucket`` tokens in slot j's pages; returns the pool."""
    import jax.numpy as jnp
    import numpy as np

    C, page = eng._C, eng._page
    G, used = C // page, -(-bucket // page)
    ids = np.zeros((rows, bucket), np.int32)
    pp = np.full((rows, bucket), -1, np.int32)
    pm = np.full((rows, C), -1, np.int32)
    tb = np.full((rows, G), -1, np.int32)
    lens = np.ones((rows,), np.int32)
    sl = np.full((rows,), -1, np.int32)
    for j in range(real):
        ids[j] = rng.integers(1, vocab, bucket)
        pp[j] = pm[j, :bucket] = np.arange(bucket)
        tb[j, :used] = j * G + np.arange(used)
        lens[j], sl[j] = bucket, j
    first, cache = eng._padmit(
        eng._params, eng._buffers, jnp.asarray(ids), jnp.asarray(pp),
        jnp.asarray(pm), jnp.asarray(tb), jnp.asarray(lens), cache,
        eng._aids_arg(np.full((rows,), -1, np.int32)), eng._slots_arg(sl))
    np.asarray(first)
    return cache


def sweep(cell, seed, repeats, rehearse, emit):
    import numpy as np

    from paddle_tpu.serving.generation import admit_rows

    vocab, eng = _engine(cell, seed, rehearse)
    try:
        _, cache = eng._init_pool()
        rng = np.random.default_rng(seed)
        for b in eng._buckets:
            ms = {}
            for name, rows, real in (("one_of_1", 1, 1), ("one_of_2", 2, 1),
                                     ("two_of_2", 2, 2)):
                # the first call compiles
                cache = _call(eng, vocab, cache, rows, real, b, rng)
                best = None
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    cache = _call(eng, vocab, cache, rows, real, b, rng)
                    dt = (time.perf_counter() - t0) * 1e3
                    best = dt if best is None else min(best, dt)
                ms[name] = round(best, 3)
            emit({"cell": cell, "bucket": b, "slots": eng._batch,
                  "rows_by_rule": admit_rows(b, eng._batch), "ms": ms,
                  # a lone admitted row: what the second, inert row costs
                  "one_row_2_over_1": round(
                      ms["one_of_2"] / ms["one_of_1"], 3),
                  # two admitted rows: one call of two against two of one
                  "two_rows_2_over_1": round(
                      ms["two_of_2"] / (2 * ms["one_of_1"]), 3)})
    finally:
        eng.close(drain=False, timeout=60)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax

    from paddle_tpu import sysconfig

    platform = jax.devices()[0].platform
    if (platform == "tpu") == args.rehearse:
        print(f"platform {platform!r}: --rehearse is the CPU path, and the "
              f"only one there", file=sys.stderr)
        return 2
    sysconfig.enable_persistent_compilation_cache()
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    lines = []

    def emit(rec):
        rec["platform"] = platform
        lines.append(rec)
        print(json.dumps(rec), flush=True)
        if not args.rehearse:  # a rehearsal leaves a chip call's file alone
            with open(os.path.join(out, "admit_rows_sweep.json"), "w") as f:
                json.dump(lines, f, indent=1)

    for cell in args.cells.split(","):
        sweep(cell, args.seed, args.repeats, args.rehearse, emit)
        gc.collect()  # the next cell's weights need the room
    return 0


if __name__ == "__main__":
    sys.exit(main())
