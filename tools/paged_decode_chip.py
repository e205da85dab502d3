"""The ``paged_decode`` kernel on the chip, at the GPT-2 cells' own shapes.

The kernel's dispatch is a TPU-only branch of ``GPTModel.forward_paged``
that no CPU test takes (PR 26 was refused ``outputs_incorrect`` with the
CPU suite green), so before a number is quoted:

    chiprun -- python tools/paged_decode_chip.py

1. ``agree``: one GPT-2-small layer's ``forward_paged`` with the kernel
   against the same call on the gather path, over 2048 float pages of 16
   filled at random: the decode width ``[32, 1]`` with ragged lengths and
   one free slot, the verify width ``[32, 5]``, and an admission chunk
   ``[2, T]`` for every bucket the cells serve, one row a padding row;
   then the kernel alone against a plain gather for a slot that has
   wrapped (positions the model's embedding table does not reach).
2. ``kernel_ms``: the kernel alone, a layer, 12 dependent calls in one
   program: the decode width at ``docs_closed``'s and ``chat_open``'s
   occupancy, the admission chunk at its widest and narrowest bucket.

``--admit`` instead times the kernel at the admission width alone (a
builder's tool, no cell runs it): one layer's ``[2, bucket]`` call for
every ``docs_closed`` bucket (and ``[2, 1024]``) over two rows of real
ragged lengths, in each form the grid can take: the bucket as ONE tile
swept to the slot's bound with 2 or 4 heads a step (the form the kernel
had until PR 42, ``block_h`` raced by the autotuner), query tiles of 128
to 384 rows each swept to its own bound with all 12 heads a step and one
to twelve heads' chains of products abreast, and ``rule``: what
``paged_flash_decode`` itself resolves to.  A form the compiler refuses
(VMEM) reads ``null``.  The rule in ``ops/paged_attention.py``
(``QUERY_TILE``, ``query_tile``, ``_ABREAST``, ``_heads_a_step``) is the
winner of this table.

``--latent`` instead times ``ops/latent_attention.py:latent_decode``, the
latent decoders' page walk, alone at both latent cells' decode shapes (32
slots x 288 pages and 128 slots x 304 pages of 16 rows of 640 bfloat16
lanes, 32 heads as the query rows), windows 57 % live as the cells' are:
first the kernel against plain softmax attention over a gathered view (a
wrapped slot, a free slot, a last page partly filled among the rows), then
ms a layer call for every candidate keys-a-block, the GB/s and the share
of ``swept bytes / 819 GB/s`` that is (swept = the pages inside
``sweep_bound``, what the engine's ``kv_pages_swept_steps`` counts: 16 x
640 x 2 B each), and beside it the gather path (``pool.at[tab].get`` and
the absorbed products, what ``LatentAttention.absorbed`` does to the view)
and ``paged_decode`` handed the pool as both its K and its V pool (every
page read twice: the floor).  ``ops/latent_attention.py:DECODE_KEYS`` is
the winner of this table.

``--trace <dir>`` instead reads a profiler trace a benchmark run left
(``.cache/bench_trace/<cell>``) and prints, for each paged program in
it, its mean time on the device and the mean time of one ``paged_decode``
(or ``latent_decode``) call inside it.  One JSON object, last line; exit 1
on a disagreement.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAGE, PAGES, C, H, HD = 16, 2048, 1024, 12, 64
G = C // PAGE
BUCKETS = (64, 128, 256, 512, 640, 768)


def _layout(rng, lengths, T):
    """Page table, position map and the last ``T`` positions of slots
    holding ``lengths`` tokens, pages drawn from the pool at random."""
    B = len(lengths)
    free = list(rng.permutation(PAGES))
    table = np.full((B, G), -1, np.int32)
    pos_map = np.full((B, C), -1, np.int32)
    pos = np.full((B, T), -1, np.int32)
    for b, n in enumerate(lengths):
        for g in range(-(-n // PAGE)):
            table[b, g] = free.pop()
        pos_map[b, :n] = np.arange(n)
        if n:
            pos[b, -min(T, n):] = np.arange(n - min(T, n), n)
    return table, pos_map, pos


def agree():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models import gpt as G_
    from paddle_tpu.models.gpt import GPTConfig, GPTModel
    from paddle_tpu.ops.paged_attention import (key_visible,
                                                paged_flash_decode,
                                                sweep_bound)

    pt.seed(30)
    model = GPTModel(GPTConfig(num_layers=1, dropout=0.0))
    model.eval()
    rng = np.random.RandomState(30)
    pool = {"layers": [{
        n: jnp.asarray(rng.standard_normal((PAGES + 1, PAGE, H * HD)),
                       jnp.float32) for n in ("k", "v")}]}
    assert G_._paged_flash(HD, PAGE), "the kernel's gate is shut here"
    gate = G_._paged_flash
    cases = [("decode[32,1]", 1,
              [0] + [int(n) for n in rng.randint(1, C, 31)]),
             ("verify[32,5]", 5,
              [0] + [int(n) for n in rng.randint(5, C, 31)])]
    cases += [(f"admit[2,{T}]", T, [int(rng.randint(T // 2 + 1, T + 1)), 0])
              for T in BUCKETS]
    out, worst = {}, 0.0
    for name, T, lengths in cases:
        table, pos_map, pos = _layout(rng, lengths, T)
        ids = rng.randint(0, 50257, pos.shape).astype(np.int32)
        hidden = {}
        for kernel in (True, False):
            G_._paged_flash = gate if kernel else (lambda hd, pg: False)
            try:
                h, _ = jax.jit(model.forward_paged)(ids, pos, pos_map, table,
                                                    pool)
            finally:
                G_._paged_flash = gate
            hidden[kernel] = np.asarray(h)
        live = pos >= 0
        gap = float(np.abs(hidden[True][live] - hidden[False][live]).max())
        scale = float(np.abs(hidden[False][live]).max())
        bound = sweep_bound(key_visible(pos_map[:, None, :],
                                        pos[:, :, None], C), PAGE)
        out[name] = {"max_gap": gap, "ref_max": scale,
                     "pages": int(bound.sum()), "rows": int(live.sum())}
        worst = max(worst, gap / scale)
    # a wrapped slot: the kernel against a plain gather at "highest"
    table, pos_map, _ = _layout(rng, [C, C * 2 // 3, 0], 1)
    at = C + 333
    c = np.arange(C)
    lap = at - at % C + c
    pos_map[0] = np.where(c <= at % C, lap, lap - C)
    pos_map[0, :40] = -1
    pos = np.asarray([[at], [C * 2 // 3 - 1], [-1]], np.int32)
    q = jnp.asarray(rng.standard_normal((3, H, 1, HD)), jnp.float32)
    k, v = pool["layers"][0]["k"], pool["layers"][0]["v"]
    tab = jnp.maximum(jnp.asarray(table), 0)
    mask = key_visible(pos_map[:, None, :], pos[:, :, None], C)
    bound = sweep_bound(mask, PAGE)
    got = np.asarray(jax.jit(paged_flash_decode)(
        q, k, v, tab, jnp.asarray(pos_map), jnp.asarray(pos),
        jnp.asarray(bound)))
    with jax.default_matmul_precision("highest"):
        kv = [jnp.take(a, tab, axis=0).reshape(3, C, H, HD) for a in (k, v)]
        s = jnp.einsum("bhqd,bchd->bhqc", q, kv[0]) / np.sqrt(HD)
        s = jnp.where(jnp.asarray(mask)[:, None], s, -1e30)
        want = np.asarray(jnp.einsum("bhqc,bchd->bhqd",
                                     jax.nn.softmax(s, -1), kv[1]))
    gap = float(np.abs(got[:2] - want[:2]).max())
    out["wrapped[3,1]"] = {"max_gap": gap,
                           "ref_max": float(np.abs(want[:2]).max()),
                           "pages": bound.tolist(),
                           "free_slot_zero": bool((got[2] == 0).all())}
    # default matmul precision (bf16 passes) on both sides: 1e-2 relative
    ok = (worst < 2e-2 and gap < 2e-2 * np.abs(want[:2]).max()
          and out["wrapped[3,1]"]["free_slot_zero"])
    return ok, out


def _ms_a_layer(attend, q, *args):
    """Best mean time of one call of ``attend(q, *args)``, 12 dependent
    calls in one program (each needs the one before it), 5 x 10 runs."""
    import jax

    @jax.jit
    def twelve(q, *a):
        for _ in range(12):
            q = q + (1e-3 * attend(q, *a)).astype(q.dtype)
        return q

    twelve(q, *args).block_until_ready()
    best = float("inf")
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(10):
            r = twelve(q, *args)
        r.block_until_ready()
        best = min(best, (time.perf_counter() - t) / 120)
    return best * 1e3


def _pools(rng):
    import jax.numpy as jnp

    return [jnp.asarray(rng.standard_normal((PAGES + 1, PAGE, H * HD)),
                        jnp.float32) for _ in range(2)]


#: the forms of the admission grid: name -> (query tile or None for the
#: bucket whole, heads a step, heads' chains abreast)
ADMIT_FORMS = {"one_tile_h2": (None, 2, 1), "one_tile_h4": (None, 4, 1),
               "one_tile_h4_g4": (None, 4, 4),
               "tile128_h12": (128, 12, 1), "tile128_h12_g4": (128, 12, 4),
               "tile256_h12": (256, 12, 1), "tile256_h12_g2": (256, 12, 2),
               "tile256_h12_g4": (256, 12, 4), "tile256_h12_g6": (256, 12, 6),
               "tile256_h12_g12": (256, 12, 12),
               "tile320_h12_g4": (320, 12, 4), "tile320_h12_g6": (320, 12, 6),
               "tile384_h12_g4": (384, 12, 4), "rule": (0, 0, 0)}
#: two rows a call, as docs_closed fills them: uniform 384-768, the shorter
#: row padded to the wider one's bucket
ADMIT_ROWS = {512: (500, 400), 640: (600, 530), 768: (704, 512),
              1024: (1000, 800)}


def admit_ms():
    import functools

    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import (_sweep, block_pages,
                                                key_visible,
                                                paged_flash_decode,
                                                query_tile, sweep_bound)

    rng = np.random.RandomState(42)
    k, v = _pools(rng)
    out = {}
    for T, lengths in ADMIT_ROWS.items():
        table, pos_map, pos = _layout(rng, lengths, T)
        pos[:] = -1  # rows left-aligned, as an admission packs them
        for b, n in enumerate(lengths):
            pos[b, :n] = np.arange(n)
        mask = key_visible(pos_map[:, None, :], pos[:, :, None], C)
        q = jnp.asarray(rng.standard_normal((2, H, T, HD)), jnp.float32)
        row = out[f"admit[2,{T}]"] = {"lengths": list(lengths)}
        for name, (tile, bh, abreast) in ADMIT_FORMS.items():
            if name == "rule":  # what the model's call resolves to
                tile, attend = query_tile(T), paged_flash_decode
            else:
                tile = tile or T
                attend = functools.partial(
                    _sweep, k_scale=None, v_scale=None, block_h=bh,
                    sm_scale=HD ** -0.5, tile=tile, abreast=abreast)
            bound = sweep_bound(mask, PAGE, tile)
            args = (jnp.maximum(jnp.asarray(table), 0), jnp.asarray(pos_map),
                    jnp.asarray(pos), jnp.asarray(bound))
            try:
                ms = _ms_a_layer(attend, q, k, v, *args)
            except Exception as e:  # the compiler's refusal (VMEM)
                ms, row[name + "_error"] = None, repr(e)[:300]
            row[name] = ms
            # [128 rows x 128 keys] products a head the form multiplies
            row[name + "_products"] = int(
                (-(-bound // block_pages(PAGE))).sum()) * -(-tile // 128)
    return out


def kernel_ms():
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import (key_visible,
                                                paged_flash_decode,
                                                sweep_bound)

    rng = np.random.RandomState(31)
    k, v = _pools(rng)
    cases = {
        "decode_docs_closed[32,1]": (1, [int(n) for n in
                                         rng.randint(400, 800, 32)]),
        "decode_chat_open[32,1]": (1, [200, 90, 330] + [0] * 29),
        "decode_whole_window[32,1]": (1, [C] * 32),
        "admit[2,768]_two_rows": (768, [768, 600]),
        "admit[2,768]_one_row": (768, [700, 0]),
        "admit[2,64]_one_row": (64, [50, 0]),
    }
    out = {}
    for name, (T, lengths) in cases.items():
        table, pos_map, pos = _layout(rng, lengths, T)
        B = len(lengths)
        bound = sweep_bound(key_visible(pos_map[:, None, :],
                                        pos[:, :, None], C), PAGE)
        q = jnp.asarray(rng.standard_normal((B, H, T, HD)), jnp.float32)
        args = (jnp.maximum(jnp.asarray(table), 0), jnp.asarray(pos_map),
                jnp.asarray(pos), jnp.asarray(bound))
        out[name] = {"ms_a_layer": _ms_a_layer(paged_flash_decode, q, k, v,
                                               *args),
                     "pages": int(bound.sum()),
                     "kv_mb": int(bound.sum()) * PAGE * 2 * H * HD * 4 / 1e6}
    return out


#: the latent cells' decode shapes: slots, pages a slot, contexts' range
LATENT_SHAPES = {"joyai_flash[32x288]": (32, 288, (1100, 4200)),
                 "kimi_linear[128x304]": (128, 304, (1100, 4500))}
LATENT_KEYS = (128, 256, 512, 1024)
L_PAGE, L_WIDTH, L_RANK, L_HEADS = 16, 640, 512, 32


def _latent_layout(rng, B, G, span):
    """A pool's worth of pages dealt to ``B`` slots of contexts drawn from
    ``span``; slot 0 has wrapped, slot 1 is free, slot 2 ends inside a
    page."""
    C = G * L_PAGE
    lengths = rng.integers(*span, B)
    lengths[1], lengths[2] = 0, lengths[2] // L_PAGE * L_PAGE + 5
    free = list(rng.permutation(B * G))
    table = np.full((B, G), -1, np.int32)
    pos_map = np.full((B, C), -1, np.int32)
    pos = np.full((B, 1), -1, np.int32)
    for b, n in enumerate(lengths):
        for g in range(-(-n // L_PAGE)):
            table[b, g] = free.pop()
        pos_map[b, :n] = np.arange(n)
        if n:
            pos[b, 0] = n - 1
    at = C + 777  # slot 0: the ring has wrapped, every page live
    table[0] = [free.pop() if t < 0 else t for t in table[0]]
    c = np.arange(C)
    lap = at - at % C + c
    pos_map[0] = np.where(c <= at % C, lap, lap - C)
    pos[0, 0] = at
    return table, pos_map, pos


def latent_ms(hbm_bytes_s):
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.latent_attention import (DECODE_KEYS,
                                                 decode_block_pages,
                                                 latent_decode)
    from paddle_tpu.ops.paged_attention import (_sweep, key_visible,
                                                sweep_bound)

    scale = np.float32(192 ** -0.5)
    out, ok = {}, True
    for name, (B, G, span) in LATENT_SHAPES.items():
        rng = np.random.default_rng(45)
        C = G * L_PAGE
        table, pos_map, pos = _latent_layout(rng, B, G, span)
        mask = key_visible(pos_map[:, None, :], pos[:, :, None], C)
        bound = sweep_bound(mask, L_PAGE)
        pool = jax.random.normal(jax.random.PRNGKey(45),
                                 (B * G + 1, L_PAGE, L_WIDTH), jnp.bfloat16)
        q = jnp.asarray(rng.normal(size=(B, L_HEADS, L_WIDTH)) * 0.3,
                        jnp.bfloat16)
        args = (jnp.maximum(jnp.asarray(table), 0), jnp.asarray(pos_map),
                jnp.asarray(pos), jnp.asarray(bound))
        swept = int(bound.sum()) * L_PAGE * L_WIDTH * 2

        def gather(q, pool, tab, pm, qp, _bound):
            # LatentAttention.absorbed over the view forward_paged gathers
            view = pool.at[tab].get(mode="promise_in_bounds").reshape(
                B, C, L_WIDTH)
            s = jnp.einsum("bhw,bsw->bhs", q, view,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(key_visible(pm[:, None, :], qp[:, :, None], C), s,
                          jnp.finfo(jnp.float32).min)
            p = jax.nn.softmax(s, axis=-1).astype(view.dtype)
            return jnp.einsum("bhs,bsc->bhc", p, view[..., :L_RANK],
                              preferred_element_type=jnp.float32).astype(
                                  view.dtype)

        def twice(q, pool, tab, pm, qp, bound):
            # the K/V walk handed the pool as both pools: each page twice
            o = _sweep(q[:, None], pool, pool, tab, pm,
                       jnp.broadcast_to(qp, (B, L_HEADS)), bound, None, None,
                       block_h=1, sm_scale=float(scale))
            return o[:, 0, :, :L_RANK]

        def walk(keys):
            return functools.partial(latent_decode, scale=float(scale),
                                     value_width=L_RANK, block_keys=keys)

        def rates(attend):
            def fed_back(q, pool, *a):  # [B, H, rank] back into a query
                o = attend(q, pool, *a)
                return jnp.pad(o, ((0, 0), (0, 0), (0, L_WIDTH - L_RANK)))
            t = _ms_a_layer(fed_back, q, pool, *args)
            return {"ms_a_layer": t, "gb_s": swept / t / 1e6,
                    "roofline_share": swept / hbm_bytes_s / (t / 1e3)}

        # a free slot's row is the kernel's zeros and the view's mean: unread
        live = pos[:, 0] >= 0
        want = np.asarray(jax.jit(gather)(q, pool, *args), np.float32)[live]
        row = out[name] = {
            "live_share": float((pos_map >= 0).mean()),
            "pages_swept": int(bound.sum()), "swept_mb": swept / 1e6,
            "roofline_ms": swept / hbm_bytes_s * 1e3, "rule": DECODE_KEYS}
        for keys in LATENT_KEYS:
            got = np.asarray(jax.jit(walk(keys))(q, pool, *args), np.float32)
            gap = float(np.abs(got[live] - want).max())
            ppb = decode_block_pages(L_PAGE, G, keys)
            row[f"keys{keys}"] = {
                "max_gap": gap, "ref_max": float(np.abs(want).max()),
                "free_slot_zero": bool((got[1] == 0).all()),
                "pages_fetched": int((-(-bound // ppb)).sum()) * ppb,
                **rates(walk(keys))}
            # bfloat16 probabilities and contexts on both sides
            ok = bool(ok and gap < 2e-2 * np.abs(want).max()
                      and row[f"keys{keys}"]["free_slot_zero"])
        row["gather_path"] = rates(gather)
        row["paged_decode_pool_twice"] = rates(twice)
    return ok, out


def read_trace(trace_dir):
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks"))
    from harness import trace_reduce as tr

    dev = tr.load(tr.find_xplane(trace_dir))["devices"]
    dev = dev[min(dev)]
    # an op is named by its HLO text, operands included: the kernel is the
    # instruction CALLED paged_decode.N (latent_decode.N: the latent
    # decoders' walk), not every op that reads its result
    kern = sorted((s, d) for n, s, d in dev["ops"]
                  if tr.short_op_name(n).startswith(("paged_decode",
                                                     "latent_decode")))
    out = {}
    for name, s, d in dev["modules"]:
        mine = [kd for ks, kd in kern if s <= ks < s + d]
        if not mine:
            continue
        row = out.setdefault(name, {"runs": 0, "ms": 0.0, "kernel_calls": 0,
                                    "kernel_ms": 0.0})
        row["runs"] += 1
        row["ms"] += d / 1e6
        row["kernel_calls"] += len(mine)
        row["kernel_ms"] += sum(mine) / 1e6
    for row in out.values():
        row["ms_a_run"] = row.pop("ms") / row["runs"]
        row["kernel_ms_a_call"] = row["kernel_ms"] / row["kernel_calls"]
        row["kernel_ms_a_run"] = row.pop("kernel_ms") / row["runs"]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", help="read this trace directory instead")
    ap.add_argument("--admit", action="store_true",
                    help="time the admission width's grid forms instead")
    ap.add_argument("--latent", action="store_true",
                    help="time the latent decoders' page walk instead")
    a = ap.parse_args()
    if a.trace:
        print(json.dumps({"trace": a.trace, "programs": read_trace(a.trace)}))
        return 0
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no TPU here: {d.platform}"}))
        return 1
    if a.admit:
        print(json.dumps({"ok": True, "device": d.device_kind,
                          "admit_ms_a_layer": admit_ms()}))
        return 0
    if a.latent:
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks"))
        from harness import peaks

        ok, rows = latent_ms(
            peaks.peaks_for(d.device_kind)["hbm_bytes_per_s"])
        print(json.dumps({"ok": ok, "device": d.device_kind,
                          "latent_decode": rows}))
        return 0 if ok else 1
    ok, rows = agree()
    line = {"ok": ok, "device": d.device_kind, "agree": rows}
    if ok:  # no timing of a kernel that disagrees
        line["kernel_ms"] = kernel_ms()
    print(json.dumps(line))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
