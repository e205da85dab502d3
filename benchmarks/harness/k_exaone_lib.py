"""Per-layer readers for a decoder of sliding-window layers (per-slot K/V
rings) and global layers (K/V pages) whose FFNs are a dense MLP and expert
layers HOLDING A SHARE of their experts (``paddle_tpu/models/hybrid.py`` with
``families/k_exaone.py``'s options): the operations and bytes of each Pallas
kernel it runs and the share of device-busy time in each of its three
mechanisms.

The trace is read as ``latent_moe_lib`` reads it (an op's event carries no
named scope, so the scopes come from the engine's compiled programs through
``scope_map``; the cell's runner puts them into ``ev["facts"]["op_scopes"]``).
Every reader returns None, and its metric is left out, where the program has
no such counter, scope or kernel.

What is counted is what the MODEL defines: a window layer's admission earns
the (query, key) pairs inside the window of REAL prompt tokens, never the
blocks the kernel swept and masked; the expert kernel's bytes are the
matrices of the held experts that got a row, once each (an expert's second
row tile re-reads them in the width-tiled kernel: time the share pays for).
"""
from benchmarks.harness import latent_moe_lib, qwen3_next_lib, trace_reduce
from benchmarks.harness.latent_moe_lib import (_OP_NAME, _counters,
                                               _kernel_calls, _trace, head)

# exact for this model as they are: rows and touched experts come from
# counters of the held experts only, the sizes from the configuration's keys
# of the same names (``num_experts`` counts the experts held)
decode_expert_kernel_roofline_share = (
    latent_moe_lib.decode_kernel_roofline_share)
admit_expert_kernel_roofline_share = (
    qwen3_next_lib.admit_expert_kernel_roofline_share)
paged_decode_roofline_share = qwen3_next_lib.paged_decode_roofline_share
moe_local_pair_share = qwen3_next_lib.moe_local_pair_share
experts_touched_per_step = latent_moe_lib.experts_touched_per_step
expert_load_max_over_mean = latent_moe_lib.expert_load_max_over_mean
_least = qwen3_next_lib._least


# -- the window layers' kernels ----------------------------------------------------
def window_pairs(length, window):
    """(query, key) pairs a head of a window layer defines over a prompt of
    ``length`` tokens: ``sum_p min(p + 1, window)``."""
    full = min(length, window)
    return full * (full + 1) // 2 + max(length - window, 0) * window


def window_prefill_flops(pairs, query_heads, head_dim):
    """The score and the weighted sum of each pair, 2 FLOPs a
    multiply-add."""
    return 4.0 * query_heads * head_dim * pairs


def window_prefill_bytes(tokens, query_heads, kv_heads, head_dim, itemsize=2):
    """Queries in and contexts out, K and V of the K/V heads once."""
    return itemsize * tokens * head_dim * 2.0 * (query_heads + kv_heads)


def window_prefill_roofline_share(ev):
    """``flash_fwd_window``, one event a window layer of an admission call:
    the pairs of the call's real prompt tokens (the mix's mean of
    :func:`window_pairs` x the rows a call admits) over the events' mean
    time."""
    c, s = _counters(ev), ev["facts"].get("sizes") or {}
    pairs, calls = ev["facts"].get("window_pairs_mean"), _kernel_calls(
        ev, "flash_fwd_window")
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim")
    if (not c.get("admit_steps") or not pairs or calls is None
            or any(k not in s for k in keys)):
        return None
    h, hkv, hd = (s[k] for k in keys)
    rows = c["admit_rows"] / c["admit_steps"]
    tokens = c["admit_tokens"] / c["admit_steps"]
    return 100.0 * _least(ev, window_prefill_flops(rows * pairs, h, hd),
                          window_prefill_bytes(tokens, h, hkv, hd)) / calls[0]


def window_decode_bytes(slots, window, row_lanes, itemsize=2):
    """The slots' ring rows of K and V once.  The block-diagonal queries and
    the contexts (4 MB each way at 32 slots x 64 heads x 1024 lanes) are
    step-local intermediates and are NOT counted: counted, the kernel read
    113.7 % of the HBM roofline on the chip (my chip runs, PR 40), so they
    do not all cross it."""
    return itemsize * 2.0 * slots * window * row_lanes


def window_decode_roofline_share(ev):
    """``window_decode`` (``paged_decode`` over the rings, one page of
    ``sliding_window`` rows a slot), one event a window layer of a decode
    step: the LIVE slots' ring rows (a free slot's bound is 0 and nothing
    of it is fetched), counted whole although a young sequence has filled
    few of them."""
    c, s = _counters(ev), ev["facts"].get("sizes") or {}
    calls = _kernel_calls(ev, "window_decode")
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window")
    if (not c.get("decode_steps") or "live_slot_steps" not in c
            or calls is None or any(k not in s for k in keys)):
        return None
    h, hkv, hd, window = (s[k] for k in keys)
    live = c["live_slot_steps"] / c["decode_steps"]
    return 100.0 * _least(
        ev, qwen3_next_lib.paged_decode_flops(live * window, h, hkv * hd),
        window_decode_bytes(live, window, hkv * hd)) / calls[0]


# -- time by mechanism ----------------------------------------------------------------
_KINDS = ("moe", "win", "attn")
_KERNELS = (("moe_gated_mlp", "moe"), ("window_decode", "win"),
            ("flash_fwd_window", "win"), ("paged_decode", "attn"),
            ("flash_fwd_grouped", "attn"))


def classify(op_name):
    """``moe``, ``win``, ``attn`` or None for an ``op_name`` path: the
    program wraps the expert layer and the two mixers in
    ``jax.named_scope`` of those names."""
    parts = op_name.split("/")
    return next((k for k in _KINDS if k in parts), None)


def scope_map(program_texts):
    """``latent_moe_lib.scope_map`` over this model's three scopes."""
    out, clash = {}, set()
    for text in program_texts.values():
        for line in text.splitlines():
            h, m = head(line), _OP_NAME.search(line)
            if h is None or m is None:
                continue
            kind = classify(m.group(1))
            if out.setdefault(h, kind) != kind:
                clash.add(h)
    return {h: k for h, k in out.items() if k and h not in clash}


def _kind(ev, text):
    for mark, kind in _KERNELS:
        if mark in text:
            return kind
    return (ev["facts"].get("op_scopes") or {}).get(head(text))


def _time_share(ev, kind):
    t = _trace(ev)
    if t is None or not t["ops"] or not ev["facts"].get("op_scopes"):
        return None
    busy = trace_reduce.union_ns([(s, d) for _, s, d in t["ops"]])
    mine = trace_reduce.union_ns([(s, d) for text, s, d in t["ops"]
                                  if _kind(ev, text) == kind])
    return 100.0 * mine / busy if busy else None


def moe_time_share(ev):
    return _time_share(ev, "moe")


def window_attn_time_share(ev):
    return _time_share(ev, "win")


def full_attn_time_share(ev):
    return _time_share(ev, "attn")
