"""Per-layer readers for a decoder of gated-delta-rule layers and gated
full-attention layers whose every FFN is an expert layer HOLDING A SHARE of
its experts (``paddle_tpu/models/hybrid.py`` with
``families/qwen3_next.py``'s options): the operations and bytes of each
Pallas kernel it runs, the share of (token, choice) pairs whose expert is
here, and the share of device-busy time in each of its three mechanisms.

The trace is read as ``latent_moe_lib`` reads it (an op's event carries no
named scope, so the scopes come from the engine's compiled programs through
``scope_map``; the cell's runner puts them into ``ev["facts"]["op_scopes"]``).
Every reader returns None, and its metric is left out, where the program has
no such counter, scope or kernel.

What is counted is what is HELD and TOUCHED: the expert kernel's bytes are
the matrices of the held experts that got a row, never the router's width.
"""
from benchmarks.harness import hybrid_lib, latent_moe_lib, trace_reduce
from benchmarks.harness.latent_moe_lib import (_OP_NAME, _counters,
                                               _kernel_calls, _trace, head)

# exact for this model as they are: both count per VALUE head (the chunk
# walk's operands are per value head), and the expert kernel's rows and
# touched experts come from counters of the held experts only
chunk_kernel_roofline_share = hybrid_lib.chunk_kernel_roofline_share
decode_expert_kernel_roofline_share = (
    latent_moe_lib.decode_kernel_roofline_share)
experts_touched_per_step = latent_moe_lib.experts_touched_per_step
expert_load_max_over_mean = latent_moe_lib.expert_load_max_over_mean


def _least(ev, flops, nbytes):
    return max(flops / ev["peaks"]["bf16_flops"],
               nbytes / ev["peaks"]["hbm_bytes_per_s"])


# -- counters -------------------------------------------------------------------
def moe_local_pair_share(ev):
    """Of the (token, choice) pairs the decode steps' routers made, the
    share whose expert this chip holds."""
    c = _counters(ev)
    if not c.get("moe_pairs_routed") or "moe_pairs_local" not in c:
        return None
    return 100.0 * c["moe_pairs_local"] / c["moe_pairs_routed"]


# -- the kernels' operations and bytes ---------------------------------------------
def step_flops(slots, value_heads, dk, dv):
    """``gated_delta_step``: per slot and value head the decay (dk dv), the
    prediction and the output (2 dk dv each) and the rank-one write (2 dk
    dv)."""
    return 7.0 * slots * value_heads * dk * dv


def step_bytes(slots, key_heads, value_heads, dk, dv, itemsize=4):
    """Every slot's float32 state in and out, q and k of the KEY heads, v,
    the two gates and the output of the value heads."""
    return itemsize * slots * (value_heads * (2.0 * dk * dv + 2 * dv + 2)
                               + key_heads * 2 * dk)


def step_kernel_roofline_share(ev):
    """One event a linear layer of a decode step, which updates every
    slot's state, live or not: the slots are the counted state bytes of a
    step (``state_bytes_steps / decode_steps``, in and out) over one slot's
    (``facts["slot_state_bytes"]``)."""
    c, s = _counters(ev), ev["facts"].get("sizes") or {}
    per_slot = ev["facts"].get("slot_state_bytes")
    keys = ("linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim")
    calls = _kernel_calls(ev, "gated_delta_step")
    if (not c.get("decode_steps") or not c.get("state_bytes_steps")
            or any(k not in s for k in keys) or not per_slot
            or calls is None):
        return None
    slots = c["state_bytes_steps"] / c["decode_steps"] / (2.0 * per_slot)
    hk, hv, dk, dv = (s[k] for k in keys)
    return 100.0 * _least(ev, step_flops(slots, hv, dk, dv),
                          step_bytes(slots, hk, hv, dk, dv)) / calls[0]


def paged_decode_flops(keys, query_heads, row_lanes):
    """The decode width: per swept key one ``[H, row] x [row]`` score
    product and one ``[H] x [row]`` context product, 2 FLOPs a
    multiply-add (the heads are the query rows of one head as wide as a
    pool row)."""
    return 4.0 * keys * query_heads * row_lanes


def paged_decode_bytes(keys, slots, query_heads, row_lanes, itemsize=2):
    """The swept pages' K and V rows once, the block-diagonal queries in
    and the contexts out."""
    return itemsize * (2.0 * keys * row_lanes
                       + 2.0 * slots * query_heads * row_lanes)


def paged_decode_roofline_share(ev):
    """One event a full layer of a decode step (an admission attends to its
    own K/V by the flash kernel): the keys of the pages the kernel walked
    (``kv_pages_swept_steps / decode_steps`` x the page size), rows of
    ``num_key_value_heads x head_dim`` lanes."""
    c, s = _counters(ev), ev["facts"].get("sizes") or {}
    page, calls = ev["facts"].get("kv_page_size"), _kernel_calls(
        ev, "paged_decode")
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim")
    if (not c.get("decode_steps") or "kv_pages_swept_steps" not in c
            or not c.get("kv_page_slots_steps") or not page or calls is None
            or any(k not in s for k in keys)):
        return None
    h, hkv, hd = (s[k] for k in keys)
    swept = c["kv_pages_swept_steps"] / c["decode_steps"] * page
    # B x G page-table entries a step over G entries a slot: the slots
    slots = ev["facts"].get("slots") or 0
    return 100.0 * _least(ev, paged_decode_flops(swept, h, hkv * hd),
                          paged_decode_bytes(swept, slots, h, hkv * hd)
                          ) / calls[0]


def admit_expert_kernel_roofline_share(ev):
    """The admission call's expert kernel (128-row tiles): the USEFUL prompt
    tokens of a call x top-k x the share of pairs that land here (the
    decode steps' measured share: the routers are the same) rows, every
    HELD expert's matrices once, over the traced calls' mean time."""
    c, s = _counters(ev), ev["facts"].get("sizes") or {}
    share, calls = moe_local_pair_share(ev), _kernel_calls(
        ev, "moe_gated_mlp_tm128")
    keys = ("hidden_size", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok")
    if (not c.get("admit_steps") or share is None or calls is None
            or any(k not in s for k in keys)):
        return None
    d, f, held, k = (s[key] for key in keys)
    rows = c["admit_tokens"] / c["admit_steps"] * k * share / 100.0
    return 100.0 * _least(
        ev, latent_moe_lib.gated_mlp_flops(rows, d, f),
        latent_moe_lib.gated_mlp_bytes(rows, held, d, f)) / calls[0]


# -- time by mechanism ----------------------------------------------------------------
_KINDS = ("moe", "gdn", "attn")
_KERNELS = (("moe_gated_mlp", "moe"), ("gated_delta_", "gdn"),
            ("paged_decode", "attn"), ("flash_fwd_grouped", "attn"))


def classify(op_name):
    """``moe``, ``gdn``, ``attn`` or None for an ``op_name`` path: the
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


def gdn_time_share(ev):
    return _time_share(ev, "gdn")


def full_attn_time_share(ev):
    return _time_share(ev, "attn")
