"""Per-layer readers for a decoder of gated-delta-rule layers and
full-attention layers (``paddle_tpu/models/hybrid.py``): the operations and
bytes of its two Pallas kernels (``paddle_tpu/ops/gated_delta.py``) and the
share of device-busy time inside each kind of mixer.

The trace is read as ``latent_moe_lib`` reads it (an op's event carries no
named scope, so the scopes come from the engine's compiled programs through
``scope_map``; the cell's runner puts them into ``ev["facts"]["op_scopes"]``).
Every reader returns None, and its metric is left out, where the program has
no such counter, scope or kernel.
"""
from benchmarks.harness import trace_reduce
from benchmarks.harness.latent_moe_lib import (_OP_NAME, _counters,
                                               _kernel_calls, _trace, head)

CHUNK = 64  # paddle_tpu/ops/gated_delta.py:CHUNK


# -- the kernels' operations and bytes ------------------------------------------
def chunk_flops(tokens, heads, dk, dv, chunk=CHUNK):
    """``gated_delta_chunk`` (the walk over a prompt's chunks): per chunk
    and head ``W S`` and ``(q e^b) S`` (2 C dk dv each), ``P (U - W S)``
    (2 C C dv) and ``(k e^..)^T (U - W S)`` (2 dk C dv)."""
    return tokens / chunk * heads * 2.0 * chunk * dv * (3 * dk + chunk)


def chunk_bytes(tokens, rows, heads, dk, dv, chunk=CHUNK, itemsize=4):
    """Per token and head the five float32 operands in (three of ``dk``,
    one of ``dv``, a row of the ``[C, C]`` scores) and ``dv`` out; per row
    and head the final state out."""
    return itemsize * heads * (tokens * (3 * dk + 2 * dv + chunk)
                               + rows * dk * dv)


def step_flops(slots, heads, dk, dv):
    """``gated_delta_step``: per slot and head the decay (dk dv), the
    prediction ``k^T S`` and the output ``q^T S`` (2 dk dv each) and the
    rank-one write (2 dk dv)."""
    return 7.0 * slots * heads * dk * dv


def step_bytes(slots, heads, dk, dv, itemsize=4):
    """Every slot's float32 state in and out, its q, k, v, the two gates
    and the output."""
    return itemsize * slots * heads * (2.0 * dk * dv + 2 * dk + 2 * dv + 2)


def _sizes(ev):
    s = ev["facts"].get("sizes") or {}
    keys = ("linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim")
    return tuple(s[k] for k in keys) if all(k in s for k in keys) else None


def _share(ev, kernel, flops, nbytes):
    calls = _kernel_calls(ev, kernel)
    if calls is None:
        return None
    least = max(flops / ev["peaks"]["bf16_flops"],
                nbytes / ev["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / calls[0]


def chunk_kernel_roofline_share(ev):
    """One event a linear layer of an admission call: the call's USEFUL
    prompt tokens (``gdn_prefill_tokens / admit_steps``; chunks walked for
    padding are work the algorithm does not need) over the events' mean
    time."""
    c, sizes = _counters(ev), _sizes(ev)
    if (not c.get("admit_steps") or "gdn_prefill_tokens" not in c
            or sizes is None):
        return None
    tokens = c["gdn_prefill_tokens"] / c["admit_steps"]
    rows = c["admit_rows"] / c["admit_steps"]
    return _share(ev, "gated_delta_chunk", chunk_flops(tokens, *sizes),
                  chunk_bytes(tokens, rows, *sizes))


def step_kernel_roofline_share(ev):
    """One event a linear layer of a decode step, which updates every
    slot's state, live or not: the slots are the counted state bytes of a
    step (``state_bytes_steps / decode_steps``, in and out) over one slot's
    (``facts["slot_state_bytes"]``)."""
    c, sizes = _counters(ev), _sizes(ev)
    per_slot = ev["facts"].get("slot_state_bytes")
    if (not c.get("decode_steps") or not c.get("state_bytes_steps")
            or sizes is None or not per_slot):
        return None
    slots = c["state_bytes_steps"] / c["decode_steps"] / (2.0 * per_slot)
    return _share(ev, "gated_delta_step", step_flops(slots, *sizes),
                  step_bytes(slots, *sizes))


# -- time by mechanism ------------------------------------------------------------
def classify(op_name):
    """``gdn``, ``attn`` or None for an ``op_name`` path: the program wraps
    the two mixers in ``jax.named_scope("gdn")`` / ``("attn")``."""
    parts = op_name.split("/")
    return "gdn" if "gdn" in parts else "attn" if "attn" in parts else None


def scope_map(program_texts):
    """``latent_moe_lib.scope_map`` over this model's two scopes."""
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
    if "gated_delta_" in text:
        return "gdn"
    if "paged_decode" in text:
        return "attn"
    return (ev["facts"].get("op_scopes") or {}).get(head(text))


def _time_share(ev, kind):
    t = _trace(ev)
    if t is None or not t["ops"] or not ev["facts"].get("op_scopes"):
        return None
    busy = trace_reduce.union_ns([(s, d) for _, s, d in t["ops"]])
    mine = trace_reduce.union_ns([(s, d) for text, s, d in t["ops"]
                                  if _kind(ev, text) == kind])
    return 100.0 * mine / busy if busy else None


def gdn_time_share(ev):
    return _time_share(ev, "gdn")


def full_attn_time_share(ev):
    return _time_share(ev, "attn")
