"""Per-layer readers for a decoder of Kimi-Delta-Attention layers (slot
state) and NoPE latent-attention layers (latent pages) whose FFNs are a
dense MLP and expert layers HOLDING A SHARE of their experts
(``paddle_tpu/models/kimi_linear.py``): the operations and bytes of its two
new Pallas kernels (``paddle_tpu/ops/kda.py``), the other kernels' by the
counts the benchmark already has, and the share of device-busy time in each
of its three mechanisms.

The trace is read as ``latent_moe_lib`` reads it (an op's event carries no
named scope, so the scopes come from the engine's compiled programs through
``scope_map``; the cell's runner puts them into ``ev["facts"]["op_scopes"]``).
Every reader returns None, and its metric is left out, where the program has
no such counter, scope or kernel.

What is counted is what crosses HBM for what the MODEL defines: the state
kernel's bytes are every slot's state in and out and its rows; the chunk
walk's what the kernel itself reads and writes for the call's REAL prompt
tokens (chunks walked for padding earn nothing); the expert kernels' the
matrices of the experts held and touched, once each.
"""
from benchmarks.harness import latent_moe_lib, qwen3_next_lib, trace_reduce
from benchmarks.harness.latent_moe_lib import (_OP_NAME, _counters,
                                               _kernel_calls, _trace, head)

CHUNK = 64  # paddle_tpu/ops/gated_delta.py:CHUNK
experts_touched_per_step = latent_moe_lib.experts_touched_per_step
# the NoPE latent layers' decode walk is the latent cell's kernel, its pages
# laid out alike: the same count
latent_decode_roofline_share = latent_moe_lib.latent_decode_roofline_share
_least = qwen3_next_lib._least


# -- the two KDA kernels' operations and bytes ------------------------------------
def chunk_flops(tokens, heads, dk, dv):
    """``kda_chunk`` (the walk over a prompt's chunks): per chunk and head
    ``W S^T`` and ``(q e^b) S^T`` (2 C dk dv each), ``P (U - W S)`` (2 C C
    dv) and ``(U - W S)^T (k e^..)`` (2 dv C dk)."""
    return tokens / CHUNK * heads * 2.0 * CHUNK * dv * (3 * dk + CHUNK)


def chunk_bytes(tokens, rows, heads, dk, dv):
    """What the kernel itself reads and writes, float32: per token and head
    three operands of ``dk`` (``q e^b``, ``k e^{b_C - b}``, ``W``), one of
    ``dv`` (``U``), a row of the ``[C, C]`` scores and ``dv`` out; per chunk
    and head the ``dk`` decays of the state's hand-over; per row and head
    the final state out.  The WY operands' making (XLA) is not this
    kernel's."""
    return 4 * heads * (
        tokens * (3 * dk + 2 * dv + CHUNK + dk / CHUNK) + rows * dk * dv)


def step_flops(slots, heads, dk, dv):
    """``kda_step``: per slot and head the decay (dk dv), the prediction
    ``k^T S`` and the output ``q^T S`` (2 dk dv each) and the rank-one
    write (2 dk dv)."""
    return 7.0 * slots * heads * dk * dv


def step_bytes(slots, heads, dk, dv):
    """Every slot's float32 state in and out (``2 x 32 x 128 x 128 x 4 B``
    a slot and layer at the published sizes), and its rows: the decay, k
    and q of ``dk``, v, the broadcast ``beta`` and the output of ``dv``."""
    return 4 * slots * heads * (2.0 * dk * dv + 3 * dk + 3 * dv)


def _kda(ev):
    s = ev["facts"].get("kda") or {}
    keys = ("heads", "dk", "dv")
    return tuple(s[k] for k in keys) if all(k in s for k in keys) else None


def chunk_kernel_roofline_share(ev):
    """One event a KDA layer of an admission call: the call's USEFUL prompt
    tokens (``gdn_prefill_tokens / admit_steps``: the counter of any
    slot-state model's admitted prompt tokens) over the events' mean
    time."""
    c, sizes = _counters(ev), _kda(ev)
    calls = _kernel_calls(ev, "kda_chunk")
    if (not c.get("admit_steps") or "gdn_prefill_tokens" not in c
            or sizes is None or calls is None):
        return None
    tokens = c["gdn_prefill_tokens"] / c["admit_steps"]
    rows = c["admit_rows"] / c["admit_steps"]
    return 100.0 * _least(ev, chunk_flops(tokens, *sizes),
                          chunk_bytes(tokens, rows, *sizes)) / calls[0]


def step_kernel_roofline_share(ev):
    """One event a KDA layer of a decode step, which updates every slot's
    state, live or not: the slots are the counted state bytes of a step
    (``state_bytes_steps / decode_steps``, in and out) over one slot's
    (``facts["slot_state_bytes"]``)."""
    c, sizes = _counters(ev), _kda(ev)
    per_slot, calls = ev["facts"].get("slot_state_bytes"), _kernel_calls(
        ev, "kda_step")
    if (not c.get("decode_steps") or not c.get("state_bytes_steps")
            or sizes is None or not per_slot or calls is None):
        return None
    slots = c["state_bytes_steps"] / c["decode_steps"] / (2.0 * per_slot)
    return 100.0 * _least(ev, step_flops(slots, *sizes),
                          step_bytes(slots, *sizes)) / calls[0]


# -- the kernels the benchmark already counts ----------------------------------------
def _renamed(ev):
    """``ev`` with the sizes under the name the older libraries read them
    by: this configuration says ``num_experts_per_token``."""
    s = dict(ev["facts"].get("sizes") or {})
    if "num_experts_per_token" in s:
        s["num_experts_per_tok"] = s["num_experts_per_token"]
    return {**ev, "facts": {**ev["facts"], "sizes": s}}


def decode_expert_kernel_roofline_share(ev):
    """``latent_moe_lib``'s count: rows and touched experts come from
    counters of the held experts only."""
    return latent_moe_lib.decode_kernel_roofline_share(_renamed(ev))


def admit_expert_kernel_roofline_share(ev):
    """``qwen3_next_lib``'s count at the measured local share of pairs,
    every HELD expert's matrices once."""
    return qwen3_next_lib.admit_expert_kernel_roofline_share(_renamed(ev))


# -- time by mechanism ----------------------------------------------------------------
_KINDS = ("moe", "kda", "mla")
_KERNELS = (("moe_gated_mlp", "moe"), ("kda_", "kda"),
            ("latent_prefill_attention", "mla"))


def classify(op_name):
    """``moe``, ``kda``, ``mla`` or None for an ``op_name`` path: the
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


def kda_time_share(ev):
    return _time_share(ev, "kda")


def mla_time_share(ev):
    return _time_share(ev, "mla")
