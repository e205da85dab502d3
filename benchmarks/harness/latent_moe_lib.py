"""Per-layer readers for a latent-attention decoder with routed experts
(``paddle_tpu/models/latent_moe.py``): the operations and bytes of its
grouped gated-MLP kernel and of its two attention kernels (the prompt's, and
the decode step's walk over the latent pages), the experts a decode step
touches, and the share of device-busy time inside the expert layers and
inside the attention.

``ev`` carries no raw ops, so a trace reader loads the xplane itself from
``ev["facts"]["trace_dir"]`` (what the cell's runner put there) and reduces
over the ``traced_window`` span.  Every reader returns None, and its metric
is left out, where the program has no such counter, scope or kernel (a
program from before they existed).

What a v5e trace shows of these (looked at by hand, PR 27) is written
above ``classify``.
"""
import re

from benchmarks.harness import trace_reduce

_TRACES = {}


def _counters(ev):
    return ev["facts"].get("counters") or {}


# -- counters ---------------------------------------------------------------
def experts_touched_per_step(ev):
    """Mean over decode steps and expert layers of the experts that got at
    least one token."""
    c = _counters(ev)
    if not c.get("moe_layer_steps") or "moe_experts_touched" not in c:
        return None
    return c["moe_experts_touched"] / c["moe_layer_steps"]


def expert_load_max_over_mean(ev):
    """The busiest expert's routed tokens over the mean expert's, decode
    steps of the window, summed over the expert layers."""
    routed = ev["facts"].get("expert_routed")
    if not routed or not sum(routed):
        return None
    return max(routed) * len(routed) / sum(routed)


# -- the kernel's operations and bytes -----------------------------------------
def gated_mlp_flops(rows, d_model, width):
    """Three ``[rows, d] x [d, f]``-sized matmuls (gate, up, down)."""
    return 6.0 * rows * d_model * width


def gated_mlp_bytes(rows, experts, d_model, width, itemsize=2):
    """Each touched expert's three matrices once, each row in and out."""
    return itemsize * (3.0 * experts * d_model * width + 2.0 * rows * d_model)


def _kernel_calls(ev, name, own=False):
    """(mean seconds, count) of the device events of the Pallas kernel
    ``name`` inside the traced window.  An event's text holds its operands'
    names too: ``own`` keeps the events whose OWN instruction is named after
    the kernel (``%latent_decode.4 = ...``)."""
    t = _trace(ev)
    if t is None:
        return None
    durs = [d for text, _, d in t["ops"]
            if name in (text.split(" = ", 1)[0] if own else text)
            and trace_reduce.MOSAIC_MARK in text]
    return (sum(durs) / len(durs) / 1e9, len(durs)) if durs else None


def _roofline_share(ev, name, rows, experts):
    sizes, calls = ev["facts"].get("sizes") or {}, _kernel_calls(ev, name)
    if calls is None or rows is None or experts is None or not sizes:
        return None
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    least = max(gated_mlp_flops(rows, d, f) / ev["peaks"]["bf16_flops"],
                gated_mlp_bytes(rows, experts, d, f)
                / ev["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / calls[0]


def decode_kernel_roofline_share(ev):
    """The decode step's kernel call (16-row tiles): ``live slots x top-k``
    rows, the experts a layer touched (both window means, from the
    counters), over the traced calls' mean time."""
    c, sizes = _counters(ev), ev["facts"].get("sizes") or {}
    if not c.get("decode_steps") or not sizes:
        return None
    # every slot's row is routed, live or not: the step's batch is static
    rows = c.get("moe_routed_tokens", 0) / max(c.get("moe_layer_steps", 0), 1)
    return _roofline_share(ev, "moe_gated_mlp_tm16", rows or None,
                           experts_touched_per_step(ev))


def prefill_kernel_roofline_share(ev):
    """The admission call's kernel call (128-row tiles): the USEFUL prompt
    tokens of a call x top-k rows (padding rows are work the algorithm does
    not need), every expert touched, over the traced calls' mean time."""
    c, sizes = _counters(ev), ev["facts"].get("sizes") or {}
    if not c.get("admit_steps") or not sizes:
        return None
    rows = c["admit_tokens"] / c["admit_steps"] * sizes["num_experts_per_tok"]
    return _roofline_share(ev, "moe_gated_mlp_tm128", rows,
                           sizes["n_routed_experts"])


def prompt_attention_flops(pair_count, heads, qk_width, v_width):
    """Causal attention over ``pair_count`` visible (query, key) pairs a
    head: the score and the weighted sum, 2 FLOPs a multiply-add."""
    return 2.0 * heads * (qk_width + v_width) * pair_count


def prompt_attention_bytes(rows, keys, heads, qk_width, v_width, rope_width,
                           itemsize=2):
    """Queries in, per-head keys and values and the shared rotary key once,
    the context out."""
    return itemsize * (rows * heads * (qk_width + v_width)
                       + keys * (heads * (qk_width - rope_width + v_width)
                                 + rope_width))


def prefill_attention_roofline_share(ev):
    """The prompt-attention kernel, one event a layer of an admission call:
    the call's useful (query, key) pairs (its rows' mean of L (L + 1) / 2
    over the mix's prompts x the rows a call admits, from the counters) over
    the traced events' mean time.  Tiles the kernel walks for padding or
    beyond a row's length are work the algorithm does not need."""
    c, sizes = _counters(ev), ev["facts"].get("sizes") or {}
    pairs, calls = ev["facts"].get("prompt_pairs_mean"), _kernel_calls(
        ev, "latent_prefill_attention")
    if not c.get("admit_steps") or not sizes or not pairs or calls is None:
        return None
    rows = c["admit_rows"] / c["admit_steps"]
    h, qk, v = (sizes["num_attention_heads"], sizes["qk_head_dim"],
                sizes["v_head_dim"])
    tokens = c["admit_tokens"] / c["admit_steps"]
    least = max(
        prompt_attention_flops(rows * pairs, h, qk, v)
        / ev["peaks"]["bf16_flops"],
        prompt_attention_bytes(tokens, tokens, h, qk, v,
                               sizes["qk_rope_head_dim"])
        / ev["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / calls[0]


def latent_page_lanes(sizes):
    """Lanes of one row of a latent page: the compressed K/V and the shared
    positional key side by side, padded to whole 128-lane tiles (512 + 64 ->
    640 at both latent cells' sizes; the model owns the layout)."""
    return -(-(sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]) // 128) * 128


def latent_decode_flops(keys, heads, row_lanes, value_lanes):
    """Per swept key and head a score over the row's lanes and a context
    over its first ``value_lanes``, 2 FLOPs a multiply-add."""
    return 2.0 * keys * heads * (row_lanes + value_lanes)


def latent_decode_bytes(keys, slots, heads, row_lanes, value_lanes,
                        itemsize=2):
    """The swept pages' rows ONCE (the scores read all of a row's lanes, the
    values are the first ``value_lanes`` of the same rows), the
    ``[slots, heads, row_lanes]`` queries in and the
    ``[slots, heads, value_lanes]`` contexts out."""
    return itemsize * (keys * row_lanes
                       + slots * heads * (row_lanes + value_lanes))


def latent_decode_roofline_share(ev):
    """``latent_decode``, one event a latent layer of a decode step: the keys
    of the pages the walk fetched (``kv_pages_swept_steps / decode_steps`` x
    the page size, the window's mean from the counters) over the traced
    events' mean time."""
    c, sizes = _counters(ev), ev["facts"].get("sizes") or {}
    page, slots = ev["facts"].get("kv_page_size"), ev["facts"].get("slots")
    keys = ("kv_lora_rank", "qk_rope_head_dim", "num_attention_heads")
    calls = _kernel_calls(ev, "latent_decode", own=True)
    if (not c.get("decode_steps") or "kv_pages_swept_steps" not in c
            or not page or not slots or calls is None
            or any(k not in sizes for k in keys)):
        return None
    swept = c["kv_pages_swept_steps"] / c["decode_steps"] * page
    h, lanes, v = (sizes["num_attention_heads"], latent_page_lanes(sizes),
                   sizes["kv_lora_rank"])
    least = max(latent_decode_flops(swept, h, lanes, v)
                / ev["peaks"]["bf16_flops"],
                latent_decode_bytes(swept, slots, h, lanes, v)
                / ev["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / calls[0]


# -- time by mechanism ----------------------------------------------------------
# What a v5e trace shows (looked at by hand, PR 27, and in
# tests/data/probe_v5e.xplane.pb): an op's event is named by its HLO text
# WITHOUT the ``metadata={op_name=...}`` that carries ``jax.named_scope``
# paths, and a Pallas kernel is a custom call whose instruction is named
# after the kernel (``name=`` of its ``pallas_call``).  So the scopes come
# from the engine's own compiled programs (``compiled_programs()``), which
# print the same instruction names WITH their metadata: ``scope_map`` turns
# those texts into {instruction head: "moe" | "mla"}, the cell's runner puts
# it into ``ev["facts"]["op_scopes"]``, and an event is looked up by the head
# of its text.
_HEAD = re.compile(r"^\s*(?:ROOT )?(%?[\w.\-]+ = [^ (]*)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def classify(op_name):
    """``moe``, ``mla`` or None for an ``op_name`` path: the program wraps
    the expert layer in ``jax.named_scope("moe")`` and the attention in
    ``jax.named_scope("mla")``."""
    parts = op_name.split("/")
    return "moe" if "moe" in parts else "mla" if "mla" in parts else None


def head(text):
    """``%fusion.12 = bf16[2,4096,2048]{...}``: an instruction's name and
    result type, the part its trace event and its line in the compiled
    program share."""
    m = _HEAD.match(text)
    return m.group(1) if m else None


def scope_map(program_texts):
    """{instruction head: ``moe`` | ``mla``} over the optimized HLO texts of
    several programs; a head that two programs use for different mechanisms
    is left out."""
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
    if "moe_gated_mlp" in text:
        return "moe"
    if "latent_prefill_attention" in text:
        return "mla"
    return (ev["facts"].get("op_scopes") or {}).get(head(text))


def _trace(ev):
    trace_dir = ev["facts"].get("trace_dir")
    if not trace_dir:
        return None
    if trace_dir not in _TRACES:
        try:
            raw = trace_reduce.load(trace_reduce.find_xplane(trace_dir),
                                    ("traced_window",))
        except (FileNotFoundError, OSError):
            _TRACES[trace_dir] = None
            return None
        dev = raw["devices"][min(raw["devices"])]
        span = next(((s, s + d) for n, s, d in raw["host_spans"]
                     if n == "traced_window"), None)
        ops = dev["ops"]
        if span is not None:
            ops = trace_reduce.clip(ops, *span)
        _TRACES[trace_dir] = {"ops": ops}
    return _TRACES[trace_dir]


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


def mla_time_share(ev):
    return _time_share(ev, "mla")
