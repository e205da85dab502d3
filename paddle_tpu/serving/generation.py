"""Batched greedy generation for a paged causal LM (``models.GPTForCausalLM``,
``models.LatentMoEForCausalLM``, ``models.HybridForCausalLM``): one
scheduler over one cache form.

A persistent decode loop (:meth:`GenerationEngine._paged_loop`) owns a
``B``-slot batch and schedules at decode-step granularity — Orca-style
continuous batching.  Each iteration it admits queued requests into free
slots (**prefill**: the prompt in one forward, one jitted executable per
prompt-length bucket), runs one **decode** step for every live slot
through a SINGLE jitted step function, and harvests: slots that reached
EOS or their ``max_new_tokens`` budget are evicted and their futures
resolved, so a stalled long request holds one slot, never the batch.
Every step sees arrays of exactly the same shape (free slots ride along
as inert position ``-1`` rows: they write nothing and attend to nothing),
so the steady-state compile set is closed no matter how many tokens are
generated.  Every per-row computation depends only on its own batch row,
so the tokens are those of uncached greedy decoding.

**Paged KV cache**: the K/V state is ONE shared page pool
(``model.init_paged_cache``) behind a host-owned slot→page-table
indirection (``serving/paging.py``) — vLLM-style PagedAttention.  Pages
are allocated on demand as sequences grow, shared copy-on-write across
slots admitted with a common ``prefix_key`` (the system prompt prefills
once), and returned to a free list at eviction (a pure table edit — no
device call); when the pool runs dry mid-decode the newest slot is
preempted and requeued (greedy decode is deterministic, so regeneration is
bit-identical).  A slot's positions map into its pages modulo
``cache_len``: generation past it slides the window.  The step is a
unified decode/verify executable of static width ``1 +
FLAGS_speculative_k``: an n-gram proposer (prompt-lookup) drafts up to k
tokens per slot per step and the longest prefix matching the model's own
argmax is accepted — token-identical to plain greedy, up to k+1 tokens per
step when text repeats.

**One decode step in flight.**  The loop dispatches step n+1 BEFORE it
reads step n's tokens and reads them while the device runs n+1: a slot's
position, its pages and the end of its budget are known by count, and the
one thing a step needs of the step before it, each row's last token,
stays on the device (a row whose token is still unread carries ``-1`` for
its id and the step program takes it from the previous step's output, its
one operand beside the packed rows).  A slot whose budget ends with the
step being dispatched is freed at that dispatch and its future resolves
when the step is harvested (:class:`_Flight`, :meth:`_harvest`).  What
needs the tokens on the host reads them first, and the iteration is then
the serialized one: an engine that speculates drafts from them, so it
reads every step before it packs the next; an admission, a preemption, a
tenant over budget, a hand-off, ``close()``, new weights and a failed call
all harvest the step in flight before they touch a slot.

The admission program is ``[R(bucket), bucket]``, not ``[B, bucket]``: a
few rows, as many as fit ``_ADMIT_TOKEN_SLOTS`` token slots and at most
``_ADMIT_ROWS`` (:func:`admit_rows`: two up to a bucket of 1024, one where a
row is thousands of tokens and dwarfs the call's fixed cost).  A row reaches
the pool only through its own page-table and position-map rows, so the
loop packs the requests an iteration admits, in admission order, into
chunks (:func:`admit_chunks`: each padded to its widest row's bucket ``b``
and holding at most ``R(b)`` rows, unused rows inert), dispatches one
admission call per chunk back to back, each threading the pool to the
next, and waits for the first tokens once, after the last — the prefill
computes the rows admitted, not every slot.  A LAST chunk that is short of
its program's rows waits where the row that fills it is known to come: with
every free slot taken and a request still queued behind full slots, and the
next live slot ending (by count) inside the break-even of the loop's own
mean decode step against half its mean admission call (:func:`hold_pays`),
the chunk's rows go back to the head of the queue before anything was done
for them, and the freed slot's row joins them in one call.

**Slot state beside the pages.**  A model may keep state per slot that is no
function of pages: a linear-attention layer's matrix state and conv
window, or a WINDOW layer's K and V, which a ring of ``window`` rows a slot
holds whatever the context (position ``p`` in row ``p % window``; what a
row holds is arithmetic on the query's position, so an admission overwrites
what it needs and nothing is reset).  It says so by ``slot_state = True`` and the protocol
grows by one keyword, ``slots``, on two verbs: ``init_paged_cache(...,
slots=B)`` returns a cache whose per-slot leaves have ``B + 1`` rows (row
``B`` is the write-drop row, as page ``P`` is the write-drop page), and
every admission hands ``forward_paged(..., slots=[R])`` its rows' slot
numbers (``-1``: an inert row).  An admitted row starts from the zero
state and leaves its state in its slot; a decode row IS its slot (row
``i`` of the step is slot ``i``; position ``-1`` leaves the slot as it
was); ``slot_state_bytes()`` is what one slot holds.  Preemption and a
restart re-prefill, which resets the state; sliding past ``cache_len``
touches only the layers that have pages.  What has no meaning for such
state is refused at construction or at ``submit``, by name: speculation (a
rejected draft cannot be rolled back), ``role != 'any'`` and ``handoff=``
(the payload carries pages), ``quantized=``; a ``prefix_key`` is served
cold and counted (``prefix_unshared``: mapped pages would come without the
state at the prefix's boundary).  All of these apply to a window layer's
rings as they stand (a ring of exactly ``window`` rows would lose the key a
rejected draft overwrote).  A model without the attribute is handed
neither keyword and builds the programs it always built.  The pages beside
the slot state are the model's own too: K/V pools (``models/hybrid.py``) or
latent pools (``models/kimi_linear.py``), through the same page verbs.

The compile set is closed and traced in :meth:`warmup`:
``len(prompt_buckets) + 3`` with speculation (per-bucket ``[R(bucket),
bucket]`` admission, the unified step, its ``[B, 1]`` no-draft fast trace, the
page-copy op) or ``+ 2`` without.  The loop self-measures both step
variants and drafts only when the predicted accepted tokens out-earn the
wide step's extra cost, with per-slot exponential backoff after
zero-accept verifies — on compute-bound hosts speculation turns itself
off instead of losing throughput.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import (Dict, List, Mapping, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from ..framework.errors import (
    ExecutionTimeoutError,
    InvalidArgumentError,
    UnavailableError,
    is_transient,
)
from ..framework.flags import flag
from ..nn.layer_base import functional_call
from ..ops.paged_attention import block_pages, key_visible, sweep_bound
from ..observability import tracing as _tracing
from ..resilience import CircuitBreaker
from ..resilience import retry as _retry_mod
from ..resilience.faults import fault_point
from .batcher import MicroBatcher, Request
from .metrics import (ADMIT_WALK_COUNTERS, HANDOFF_COUNTERS, LOOP_COUNTERS,
                      LORA_COUNTERS,
                      MOE_COUNTERS, PAGED_COUNTERS, QUANT_COUNTERS,
                      SLOT_COUNTERS, STATE_COUNTERS, TENANCY_COUNTERS,
                      LoopClock, ServingMetrics)
from .paging import PagePool

__all__ = ["GenerationEngine", "KVHandoff"]


class _Flight(NamedTuple):
    """A decode step that was dispatched and whose tokens are still on the
    device: all :meth:`GenerationEngine._harvest` needs, captured at the
    dispatch, so that a row is harvested into the request it was computed
    for whatever its slot holds by then."""

    out: object   # device handle of the step's tokens, [B, columns] int32
    rows: tuple   # (slot, slot record, position, drafts) of each live row
    moe: object   # device handle of its per-expert counts, or None

_gen_counter = [0]

#: the paged admission program's rows: ``R(bucket) = min(batch_size,
#: _ADMIT_ROWS, max(1, _ADMIT_TOKEN_SLOTS // bucket))`` (:func:`admit_rows`).
#: A loop iteration admits a request or two between decode steps, not a
#: batch, and the prefill costs what its rows x bucket cost whether they
#: hold a prompt or not, so the program is traced at ``[R(bucket), bucket]``
#: and an iteration that admits more dispatches it once per chunk.  One
#: row count a bucket, not a ladder of them: every further row size would
#: add ``len(prompt_buckets)`` executables to the warm-up.
#:
#: Two rows pay where the calls carry more rows than the second row costs:
#: with ``t1`` a ``[1, b]`` call and ``t2`` a ``[2, b]`` call (taken with
#: one row real; both real cost 0-11 % more for GPT-2 and nothing for the
#: expert hybrid), a row costs ``t2 / n`` at ``n`` rows a call against
#: ``t1``.  Swept on a TPU v5 lite at the serving cells' own sizes
#: (``tools/admit_rows_chip.py``, PERF.md, PRs 32 and 38; host clock, ms):
#:
#:   GPT-2-small, 32 slots      b   64    128   256   512   640   768
#:                              t1  3.98  4.44  5.06  7.15  7.66  9.20
#:                              t2  4.20  4.95  6.46  9.52 11.00 12.46
#:   latent, 256 experts, 32 s. b   1536   2048   3072   4096
#:                              t1  35.2   40.4   52.4   69.4
#:                              t2  53.8   65.9   90.0  114.5
#:   hybrid, 16 slots           t1  103.5  144.3  200.6  283.5
#:                              t2  214.8  303.9  500.5  699.2
#:   expert hybrid (qwen3_next) b   256    512    768    1024
#:   128 of 512 experts, 64 s.  t1  18.2   23.5   29.5   39.9
#:                              t2  23.5   41.2   54.5   71.8
#:                both rows real    23.8   40.4   53.8   70.6
#:
#: About 2 ms of a call are its two ends on the host, whatever its rows.
#: Up to 768 tokens of GPT-2 that is a large part of the call: ``t2 / t1``
#: is 1.06-1.44, so two rows are cheaper a row from 1.5 rows a call; an
#: open loop that admits one row a call pays 0.2-2.4 ms more a request for
#: them, of a request of hundreds.  From 1536 tokens a row dwarfs the ends:
#: ``t2 / t1`` is 1.53-1.72 (latent) and 2.07-2.49 (hybrid: two rows cost
#: MORE than two calls of one), and a closed loop of long answers frees
#: one slot at a time.  The cap lies between: 2048 token slots a call keeps
#: two rows up to a bucket of 1024 and gives one row past it.
#:
#: Under the cap a lone row is alone only if the loop does not wait, and a
#: FILLED call is the cheapest row there is (``t2 / 2``: 0.53-0.72 of
#: ``t1`` for GPT-2, 0.65-0.91 for the expert hybrid, whose ``t2 / t1`` of
#: 1.29-1.85 would otherwise make a lone row pay nearly two).  So a short
#: last chunk waits for the next slot to end where that is known to be
#: near (:func:`hold_pays`, the loop's admission block): the same cost a
#: row as a ``[1, b]`` program beside the ``[2, b]`` one, which would add
#: ``len(prompt_buckets)`` executables of 1.5-3 s each to a warm-up whose
#: ``setup_s`` is held to 10 %.  Measured, PR 38: rows a call 1.03 -> 2.00
#: (``longgen_closed``: a slot ends every 10 steps) and 1.56 -> 2.00
#: (``docs_closed``), half a slot standing empty meanwhile.
_ADMIT_ROWS = 2
_ADMIT_TOKEN_SLOTS = 2048


def admit_rows(bucket: int, batch_size: int) -> int:
    """Rows of the admission program traced for ``bucket``: as many as the
    call's token slots hold, at least one, at most ``_ADMIT_ROWS`` and the
    engine's slots."""
    return min(batch_size, _ADMIT_ROWS, max(1, _ADMIT_TOKEN_SLOTS // bucket))


def admit_chunks(buckets: Sequence[int], rows_of) -> List[List[int]]:
    """Pack admitted rows, given by their prompt buckets in admission
    order, into admission calls: a chunk takes consecutive rows, is padded
    to its widest row's bucket ``b`` and holds at most ``rows_of(b)`` rows.
    Returns each chunk's row indices.  A chunk is closed as soon as the
    next row would not fit beside the ones it holds, so a chunk in the
    middle may be short of ``rows_of(b)`` (a wide row after a narrow one)."""
    chunks: List[List[int]] = []
    widest = 0
    for j, b in enumerate(buckets):
        if chunks and len(chunks[-1]) < rows_of(max(widest, b)):
            chunks[-1].append(j)
            widest = max(widest, b)
        else:
            chunks.append([j])
            widest = b
    return chunks


def attn_blocks(pos_map, positions, page: int) -> Tuple[int, int]:
    """(walked, square) of one admission call, in (query tile, key block)
    pairs: what the ``paged_decode`` kernel walks, each tile of each row to
    its own bound, and what it walked when every tile went to its slot's
    bound; the kernel's own bound function on the arrays the program was
    given (``pos_map`` ``[R, C]``, ``positions`` ``[R, bucket]``)."""
    R, C = pos_map.shape
    pages = sweep_bound(key_visible(pos_map[:, None, :],
                                    positions[:, :, None], C), page)
    blocks = -(-pages.reshape(R, -1) // block_pages(page))  # [R, tiles]
    return (int(blocks.sum()),
            int(blocks.max(axis=1).sum()) * blocks.shape[1])


def hold_pays(steps: int, live: int, clock: Mapping[str, int]) -> bool:
    """Whether a short admission chunk should wait ``steps`` decode steps
    for the slot that will fill it: one slot of ``live + 1`` left empty for
    those steps costs less than the half call a filled chunk saves,
    ``steps x decode_step / live < admit_call / 2``, both times the loop's
    own means so far (``clock``: :attr:`LoopClock.sums`).  Never before
    both have a reading."""
    if not (clock["decode_steps"] and clock["admit_steps"]):
        return False
    step_us = clock["loop_us_decode_device"] / clock["decode_steps"]
    call_us = clock["loop_us_admit_device"] / clock["admit_steps"]
    return steps * step_us / live < call_us / 2


class KVHandoff(NamedTuple):
    """The prefill→decode hand-off payload (disaggregated serving).

    A prefill-role engine resolves a ``submit(..., handoff=True)`` future
    with one of these instead of a token array: the prompt's KV pages
    exported as a single host array plus the first generated token (the
    prefill already computed its logits, so the token rides along for
    free).  A decode-role engine accepts it via
    ``submit(prompt, ..., handoff=<KVHandoff>)`` and adopts the pages
    into its own pool (``PagePool.adopt`` + ``GPTModel.scatter_pages``)
    — decode resumes at position ``length`` exactly as if it had
    prefilled locally, so tokens are bit-identical to the co-located
    path.  ``done`` short-circuits the decode leg entirely (budget of 1,
    or EOS on the first token)."""

    prompt: np.ndarray    # [length] int32 prompt tokens
    first_token: int      # greedy token from the prompt's last logit
    kv: object            # exported pages in the donor pool's stored
    #                       order (GPTModel: [layers, 2, K, page,
    #                       heads*hd]), or a (pages, scales) pair when the
    #                       donor pool is quantized — both engines must
    #                       share the same `quantized` mode
    length: int           # resident KV covers positions 0..length-1
    done: bool            # True: no decode needed (budget 1 / EOS)


class GenerationEngine:
    """Continuous-batching greedy decoder over a paged causal LM.

    ``prompt_buckets`` — prompt lengths requests are padded up to (the
    admission compile set); ``batch_size`` — the one decode batch width
    (free slots run as inert ``-1``-position rows, occupancy is a metric,
    not a shape); ``cache_len`` — positions of KV a slot keeps (default
    ``model.max_position``; generation past it slides the window).

    ``kv_pages`` sizes the shared page pool (default ``batch_size *
    cache_len / kv_page_size``: every slot can fill its window; size it
    DOWN to hold more slots in the same HBM budget, the whole point of
    paging).  ``kv_page_size`` / ``speculative_k`` default to
    ``FLAGS_kv_page_size`` / ``FLAGS_speculative_k``.

    ``role`` — prefill/decode disaggregation: ``'prefill'`` engines serve
    ``submit(..., handoff=True)`` by exporting the prompt's KV pages as a
    :class:`KVHandoff` (plus the first token) without ever decoding;
    ``'decode'`` engines adopt such hand-offs and decode from them, so a
    prefill burst on one replica can never stall another replica's decode
    steps.  ``'any'`` (default) is the co-located engine — its compile
    set and behavior are untouched by the seam.

    ``continuous`` / ``paged`` select nothing: the dense ring and
    run-to-completion schedulers they once chose were removed in PR 29.
    ``None`` or ``True`` is accepted (the benchmark's runners still pass
    them), ``False`` raises.

    ``quantized`` — serve at reduced precision (``'int8'`` / ``'fp8'``):
    the bound weight trees are quantized once at construction
    (``slim.quantize_model_trees`` — the model object keeps its float
    weights), Linear hot paths dispatch to ``ops.quantized_matmul``, and
    the KV page pool stores int8/fp8 pages with per-token
    scale planes (quantize-on-write, dequantize-on-gather), so the same
    HBM budget holds ~4x (int8 vs f32) the resident pages.  The whole
    compile set is traced at low precision in :meth:`warmup` — the
    zero-post-warmup-recompile guarantee carries over unchanged — and
    :meth:`swap_weights` hot-swaps ``slim.export_quantized`` artifacts
    with zero recompiles.
    """

    @classmethod
    def from_tuned(cls, model, config: Dict, **overrides):
        """Build an engine from a measured-search serving config (a
        ``tuning.serving_space`` winner, in-process or replayed from the
        tuning cache).  Config keys map onto constructor arguments:
        ``buckets`` → ``prompt_buckets``, plus ``batch_size`` /
        ``max_queue_delay_ms`` / ``kv_page_size`` / ``speculative_k``
        verbatim; keyword ``overrides`` win over the config (e.g. a
        caller-pinned ``name``)."""
        kw = {}
        if "buckets" in config:
            kw["prompt_buckets"] = [int(b) for b in config["buckets"]]
        for k in ("batch_size", "kv_page_size", "speculative_k"):
            if config.get(k) is not None:
                kw[k] = int(config[k])
        if config.get("max_queue_delay_ms") is not None:
            kw["max_queue_delay_ms"] = float(config["max_queue_delay_ms"])
        if config.get("role"):
            kw["role"] = str(config["role"])
        if config.get("quantization") not in (None, "none"):
            kw["quantized"] = str(config["quantization"])
        kw.update(overrides)
        return cls(model, **kw)

    def __init__(self, model, *, prompt_buckets: Sequence[int],
                 batch_size: int = 4, cache_len: Optional[int] = None,
                 max_queue_delay_ms: float = 5.0, max_queue_depth: int = 256,
                 eos_token_id: Optional[int] = None,
                 circuit_breaker: bool = True,
                 retry_transient: bool = True,
                 continuous: Optional[bool] = None,
                 paged: Optional[bool] = None,
                 kv_pages: Optional[int] = None,
                 kv_page_size: Optional[int] = None,
                 speculative_k: Optional[int] = None,
                 role: str = "any",
                 quantized: Optional[str] = None,
                 tenancy=None,
                 name: Optional[str] = None):
        for kw, given in (("continuous", continuous), ("paged", paged)):
            if given is not None and not given:
                raise InvalidArgumentError(
                    f"{kw}=False: the dense ring and run-to-completion "
                    f"schedulers were removed in PR 29; the engine runs "
                    f"the paged continuous loop only")
        if name is None:
            _gen_counter[0] += 1
            name = f"generate#{_gen_counter[0]}"
        self.name = name
        self._model = model
        model.eval()
        if quantized is not None and quantized not in ("int8", "fp8"):
            raise InvalidArgumentError(
                f"quantized must be None, 'int8' or 'fp8', got "
                f"{quantized!r}")
        self._quantized = quantized
        if quantized is not None:
            # quantize once at construction, into the bound trees — the
            # model object keeps its float weights (training / other
            # engines untouched); the executables only ever see the
            # quantized leaves, so the compile set is quantized end to end
            from ..slim.quantization import quantize_model_trees
            self._params, self._buffers = quantize_model_trees(
                model, quantized)
        else:
            self._params = model.param_pytree()
            self._buffers = model.buffer_pytree()
        self._quant_active = self._tree_quant_active(self._params)
        self._buckets = sorted({int(b) for b in prompt_buckets})
        if not self._buckets or self._buckets[0] < 1:
            raise InvalidArgumentError(
                f"prompt_buckets must be positive lengths, got "
                f"{prompt_buckets!r}")
        self._batch = int(batch_size)
        # rows of each bucket's admission program (admit_rows)
        self._admit_rows = {b: admit_rows(b, self._batch)
                            for b in self._buckets}
        self._eos = eos_token_id
        self._C = int(cache_len or model.max_position)
        self._page = int(flag("kv_page_size")
                         if kv_page_size is None else kv_page_size)
        self._spec_k = max(int(flag("speculative_k")
                               if speculative_k is None else speculative_k),
                           0)
        if role not in ("any", "prefill", "decode"):
            raise InvalidArgumentError(
                f"role must be 'any', 'prefill' or 'decode', got {role!r}")
        self._role = role
        # recurrent state per slot beside the K/V pages (the model owns the
        # layout, see the module docstring).  What has no meaning for such
        # state is refused here, in the open
        self._slot_state = bool(getattr(model, "slot_state", False))
        if self._slot_state:
            for bad, why in (
                    (self._spec_k > 0, f"speculative_k={self._spec_k}: a "
                     f"rejected draft's write to the state cannot be rolled "
                     f"back"),
                    (role != "any", f"role={role!r}: the hand-off payload "
                     f"carries pages, not slot state"),
                    (quantized is not None, f"quantized={quantized!r}: its "
                     f"page pools have no scale planes")):
                if bad:
                    raise InvalidArgumentError(
                        f"{name}: {type(model).__name__} keeps recurrent "
                        f"state per slot, which rules out {why}")
        if self._buckets[-1] > self._C:
            raise InvalidArgumentError(
                f"largest prompt bucket ({self._buckets[-1]}) exceeds "
                f"cache_len ({self._C}) — paged admission cannot map it")
        self._kv_pages = (int(kv_pages) if kv_pages is not None
                          else self._batch * (self._C // self._page))
        self._pool = self._new_pool()  # validates page geometry
        # hand-off payloads carry whole prompt pages at ONE static
        # width: enough pages for the largest prompt bucket, padded
        # with -1 (the write-drop page) — so export/import each stay
        # a single executable regardless of prompt length
        self._Gh = -(-self._buckets[-1] // self._page)
        self._warm = False
        self._quant_fallback = 0
        self._traces: Dict[str, int] = {"decode": 0, "admit": 0, "cow": 0,
                                        "export": 0, "import": 0}
        # MoE models report per-expert routing health: the decode-step
        # bodies below collect [2, E] routed/dropped counts inside the
        # trace and a wrapper pops them off the jit output (_moe_tap) —
        # a 0-expert config builds the exact same executables as before
        self._moe_experts = int(getattr(model, "moe_experts", 0) or 0)
        self._moe_last = None  # the newest step's counts handle
        self._moe_layers = 0  # expert layers a decode step runs (set at trace)
        self._moe_pairs = 0   # (token, choice) pairs its routers make (ditto)
        self._moe_routed_cum = np.zeros(max(self._moe_experts, 1), np.int64)
        # batched multi-LoRA: capacity > 0 threads a per-slot adapter-id
        # column through every executable (warmup traces it with all -1,
        # so the compile set closes exactly as without LoRA; adapter hot
        # add/remove edits buffer leaves only)
        self._lora_cap = int(getattr(model, "lora_capacity", 0) or 0)
        self._adapters: Dict[int, str] = {}       # slot -> adapter name
        self._adapter_hits = np.zeros(max(self._lora_cap, 1), np.int64)
        self._tenancy_steps = 0  # post-warm decode steps (S607 denominator)
        self._tenancy = tenancy
        extra = (SLOT_COUNTERS + PAGED_COUNTERS + HANDOFF_COUNTERS
                 + LOOP_COUNTERS)
        if self._moe_experts:
            extra = extra + MOE_COUNTERS
        if self._quantized:
            extra = extra + QUANT_COUNTERS
        if self._lora_cap:
            extra = extra + LORA_COUNTERS
        if tenancy is not None:
            extra = extra + TENANCY_COUNTERS
        if self._slot_state:
            extra = extra + STATE_COUNTERS
        # the model's admissions attend by the page walk: count its blocks
        self._admit_walk = bool(getattr(model, "admit_page_walk", False))
        if self._admit_walk:
            extra = extra + ADMIT_WALK_COUNTERS
        self.metrics = ServingMetrics(name, extra_counters=extra)

        mdl, traces = model, self._traces
        # adapter-id args are threaded only when the model has LoRA
        # tables — a 0-capacity engine's executables take aids=None and
        # trace byte-identically to before
        lora_on = bool(self._lora_cap)
        # likewise the rows' slot numbers: handed to the model only when it
        # declares slot state
        state_on = self._slot_state

        # -- the executables (see serving/paging.py).  Admission prefills
        # STRAIGHT into the shared pool: each slot writes only its own
        # pages (padding rows scatter into the write-drop page), so live
        # slots' KV is untouched by construction.
        def padmit(params, buffers, ids, positions, pos_map, table, lens,
                   cache, aids=None, slots=None):
            def body(ids, positions, pos_map, table, lens, cache, aids,
                     slots):
                traces["admit"] += 1
                logits, cache = mdl.forward_paged(
                    ids, positions, pos_map, table, cache, gather_last=lens,
                    adapter_ids=aids if lora_on else None,
                    **({"slots": slots} if state_on else {}))
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
            return functional_call(mdl, params, ids, positions, pos_map,
                                   table, lens, cache, aids, slots,
                                   buffers=buffers, training=False, call=body)

        def pstep(params, buffers, packed, prev, cache):
            # the unified decode/verify step: T = 1 + speculative_k
            # columns (or the [B, 1] no-draft fast trace); rows with
            # position -1 (no draft / free slot) are inert.  All int32
            # per-step inputs ride ONE packed [B, 2T + C + G] transfer
            # (ids | positions | pos_map | table) — the loop
            # is dispatch-bound and one host transfer beats four.
            # `prev` is the [B, 1] token column of the step dispatched
            # before this one: a row whose first id is -1 (token ids are
            # not negative) consumes the token that step computed for
            # it, which the host has not read yet.
            # out[:, j] is the model's greedy next token after consuming
            # ids[:, :j+1] — column 0 is the plain decode token, columns
            # 1.. verify the drafts.
            def body(packed, prev, cache):
                traces["decode"] += 1
                C = self._C
                G = C // self._page
                # with LoRA the pack carries one trailing per-slot
                # adapter-id column: [B, 2T + C + G + 1]
                L = 1 if lora_on else 0
                Tp = (packed.shape[1] - C - G - L) // 2
                aids = packed[:, -1] if lora_on else None
                tab = packed[:, 2 * Tp + C:packed.shape[1] - L]
                ids = packed[:, :Tp]
                ids = jnp.where(
                    (ids < 0) & (jnp.arange(Tp, dtype=jnp.int32) == 0),
                    prev, ids)
                if self._moe_experts:
                    from ..moe import stats as moe_stats

                    with moe_stats.collect() as ms:
                        logits, cache = mdl.forward_paged(
                            ids, packed[:, Tp:2 * Tp],
                            packed[:, 2 * Tp:2 * Tp + C], tab, cache,
                            adapter_ids=aids)
                    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            cache, self._moe_sample(ms))
                logits, cache = mdl.forward_paged(
                    ids, packed[:, Tp:2 * Tp],
                    packed[:, 2 * Tp:2 * Tp + C], tab, cache,
                    adapter_ids=aids)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
            return functional_call(mdl, params, packed, prev, cache,
                                   buffers=buffers, training=False,
                                   call=body)

        def cow(cache, src, dst):
            traces["cow"] += 1
            return mdl.copy_pages(cache, src, dst)

        # hand-off seam (prefill/decode disaggregation): export gathers a
        # slot's prompt pages into one host-bound array, import scatters
        # such an array into freshly adopted pages.  Only traced in
        # warmup when `role` says this engine will actually use them —
        # a default-role engine's compile set is unchanged.
        def pexport(cache, idx):
            traces["export"] += 1
            return mdl.gather_pages(cache, idx)

        def pimport(cache, kv, dst):
            traces["import"] += 1
            return mdl.scatter_pages(cache, kv, dst)

        # the programs DONATE the pool they are handed: each returns
        # the pool it was given, updated in place, so a burst of calls in
        # flight holds one pool and not one output pool per call.  Every
        # call site threads the returned pool on and never reads its
        # argument again.
        self._padmit = jax.jit(padmit, donate_argnames=("cache",))
        self._pstep = pstep  # raw fn: the overlap-schedule search re-jits
        self._step = jax.jit(pstep, donate_argnames=("cache",))
        self._step_jit = self._step  # under the MoE tap, for lowering
        if self._moe_experts:
            self._step = self._moe_tap(self._step)
        self._cow = jax.jit(cow, donate_argnames=("cache",))
        self._export = jax.jit(pexport)
        self._import = jax.jit(pimport, donate_argnames=("cache",))
        self.breaker = (CircuitBreaker(name) if circuit_breaker else None)
        self._retry_transient = bool(retry_transient)
        # pull mode: no batcher worker — the decode loop is the consumer,
        # taking requests slot-by-slot (FCFS across buckets)
        self._batcher = MicroBatcher(
            self._route, None, pull=True,
            max_batch_size=batch_size,
            max_queue_delay_ms=max_queue_delay_ms,
            max_queue_depth=max_queue_depth,
            metrics=self.metrics,
            name=name)
        self._thread = threading.Thread(
            target=self._paged_loop, name=f"{name}-decode", daemon=True)
        self._thread.start()

    # -- routing -------------------------------------------------------------
    def _route(self, inputs: Sequence) -> int:
        n = len(np.asarray(inputs[0]).reshape(-1))
        for i, b in enumerate(self._buckets):
            if n <= b:
                return i
        self.metrics.incr("bucket_misses")
        self.metrics.publish()
        raise InvalidArgumentError(
            f"{self.name}: prompt length {n} exceeds the largest bucket "
            f"({self._buckets[-1]}) — add a bucket or truncate the prompt")

    @property
    def compile_count(self) -> int:
        """Traced executables so far: one per warmed prompt bucket (the
        ``[R(bucket), bucket]`` admission, :func:`admit_rows`) plus the
        shared decode step,
        the page-copy (CoW) op and, when speculation is on, the ``[B, 1]``
        no-draft fast trace of the decode/verify step; eviction is a pure
        host table edit with no executable at all."""
        return sum(self._traces.values())

    def warmup(self) -> int:
        """Trace the full compile set on dummy data so live traffic never
        pays compile latency.  Returns the (closed) compile count:
        ``len(prompt_buckets) + 2`` without speculation (the admission
        traced once per bucket at that bucket's rows, ``[R(bucket),
        bucket]`` by :func:`admit_rows`; the step; the page-copy op),
        ``len(prompt_buckets) + 3`` with it (the extra ``[B, 1]`` no-draft
        fast trace).
        Role-specialized engines add exactly one more: the page-export
        trace (``role='prefill'``) or the page-import trace
        (``role='decode'``); default-role engines trace neither.  On a
        global mesh of several devices the count is one higher: a step's
        outputs carry the mesh's sharding, so the host-built fresh pool of
        ``_init_pool`` is another abstract input and the step traces once
        more for it."""
        B = self._batch
        # warmup must mirror LIVE argument placement, not just shapes (a
        # placement mismatch is a silent XLA recompile the trace counter
        # can't see): ids/positions/pos_map/table always enter as host
        # transfers, the pool as a jit output — _init_pool covers the one
        # fresh-pool placement.  Admission is traced at each bucket's own
        # rows, [R(bucket), bucket], not [B, bucket].
        G = self._C // self._page
        prev, cache = self._init_pool()
        # sharded decode only: measured search over the collective
        # overlap schedule, BEFORE the production traces below (they
        # must be traced under the winning dials) and before
        # mark_warm (K701 stays silent; a warm restart replays the
        # winner from the tuning cache with zero searches)
        self._tune_overlap_schedule(cache)
        for sb in self._buckets:
            R = self._admit_rows[sb]
            pm0 = jnp.asarray(np.full((R, self._C), -1, np.int32))
            tb0 = jnp.asarray(np.full((R, G), -1, np.int32))
            ids = jnp.asarray(np.zeros((R, sb), np.int32))
            pos = jnp.asarray(np.broadcast_to(
                np.arange(sb, dtype=np.int32), (R, sb)))
            lens = jnp.asarray(np.full((R,), sb, np.int32))
            _, cache = self._padmit(
                self._params, self._buffers, ids, pos, pm0, tb0, lens,
                cache, self._aids_arg(np.full((R,), -1, np.int32)),
                self._slots_arg(np.full((R,), -1, np.int32)))
        T = 1 + self._spec_k
        _, cache = self._step(
            self._params, self._buffers,
            self._pack_step(
                np.zeros((B, T), np.int32),
                np.full((B, T), -1, np.int32)), prev, cache)
        if self._spec_k:
            # the no-draft fast path: a second [B, 1]-shaped trace of
            # the same step fn.  T=1 attention/logits are ~T x
            # cheaper, and the decode loop drops to this executable
            # whenever no live slot is drafting (proposer throttled
            # or sliding-window region)
            _, cache = self._step(
                self._params, self._buffers,
                self._pack_step(
                    np.zeros((B, 1), np.int32),
                    np.full((B, 1), -1, np.int32)), prev, cache)
            # seed the loop's wide-vs-fast cost model with one timed
            # (warm, blocked) call per trace; the loop refines both
            # online from its own iteration times
            timed = {}
            for key, Tt in (("wide", T), ("fast", 1)):
                pk = self._pack_step(np.zeros((B, Tt), np.int32),
                                     np.full((B, Tt), -1, np.int32))
                best = None
                for _ in range(2):
                    t0 = time.monotonic()
                    o, cache = self._step(self._params, self._buffers,
                                          pk, prev, cache)
                    np.asarray(o)
                    ms = (time.monotonic() - t0) * 1e3
                    best = ms if best is None else min(best, ms)
                timed[key] = best
            self._it_wide0, self._it_fast0 = timed["wide"], timed["fast"]
        neg = jnp.asarray(np.full((B,), -1, np.int32))
        cache = self._cow(cache, neg, neg)
        # role-gated hand-off traces: a prefill replica exports, a
        # decode replica imports — default-role engines trace NEITHER
        # (their compile set is byte-for-byte the pre-disaggregation
        # one).  Inert -1 page indices hit only the write-drop page.
        idx0 = np.full((self._Gh,), -1, np.int32)
        if self._role == "prefill":
            # device_get, not np.asarray: a quantized pool exports a
            # (pages, scales) pair, not a single array
            jax.device_get(self._export(cache, idx0))
        elif self._role == "decode":
            cache = self._import(cache, self._handoff_zero(), idx0)
        # this pool dies with the frame, but only once the device has run
        # what is queued on it: where every program came from the compile
        # cache the calls above return at once, and the loop's own fresh
        # pool (_init_pool at its first admission) would be allocated
        # BESIDE this one, one more copy of the pool at the memory's peak
        jax.block_until_ready(cache)
        self.metrics.set_counter("compiles", self.compile_count)
        from ..tuning import engine as _tuning
        _tuning.mark_warm()  # later measured searches are hot-path (K701)
        _retry_mod.mark_warm()  # later retry storms / flaps are F801
        # the warm-up steps' expert counts go with their handles (only a
        # harvested step's are folded): the dummy-data routing never lands
        # in the post-warm S606 window
        self._warm = True  # starvation after this point is S603 material
        self._emit_quant()
        return self.compile_count

    # -- sharded-decode overlap schedule -----------------------------------
    def _tune_overlap_schedule(self, cache):
        """Measured search over the collective overlap schedule
        (``tuning.plan_space.DECODE_DIALS``) on REAL decode steps.  Only
        meshes with a tensor/expert-parallel axis have collectives in
        the decode step, so everywhere else (single chip, CPU tests,
        the smoke gates) this is a no-op and the compile set is
        untouched.  Search traces are warmup throwaways: the trace
        counters are restored so ``compile_count`` keeps describing the
        production set."""
        from ..distributed.mesh import get_mesh

        mesh = get_mesh()
        if (mesh.shape.get("model", 1) == 1
                and mesh.shape.get("expert", 1) == 1):
            return
        from ..tuning import engine as _tengine
        from ..tuning import plan_space

        B, T = self._batch, 1 + self._spec_k
        pk = self._pack_step(np.zeros((B, T), np.int32),
                             np.full((B, T), -1, np.int32))
        snap = dict(self._traces)

        def measure(cfg):
            prev = plan_space.apply_decode_schedule(cfg)
            try:
                step = jax.jit(self._pstep)  # fresh trace under cfg dials
                return _tengine.measure_ms(
                    step, (self._params, self._buffers, pk,
                           self._no_prev(), cache),
                    repeats=2)
            finally:
                plan_space.apply_decode_schedule(prev)

        winner = plan_space.tune_decode_schedule(
            f"B{B}xT{T}xC{self._C}", measure=measure, mesh=mesh,
            details={"engine": self.name})
        self._traces.clear()
        self._traces.update(snap)
        plan_space.apply_decode_schedule(winner)
        self._overlap_schedule = winner

    def compiled_programs(self) -> Dict[str, str]:
        """The optimized HLO text of the executables as warm-up
        compiled them (``"step"``, ``"admit[<bucket>]"``): instruction
        names as a device trace prints them, each with the
        ``jax.named_scope`` path of the code it came from in its
        ``op_name`` metadata, which the trace itself does not carry.
        Lowers the same abstract calls again (``admit[sb]`` at ``[R(sb),
        sb]``; with the persistent compile cache on, a read); trace
        counters are left as they were."""
        B, G = self._batch, self._C // self._page

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        pool = jax.eval_shape(self._empty_pool)
        width = 2 * (1 + self._spec_k) + self._C + G + (
            1 if self._lora_cap else 0)
        snap = dict(self._traces)
        try:
            out = {"step": self._step_jit.lower(
                self._params, self._buffers, i32(B, width), i32(B, 1),
                pool).compile().as_text()}
            for sb in self._buckets:
                R = self._admit_rows[sb]
                out[f"admit[{sb}]"] = self._padmit.lower(
                    self._params, self._buffers, i32(R, sb), i32(R, sb),
                    i32(R, self._C), i32(R, G), i32(R), pool,
                    i32(R) if self._lora_cap else None,
                    i32(R) if self._slot_state else None
                ).compile().as_text()
        finally:
            self._traces.clear()
            self._traces.update(snap)
        return out

    # -- MoE routing-health tap --------------------------------------------
    def _moe_sample(self, ms):
        """``[3, E]`` int32 of one traced decode step: per-expert routed
        and dropped tokens summed over the expert layers, and in how many
        of those layers the expert got at least one token."""
        E = self._moe_experts
        self._moe_layers = len(ms.entries)  # static: read at trace time
        self._moe_pairs = ms.pairs          # likewise
        return jnp.concatenate([ms.counts(E), ms.touched(E)[None]])

    def expert_counts(self) -> np.ndarray:
        """Tokens routed to each expert by the decode steps harvested so
        far, summed over the expert layers (a copy)."""
        return self._moe_routed_cum.copy()

    def _moe_tap(self, fn):
        """Wrap a jitted decode-step callable whose body returns a
        trailing ``[3, E]`` per-expert (routed, dropped, layers touched)
        counts array (:meth:`_moe_sample`):
        pop it off the output so every call site keeps its original
        arity, and keep its handle for the caller that harvests the step
        (the loop puts it into the step's :class:`_Flight` and reads it
        with the step's tokens; :meth:`_moe_harvest` folds it)."""

        def tapped(*args, **kwargs):
            out = fn(*args, **kwargs)
            self._moe_last = out[-1]
            return out[:-1]

        return tapped

    def _moe_harvest(self, c: Optional[np.ndarray]):
        """Fold one harvested step's ``[3, E]`` counts sample, on the host,
        into the metrics: token totals, post-warm sampled/overflow step
        counters (rule S606's ratio) and the overflow-fraction /
        dead-expert gauges."""
        if c is None:
            return
        routed, dropped = int(c[0].sum()), int(c[1].sum())
        self._moe_routed_cum += c[0].astype(np.int64)
        m = self.metrics
        m.incr("moe_routed_tokens", routed)
        m.incr("moe_dropped_tokens", dropped)
        m.incr("moe_experts_touched", int(c[2].sum()))
        m.incr("moe_layer_steps", self._moe_layers)
        m.incr("moe_pairs_routed", self._moe_pairs)
        m.incr("moe_pairs_local", routed)
        if self._warm:
            m.incr("moe_sampled_steps_after_warm")
            if dropped > 0:
                m.incr("moe_overflow_steps_after_warm")
        total = routed + dropped
        m.set_gauge("moe_overflow_frac",
                    (dropped / total) if total else 0.0)
        if int(self._moe_routed_cum.sum()) > 0:
            m.set_gauge("moe_dead_experts",
                        int((self._moe_routed_cum == 0).sum()))

    # -- quantized serving ---------------------------------------------------
    @staticmethod
    def _tree_quant_active(params) -> bool:
        """True iff the bound parameter tree carries any int8/fp8 leaf —
        the executables' dtype-dispatched Linear forwards take the
        quantized leg exactly when this holds."""
        from ..slim.quantization import _is_quantized_dtype
        return any(_is_quantized_dtype(getattr(leaf, "dtype", None))
                   for leaf in jax.tree_util.tree_leaves(params))

    def _kv_qdtype(self):
        """Page-pool storage dtype for this engine's quantization mode
        (``None`` = the model's float dtype, the pre-quantization pool)."""
        if self._quantized == "int8":
            return jnp.int8
        if self._quantized == "fp8":
            return jnp.float8_e4m3fn
        return None

    def _handoff_zero(self):
        """An all-zeros hand-off payload matching what
        :meth:`GPTModel.gather_pages` exports from this engine's pool —
        a plain array for float pools, a ``(pages, scales)`` pair for
        quantized ones (warmup's import trace must see the live pytree
        structure or adoption would retrace on first use)."""
        return self._model.handoff_zero(self._Gh, self._page,
                                        self._kv_qdtype())

    def _note_quant_step(self):
        """Per-decode-step fallback bookkeeping for quantized engines: a
        post-warmup step dispatched while the bound tree is NOT quantized
        silently runs float math — count it (rule Q801's engine signal)."""
        if self._quantized and self._warm and not self._quant_active:
            self.metrics.incr("quant_fallback_steps_after_warm")
            self._quant_fallback += 1
            if self._quant_fallback == 1 or self._quant_fallback % 100 == 0:
                self._emit_quant()

    def _emit_quant(self):
        """Publish the engine-side quantization snapshot on the event bus
        (``("quant", <engine>)`` — latest-value semantics, consumed by
        ``analysis.RetraceMonitor.quant_stats`` / rule Q801)."""
        if not self._quantized:
            return
        from ..framework import trace_events
        if not trace_events.active():
            return
        trace_events.notify(("quant", self.name), {
            "kind": "engine", "mode": self._quantized,
            "quant_active": bool(self._quant_active),
            "fallback_steps_after_warm": int(self._quant_fallback)})

    def swap_weights(self, params_file: str) -> None:
        """Hot-swap the served weights from a ``.pdiparams`` side-file —
        e.g. a ``slim.export_quantized`` artifact — with ZERO recompiles:
        params/buffers are executable *arguments*, so any file whose tree
        structure and leaf shapes/dtypes match the currently bound trees
        slots straight into the next dispatch.  A mismatched file (wrong
        model, wrong quantization mode) is rejected before it can poison
        in-flight batches.  Same contract as ``Predictor.swap_weights``;
        ``Router.swap_weights_rolling`` drives this one replica at a
        time behind drained traffic."""
        from ..framework import serialization
        state = serialization.load(params_file)
        if not isinstance(state, dict) or "params" not in state:
            raise InvalidArgumentError(
                f"{params_file} is not a params side-file")
        tag = state.get("quantization")
        if tag is not None and tag != (self._quantized or "none"):
            raise InvalidArgumentError(
                f"{self.name}: {params_file} is a {tag!r}-quantized "
                f"artifact but this engine serves "
                f"{self._quantized or 'none'!r}")
        new_p = jax.tree_util.tree_map(np.asarray, state["params"])
        new_b = jax.tree_util.tree_map(np.asarray, state.get("buffers", {}))
        for part, old, new in (("params", self._params, new_p),
                               ("buffers", self._buffers, new_b)):
            old_s = jax.tree_util.tree_map(
                lambda a: (a.shape, np.dtype(a.dtype).name), old)
            new_s = jax.tree_util.tree_map(
                lambda a: (a.shape, np.dtype(a.dtype).name), new)
            if old_s != new_s:
                raise InvalidArgumentError(
                    f"swap_weights: {params_file} {part} do not match "
                    f"the served model (different tree structure or "
                    f"leaf shapes/dtypes)")
        self._params, self._buffers = new_p, new_b
        self._quant_active = self._tree_quant_active(new_p)
        self.metrics.publish({"weight_swap": 1})
        self._emit_quant()

    # -- scheduler -----------------------------------------------------------
    def _aids_arg(self, aidsv: np.ndarray):
        """Per-row adapter ids as a host transfer for the admission
        executables — ``None`` (not traced at all) when the model has no
        LoRA tables, so a 0-capacity engine's compile set is unchanged.
        The copy snapshots the host array against async dispatch."""
        if not self._lora_cap:
            return None
        return jnp.asarray(np.asarray(aidsv, np.int32).copy())

    def _slots_arg(self, slots: np.ndarray):
        """An admission chunk's slot numbers (``-1``: an inert row) as a
        host transfer — ``None`` (not traced at all) unless the model
        keeps state per slot, as :meth:`_aids_arg`."""
        if not self._slot_state:
            return None
        return jnp.asarray(np.asarray(slots, np.int32))

    def _expire_carry(self, carry: List[tuple]) -> List[tuple]:
        """Deadline sweep for requests held outside the batcher queue
        (breaker-deferred admissions, restart re-admissions)."""
        now = time.monotonic()
        keep: List[tuple] = []
        for r, n in carry:
            if r.deadline_t is not None and now > r.deadline_t:
                self.metrics.incr("expired")
                if not r.future.done():
                    r.future.set_exception(ExecutionTimeoutError(
                        f"{self.name}: deadline exceeded after "
                        f"{(now - r.enqueue_t) * 1e3:.1f}ms awaiting a "
                        f"decode slot"))
            else:
                keep.append((r, n))
        if len(keep) != len(carry):
            self.metrics.publish()
        return keep

    def _short_tail(self, take: List[tuple]) -> int:
        """How many rows the last admission call of ``take`` would carry
        where that is fewer than its program's ``R(bucket)``, else 0.  An
        iteration that adopts a hand-off runs serially and holds nothing."""
        if any(isinstance(r.meta[3], KVHandoff) for r, _ in take):
            return 0
        widths = [self._buckets[r.bucket] for r, _ in take]
        last = admit_chunks(widths, self._admit_rows.__getitem__)[-1]
        full = self._admit_rows[max(widths[j] for j in last)]
        return len(last) if len(last) < full else 0

    def _finish(self, s: dict, now: float):
        """Resolve one completed slot: future, latency/span/token metrics,
        breaker success."""
        r: Request = s["req"]
        queue_ms = (s["t0"] - r.enqueue_t) * 1e3
        execute_ms = (now - s["t0"]) * 1e3
        self.metrics.incr("completed")
        self.metrics.observe_latency_ms((now - r.enqueue_t) * 1e3)
        self.metrics.observe_span(queue_ms, execute_ms)
        self.metrics.observe_tokens(len(s["out"]), max(now - s["t0"], 1e-9))
        if profiler.profiling_active():
            args = {"span": r.span_id}
            profiler.record_span(f"{self.name}/queue", r.enqueue_t,
                                 queue_ms, cat="serving", args=args)
            profiler.record_span(f"{self.name}/decode", s["t0"],
                                 execute_ms, cat="serving", args=args)
        tenant = s.get("tenant")
        if tenant is not None:
            self.metrics.observe_tenant(tenant, (now - r.enqueue_t) * 1e3,
                                        len(s["out"]))
        tr = _tracing._active
        if tr is not None and r.trace is not None:
            # one span per slot residency, decode-step slices aggregated
            args = {"engine": self.name, "steps": len(s["out"])}
            if tenant is not None:
                args["tenant"] = tenant
            tr.record("slot/decode", r.trace, s["t0"], execute_ms,
                      kind="decode", args=args)
        if self.breaker is not None:
            self.breaker.record_success(0)
        if not r.future.done():
            res = s.get("result")  # hand-off producers resolve a KVHandoff
            r.future.set_result(res if res is not None
                                else np.asarray(s["out"], np.int32))

    def _harvest(self, flight: _Flight, host: np.ndarray, counts,
                 slots: list, pos: np.ndarray, free_slot, cnt) -> bool:
        """Harvest one decode step from its record, its tokens and its
        expert counts (``None`` without experts) on the host: per row,
        accept the longest draft prefix matching the model's own argmax,
        append the tokens to the request the row was computed for, charge
        its tenant, and resolve it where it ended (budget or EOS).  A
        function of the record, not of ``slots``: by the time a step is
        read the loop may have dispatched the next one, freed a row's slot
        (its budget ended by count at the dispatch) and seated another
        request there.  ``slots`` / ``pos`` are touched, and
        ``free_slot(i)`` called, only for a row whose record still holds
        its slot: an EOS, or any end with speculation on, where the count
        is not known ahead.  A row whose request an EOS ended one step ago
        was computed one token past that end: dropped and counted.
        Returns whether a request ended (the loop then publishes)."""
        pool, ten, eos, C = self._pool, self._tenancy, self._eos, self._C
        self._moe_harvest(counts)
        now = time.monotonic()
        n_evicted = 0
        evicted_traces: List = []
        for i, s, p, prop in flight.rows:
            if s.get("done"):
                cnt["decode_tokens_stale"] += 1
                continue
            a = 0
            while a < len(prop) and prop[a] == int(host[i, a]):
                a += 1
            # rejected drafts: their KV is stale — unmark it (overwritten
            # when the real token arrives)
            for j in range(a + 1, len(prop) + 1):
                pool.pos_map[i, (p + j) % C] = -1
            if prop:
                cnt["spec_drafted"] += len(prop)
                cnt["spec_accepted"] += a
                # trailing acceptance estimate feeding the wide-step
                # break-even decision
                s["spec_ema"] = (
                    0.5 * s.get("spec_ema", float(self._spec_k)) + 0.5 * a)
                if a == 0:
                    # exponential draft backoff (max 32 steps): proposer is
                    # cold on this sequence; any acceptance resets it
                    s["spec_fail"] = min(s.get("spec_fail", 0) + 1, 5)
                    s["spec_cool"] = 1 << s["spec_fail"]
                else:
                    s["spec_fail"] = 0
                # the dispatch counted the one token every row computes
                pos[i] += a
                s["sent"] += a
            done = False
            n_out = 0
            for j in range(a + 1):
                t = int(host[i, j])
                s["out"].append(t)
                s["hist"].append(t)
                n_out += 1
                if (len(s["out"]) >= s["budget"]
                        or (eos is not None and t == eos)):
                    done = True
                    break
            if ten is not None and n_out and s.get("tenant"):
                ten.charge(s["tenant"], n_out)
            if done:
                s["done"] = True
                if s["req"].trace is not None:
                    evicted_traces.append(s["req"].trace)
                if slots[i] is s:
                    free_slot(i)
                self._finish(s, now)
                n_evicted += 1
        if n_evicted:
            tr = _tracing._active
            if tr is not None and evicted_traces:
                ev_ms = (time.monotonic() - now) * 1e3
                for ctx in evicted_traces:
                    tr.record("slot/evict", ctx, now, ev_ms, kind="evict",
                              args={"engine": self.name})
            cnt["evicted"] += n_evicted
        return n_evicted > 0

    def _new_pool(self) -> PagePool:
        return PagePool(self._batch, self._kv_pages, self._page, self._C)

    def _init_pool(self):
        """Fresh empty page pool for the decode loop, pushed through one
        inert unified step (every row position ``-1``).  That step
        COMPUTES every pool array, so the returned handles carry the
        jit-output placement every steady-state executable was compiled
        against (host-built arrays would silently recompile
        placement-specialised variants of admission and step on first
        use), and the fresh-pool placement variant of the step gets built
        here, during warmup, not on first live use.  Returns ``(prev,
        pool)``: what the next step takes for the previous step's tokens
        is likewise a step's output where the loop runs ahead (no
        speculation), and the host's zeros where it never does."""
        B, T = self._batch, 1 + self._spec_k
        out, cache = self._step(
            self._params, self._buffers,
            self._pack_step(np.zeros((B, T), np.int32),
                            np.full((B, T), -1, np.int32)),
            self._no_prev(), self._empty_pool())
        return (self._no_prev() if self._spec_k else out), cache

    def _no_prev(self) -> np.ndarray:
        """The step's ``prev`` operand where no row reads it: ``[B, 1]``."""
        return np.zeros((self._batch, 1), np.int32)

    def _empty_pool(self):
        """The model's cache, zeroed: the page pools and, for a model with
        slot state, its ``batch_size + 1`` rows a layer."""
        return self._model.init_paged_cache(
            self._kv_pages, self._page, dtype=self._kv_qdtype(),
            **({"slots": self._batch} if self._slot_state else {}))

    def _pack_step(self, ids: np.ndarray, positions: np.ndarray,
                   pos_map: Optional[np.ndarray] = None,
                   table: Optional[np.ndarray] = None,
                   aids: Optional[np.ndarray] = None) -> np.ndarray:
        """One ``[B, 2T + C + G]`` int32 row per slot carrying every
        per-step host input of the unified step (``ids | positions |
        pos_map | table``), plus one trailing per-slot adapter-id column
        when the model has LoRA tables.  ``None`` pos_map/table/aids
        mean all ``-1`` (inert warmup shapes / no adapter).  The
        concatenate also snapshots the host-owned pool state, so async
        dispatch never races a later table edit."""
        B, C = self._batch, self._C
        G = C // self._page
        if pos_map is None:
            pos_map = np.full((B, C), -1, np.int32)
        if table is None:
            table = np.full((B, G), -1, np.int32)
        cols = [np.asarray(ids, np.int32), np.asarray(positions, np.int32),
                np.asarray(pos_map, np.int32), np.asarray(table, np.int32)]
        if self._lora_cap:
            if aids is None:
                aids = np.full((B,), -1, np.int32)
            cols.append(np.asarray(aids, np.int32).reshape(B, 1))
        return np.concatenate(cols, axis=1)

    @staticmethod
    def _ngram_drafts(hist: List[int], k: int, n: int = 2) -> List[int]:
        """Prompt-lookup proposer (the n-gram degenerate case of
        speculative decoding — no draft model): find the most recent
        earlier occurrence of the history's final ``n``-gram and propose
        the ``k`` tokens that followed it.  Pure host work; free when it
        misses, up to ``k`` extra tokens per verify step when text
        repeats (templated / structured output, copied spans)."""
        if k <= 0 or len(hist) < n + 1:
            return []
        tail = hist[-n:]
        for s in range(len(hist) - n - 1, -1, -1):
            if hist[s:s + n] == tail:
                return [int(t) for t in hist[s + n: s + n + k]]
        return []

    @staticmethod
    def _unpack_paged(r: Request):
        """A request's meta: ``(budget, prefix_key, prefix_len,
        handoff, tenant, adapter_id)`` (see :meth:`submit`) — ``handoff``
        is ``None`` for a plain request, ``True`` to produce a
        :class:`KVHandoff`, or a :class:`KVHandoff` instance to adopt."""
        budget, key, plen, hand, tenant, aid = r.meta
        prompt = np.asarray(r.inputs[0], np.int32).reshape(-1)
        return (prompt, key, min(int(plen), len(prompt)), int(budget), hand,
                tenant, int(aid))

    @staticmethod
    def _tenant_of(r: Request) -> Optional[str]:
        """Tenant name off a request's meta (the 6-tuple of
        :meth:`submit`), ``None`` for untagged requests."""
        return r.meta[4]

    # -- multi-LoRA adapter table --------------------------------------------
    def install_adapter(self, slot: int, adapter) -> None:
        """Hot-add ``adapter`` into table slot ``slot`` — a pure host-side
        edit of the stacked A/B/scale buffers through the same
        buffer-tree swap as ``swap_weights``: shapes and dtypes are
        preserved, so every warmed executable keeps its signature and the
        compile set stays closed.  Requests already decoding with this
        slot id pick up the new weights on their next step."""
        from ..lora.batched import write_adapter

        if not self._lora_cap:
            raise InvalidArgumentError(
                f"{self.name}: model has no LoRA tables "
                f"(GPTConfig.lora_capacity == 0)")
        self._buffers = write_adapter(self._buffers, slot, adapter)
        self._adapters[int(slot)] = adapter.name
        self.metrics.incr("adapter_installs")

    def remove_adapter(self, slot: int) -> None:
        """Hot-remove the adapter in table slot ``slot`` (zero its A/B
        rows) — slot id ``slot`` becomes a no-op delta, bitwise the base
        model, without any recompilation."""
        from ..lora.batched import clear_slot

        if not self._lora_cap:
            raise InvalidArgumentError(
                f"{self.name}: model has no LoRA tables "
                f"(GPTConfig.lora_capacity == 0)")
        self._buffers = clear_slot(self._buffers, slot)
        self._adapters.pop(int(slot), None)
        # a decode step racing the removal can at worst lose one hit
        # increment on a slot that is being cleared anyway; the counter
        # only feeds the S607 dead-adapter heuristic, never control flow
        # lock-order: benign stats race, slot is being cleared
        self._adapter_hits[int(slot)] = 0
        self.metrics.incr("adapter_removals")

    @property
    def adapters(self) -> Dict[int, str]:
        """Installed adapter names by table slot (host-side view)."""
        return dict(self._adapters)

    def _emit_tenancy(self, carry: List[tuple]) -> None:
        """Publish the tenancy/adapter health snapshot on the
        ``("tenancy", <engine>)`` bus channel — rule S607's signal
        (sustained in-budget starvation; dead adapter table entries).
        Same latest-value semantics as the ``("serving", ·)`` family."""
        from ..framework import trace_events

        if not trace_events.active():
            return
        if self._tenancy is None and not self._lora_cap:
            return
        snap: dict = {
            "decode_steps_after_warm": int(self._tenancy_steps),
            "adapters_installed": len(self._adapters),
            "adapters_dead": sum(
                1 for sl in self._adapters
                if self._adapter_hits[sl] == 0),
        }
        if self._tenancy is not None:
            queued: Dict[str, int] = {}
            for r, _ in carry:
                tn = self._tenant_of(r)
                if tn is not None:
                    queued[tn] = queued.get(tn, 0) + 1
            ts = self._tenancy.snapshot()
            for tn, st in ts.items():
                st["queued"] = queued.get(tn, 0)
            snap["tenants"] = ts
        trace_events.notify(("tenancy", self.name), snap)

    def _paged_loop(self):
        """The persistent decode loop — sole owner of the device
        pool AND the host page accounting (``PagePool``).

        Per iteration: admit queued requests FCFS while the free list
        covers their page demand (prefill lands straight in the pool —
        shared-prefix pages come mapped, not recomputed), then one
        unified decode/verify step for all live slots with n-gram drafts
        in the extra columns, then the harvest (:meth:`_harvest`) of the
        oldest step whose tokens are unread — accept the
        longest draft prefix matching the model's own argmax, invalidate
        the rest via the position map.  CoW page copies collected from
        admission / first-divergent-write are dispatched before the step
        they protect.  Pool exhaustion mid-decode preempts the NEWEST
        slot (its request requeues and regenerates bit-identically);
        eviction is a pure host table edit.

        One step in flight.  Without speculation the step read after a
        dispatch is the one BEFORE it: positions, pages and a budget's end
        follow from counts, a row's last token stays on the device (id
        ``-1``: taken from ``prev``, the previous step's output), and the
        host reads step n while the device runs n+1.  ``flights`` holds the
        steps dispatched and not yet harvested, oldest first: one between
        iterations, two between a dispatch and the read that follows it.
        The device runs its programs in dispatch order and each threads
        the one donated pool, so pages freed at a dispatch can be mapped
        by the admission dispatched next.  Whatever needs the tokens on
        the host calls ``drain`` first and then runs as it always did:
        speculation (drafts come from the tokens: every step is read
        before the next is packed), an admission (once its calls are
        dispatched, so the read hides behind them), a preemption, a tenant
        over budget, a hand-off, ``close()``, new weights, nothing live.
        A failed device call surfaces where its tokens are read, one
        iteration late: the restart requeues the rows in flight too.

        Steps where no slot drafts run a ``[B, 1]`` fast trace of the
        same step fn instead of the wide ``[B, 1+k]`` verify trace, and
        the wide path is gated by a cost model: the loop measures its own
        fast/wide iteration times and speculates only when the predicted
        accepted tokens (per-slot trailing acceptance) clear break-even —
        on accelerators the two traces cost about the same so the bar is
        ~0; on a compute-bound host the loop turns selective by itself.

        On the record: a :class:`LoopClock` cuts every iteration into the
        ``serve/*`` phases of ``metrics.LOOP_PHASES`` (trace spans on this
        thread and ``loop_us_*`` counters), ``ph.counts`` gathers the
        iteration's work counts for the one ``metrics.add`` of its
        ``flush``, each counted once its dispatch has returned (what comes
        from a step's tokens, once it is harvested).
        """
        q = self._batcher
        B, C, page = self._batch, self._C, self._page
        G = C // page
        k_max, eos = self._spec_k, self._eos
        T = 1 + k_max
        max_restarts = (max(int(flag("transient_max_retries")) - 1, 0)
                        if self._retry_transient else 0)
        slots: List[Optional[dict]] = [None] * B
        pos = np.full((B,), -1, np.int64)  # next write position (-1 = free)
        aidsv = np.full((B,), -1, np.int32)  # per-slot adapter ids
        ten = self._tenancy
        pool = self._pool
        cache = None                       # device handles: the page pool
        prev = None                        # ... and the last step's tokens
        flights: List[_Flight] = []        # steps dispatched, not yet read
        ahead = k_max == 0                 # drafts need the tokens: serial
        swapped = self._params             # the weights the last step took
        carry: List[tuple] = []            # (Request, n_restarts) to re-admit
        held: List[Request] = []           # ... the head of it held last time
        last_pub = 0.0
        # self-measured iteration costs (ms) of the [B, 1] fast trace vs
        # the wide [B, T] verify trace — seeded by warmup's timed calls
        # when available (optimistic before that: no bar until both are
        # known) and refined online from real iteration times
        it_fast: Optional[float] = getattr(self, "_it_fast0", None)
        it_wide: Optional[float] = getattr(self, "_it_wide0", None)

        def dispatch_cow(pairs):
            # chunk (src, dst, owner_slot) copies through the fixed-[B]
            # CoW op; -1 entries land in the write-drop page
            nonlocal cache
            while pairs:
                chunk, pairs = pairs[:B], pairs[B:]
                src = np.full((B,), -1, np.int32)
                dst = np.full((B,), -1, np.int32)
                for j, (s_, d_, _own) in enumerate(chunk):
                    src[j], dst[j] = s_, d_
                cache = self._cow(cache, jnp.asarray(src), jnp.asarray(dst))

        def preempt_newest() -> Optional[int]:
            victims = [v for v in range(B) if slots[v] is not None]
            if not victims:
                return None
            v = max(victims, key=lambda i: (slots[i]["t0"], i))
            vs = slots[v]
            free_slot(v)
            # regeneration from the prompt is deterministic greedy —
            # the requeued request produces bit-identical tokens
            carry.insert(0, (vs["req"], vs["restarts"]))
            self.metrics.incr("preempted")
            return v

        def poll(n, wait_s):
            # a blocking poll (nothing live, nothing waiting) is idle time
            if wait_s > 0:
                ph.to("wait")
            got = [(r, 0) for r in q.poll(n, wait_s=wait_s)]
            if wait_s > 0:
                ph.to("sched")
            return got

        def free_slot(i):
            pool.release(i)
            slots[i] = None
            pos[i] = -1
            aidsv[i] = -1

        def read_oldest() -> int:
            # the blocking wait belongs to the open decode.device phase,
            # the harvest is host work; a record leaves `flights` only once
            # harvested, so a failed read requeues its rows
            nonlocal force_pub
            host, counts = jax.device_get((flights[0].out, flights[0].moe))
            dt = ph.to("harvest")
            force_pub |= self._harvest(flights[0], host, counts, slots, pos,
                                       free_slot, cnt)
            del flights[0]
            return dt

        def drain(back):
            # read every step in flight, then return to phase `back`
            if flights:
                ph.to("decode.device", engine=self.name)
                while flights:
                    read_oldest()
                ph.to(back)

        ph = LoopClock(self.metrics)
        cnt = ph.counts
        try:
            while True:
                try:
                    ph.to("sched")
                    force_pub = False
                    closing = q.closing
                    if closing or all(s is None for s in slots):
                        # shutting down, or every slot ended by count at
                        # the last dispatch: nothing to run ahead of
                        drain("sched")
                    if closing and not q.drain_on_close:
                        err = UnavailableError(
                            f"{self.name}: dropped at shutdown "
                            f"(drain=False)")
                        for i in range(B):
                            s = slots[i]
                            if s is not None and not s["req"].future.done():
                                s["req"].future.set_exception(err)
                            slots[i] = None
                        for r, _ in carry:
                            if not r.future.done():
                                r.future.set_exception(err)
                        q.poll(B, 0.0)  # fails everything still queued
                        return
                    live = [i for i in range(B) if slots[i] is not None]
                    free = [i for i in range(B) if slots[i] is None]
                    if (closing and not live and not carry
                            and q.queue_depth == 0):
                        return

                    # ---- tenant budget enforcement: an over-budget
                    # tenant's live slots preempt through the same
                    # deterministic release path as pool exhaustion —
                    # the requeued requests regenerate bit-identically
                    # once the tenant is back in budget
                    if ten is not None and live:
                        over = ten.over_budget()
                        if flights and any(slots[i].get("tenant") in over
                                           for i in live):
                            # charges trail the dispatch by one step
                            drain("sched")
                            over = ten.over_budget()
                        if over:
                            npre = 0
                            for i in live:
                                s = slots[i]
                                if s is None or s.get("tenant") not in over:
                                    continue
                                carry.insert(0, (s["req"], s["restarts"]))
                                ten.note_preempted(s.get("tenant"))
                                free_slot(i)
                                npre += 1
                            if npre:
                                self.metrics.incr("preempted", npre)
                                self.metrics.incr("tenant_preempted", npre)
                            live = [i for i in range(B)
                                    if slots[i] is not None]
                            free = [i for i in range(B)
                                    if slots[i] is None]

                    # ---- admission: FCFS (or weighted-fair under a
                    # TenantScheduler), gated by the breaker AND the
                    # page budget; neither sheds — deferred requests wait
                    # in carry under the deadline sweep
                    take: List[tuple] = []
                    blocked_wait = False
                    if carry:
                        carry = self._expire_carry(carry)
                    if free:
                        if ten is None:
                            cand = carry[:len(free)]
                            carry = carry[len(cand):]
                            want = len(free) - len(cand)
                            if want > 0:
                                wait = (0.05 if not live and not cand
                                        else 0.0)
                                blocked_wait = wait > 0
                                cand += poll(want, wait)
                        else:
                            # weighted-fair admission considers ALL waiting
                            # requests (carry + a widened queue window) so
                            # the stride order can pass a FIFO-monopolizing
                            # tenant; over-budget tenants defer back to
                            # carry with per-tenant arrival order intact
                            cand = carry
                            carry = []
                            # the widened window bounds ADMISSIBLE work:
                            # a throttled tenant's deferred backlog must
                            # not suppress polling new arrivals (victims
                            # would sit in the queue behind it)
                            n_adm = sum(
                                1 for rc in cand
                                if not ten.is_throttled(
                                    self._tenant_of(rc[0])))
                            want = max(2 * B - n_adm, 0)
                            wait = (0.05 if not live and not cand else 0.0)
                            blocked_wait = wait > 0
                            if want > 0:
                                cand += poll(want, wait)
                            cand, deferred = ten.schedule(
                                cand,
                                tenant_of=lambda rc: self._tenant_of(rc[0]),
                                cost_of=lambda rc: max(int(rc[0].meta[0]),
                                                       1))
                            carry = deferred + carry
                        if (cand and self.breaker is not None
                                and not self.breaker.allow(0)):
                            carry = cand + carry
                            cand = []
                            q.sweep()
                        budget_pages = pool.free_pages
                        for ci, (r, nre) in enumerate(cand):
                            if len(take) >= len(free):
                                # widened tenancy window: surplus ordered
                                # candidates wait their turn in carry
                                carry = cand[ci:] + carry
                                break
                            prompt, key, _, _, hand, _, _ = \
                                self._unpack_paged(r)
                            if isinstance(hand, KVHandoff):
                                # adoption maps fresh private pages only
                                need = -(-hand.length // page)
                            else:
                                need = pool.pages_needed(prompt, key)
                            if need > budget_pages and ci == 0 and not live:
                                # nothing left to preempt: reclaim every
                                # registered prefix before giving up
                                pool.drop_all_prefixes()
                                budget_pages = pool.free_pages
                                if not isinstance(hand, KVHandoff):
                                    need = pool.pages_needed(prompt, key)
                            if need > budget_pages:
                                # head-of-line blocks: keep FCFS order
                                carry = cand[ci:] + carry
                                break
                            take.append((r, nre))
                            budget_pages -= need
                    # ---- a short last chunk waits for the row that is
                    # known to come: where it is short for want of a SLOT
                    # (every free slot taken, a request still waiting) and
                    # the next live slot ends, by count, inside the
                    # break-even (hold_pays), its rows go back to the head
                    # of carry before anything was done for them, and the
                    # freed slot's row fills the call.  Judged afresh every
                    # iteration, so the rows go as soon as a slot joins
                    # them, nobody waits any more or the bound has passed
                    back: List[tuple] = []
                    if (take and live and not closing
                            and len(take) == len(free)
                            and (carry or q.queue_depth > 0)):
                        tail = self._short_tail(take)
                        if tail and hold_pays(
                                min(slots[i]["budget"] - slots[i]["sent"]
                                    for i in live), len(live), ph.sums):
                            back, take = take[-tail:], take[:-tail]
                            carry = back + carry
                    if back or held:
                        cnt["admit_rows_held"] += sum(
                            1 for r, _ in back
                            if not any(r is h for h in held))
                        held = [r for r, _ in back]
                    n_adopted = 0
                    if take:
                        ph.to("admit.host", engine=self.name, rows=len(take))
                        if any(r.meta[3] is not None for r, _ in take):
                            drain("admit.host")  # a hand-off: serial
                        if cache is None:
                            prev, cache = self._init_pool()
                        now = time.monotonic()
                        # hand-off adoptions first: no prefill compute at
                        # all — map fresh pages, scatter the exported KV
                        # in, seed the slot with the donor's first token;
                        # decode resumes at position `length` exactly as
                        # if this engine had prefilled the prompt itself
                        pre: List[tuple] = []
                        n_adevicted = 0
                        for (r, nre), i in zip(take, free):
                            prompt, _, _, budget, hand, tenant, aid = \
                                self._unpack_paged(r)
                            if not isinstance(hand, KVHandoff):
                                pre.append(((r, nre), i))
                                continue
                            pool.adopt(i, hand.length)
                            npg = -(-hand.length // page)
                            dst = np.full((self._Gh,), -1, np.int32)
                            dst[:npg] = pool.table[i, :npg]
                            # quantized pools hand off (pages, scales)
                            # pairs; float pools a single array
                            kvp = (tuple(hand.kv)
                                   if isinstance(hand.kv, (tuple, list))
                                   else np.asarray(hand.kv))
                            with profiler.RecordEvent(
                                    f"{self.name}/adopt"):
                                cache = self._import(cache, kvp, dst)
                            t = int(hand.first_token)
                            slots[i] = {"req": r, "budget": budget,
                                        "out": [t], "sent": 1, "t0": now,
                                        "restarts": nre,
                                        "tenant": tenant,
                                        "hist": [int(x) for x in prompt]
                                        + [t]}
                            pos[i] = hand.length
                            aidsv[i] = aid
                            if ten is not None and tenant is not None:
                                ten.charge(tenant, 1)
                            n_adopted += 1
                            self.metrics.incr("handoffs_in")
                            tr = _tracing._active
                            if tr is not None and r.trace is not None:
                                tr.record(
                                    "slot/admit", r.trace, now,
                                    (time.monotonic() - now) * 1e3,
                                    kind="adopt",
                                    args={"engine": self.name, "slot": i})
                            if (hand.done or budget <= 1
                                    or (eos is not None and t == eos)):
                                self._finish(slots[i], time.monotonic())
                                free_slot(i)
                                n_adevicted += 1
                        cnt["admitted"] += n_adopted
                        cnt["evicted"] += n_adevicted
                    if take and pre:
                        cow_pairs: List[tuple] = []
                        to_register: List[tuple] = []
                        admitted: List[tuple] = []
                        for (r, nre), i in pre:
                            prompt, key, plen, budget, hand, tenant, aid = \
                                self._unpack_paged(r)
                            pairs, shared = pool.admit(i, prompt, key)
                            cow_pairs += [(s_, d_, i) for s_, d_ in pairs]
                            pos[i] = len(prompt)
                            aidsv[i] = aid
                            # "sent": the tokens computed or in flight;
                            # ahead of len("out") while a step is unread
                            slots[i] = {"req": r, "budget": budget,
                                        "out": [], "sent": 1, "t0": now,
                                        "restarts": nre,
                                        "tenant": tenant,
                                        "handoff": hand is True,
                                        "hist": [int(t) for t in prompt]}
                            admitted.append((r, i, shared, prompt))
                            if key is not None and plen > 0:
                                # registered AFTER the last chunk lands, so
                                # same-batch siblings never map pages whose
                                # boundary CoW would copy data not yet
                                # written
                                to_register.append((key, i, prompt[:plen]))
                        # the admitted rows, packed in admission order
                        # into chunks of at most R(bucket) rows
                        # (admit_chunks): a chunk's program runs over
                        # its own rows' page-table and position-map rows
                        # and nothing else, padded to its widest row's
                        # bucket; rows a chunk does not fill are inert
                        # (position -1, table -1: they write to the drop
                        # page, as warm-up's rows do)
                        chunks: List[tuple] = []
                        widths = [self._buckets[r.bucket]
                                  for r, _, _, _ in admitted]
                        for rows in admit_chunks(
                                widths, self._admit_rows.__getitem__):
                            part = [admitted[j] for j in rows]
                            Sb = max(widths[j] for j in rows)
                            R = self._admit_rows[Sb]
                            ids = np.zeros((R, Sb), np.int32)
                            pp = np.full((R, Sb), -1, np.int32)
                            lens = np.ones((R,), np.int32)
                            sl = [i for _, i, _, _ in part]
                            pm = np.full((R, C), -1, np.int32)
                            tb = np.full((R, G), -1, np.int32)
                            ra = np.full((R,), -1, np.int32)
                            rs = np.full((R,), -1, np.int32)
                            pm[:len(sl)] = pool.pos_map[sl]
                            tb[:len(sl)] = pool.table[sl]
                            ra[:len(sl)] = aidsv[sl]
                            rs[:len(sl)] = sl
                            for j, (_, _, shared, prompt) in enumerate(part):
                                L = len(prompt)
                                ids[j, :L - shared] = prompt[shared:]
                                pp[j, :L - shared] = np.arange(shared, L)
                                lens[j] = L - shared
                            chunks.append((ids, pp, pm, tb, lens, ra, rs,
                                           len(part)))
                        dispatch_cow(cow_pairs)
                        fault_point("serving.decode")
                        ph.to("admit.device", engine=self.name,
                              bucket=max(c[0].shape[1] for c in chunks),
                              rows=len(admitted))
                        # back to back, each threading the pool to the
                        # next; the host waits once, for the last
                        firsts = []
                        for ids, pp, pm, tb, lens, ra, rs, _ in chunks:
                            first, cache = self._padmit(
                                self._params, self._buffers,
                                jnp.asarray(ids), jnp.asarray(pp),
                                jnp.asarray(pm), jnp.asarray(tb),
                                jnp.asarray(lens), cache,
                                self._aids_arg(ra), self._slots_arg(rs))
                            firsts.append(first)
                        # the step in flight ran ahead of these calls: read
                        # and harvest it while the device runs them
                        drain("admit.device")
                        if self._admit_walk:
                            # still while the device runs them: the blocks
                            # the kernel walks in these calls, by the rule
                            for _, pp, pm, *_ in chunks:
                                walked, square = attn_blocks(pm, pp, page)
                                cnt["admit_attn_blocks_walked"] += walked
                                cnt["admit_attn_blocks_square"] += square
                        # serial harvest: a chunk's first n rows are the
                        # next n of `admitted`, the rest of its rows inert
                        host_first = np.concatenate([
                            f[:c[-1]] for f, c in zip(
                                jax.device_get(firsts), chunks)])
                        ph.to("admit.host", engine=self.name)
                        tr = _tracing._active
                        if tr is not None:
                            adm_ms = (time.monotonic() - now) * 1e3
                            row_bucket = [c[0].shape[1] for c in chunks
                                          for _ in range(c[-1])]
                            for j, (r, i, _, _) in enumerate(admitted):
                                if r.trace is None:
                                    continue
                                tr.record("batcher/queue", r.trace,
                                          r.enqueue_t,
                                          (now - r.enqueue_t) * 1e3,
                                          kind="queue",
                                          args={"engine": self.name,
                                                "bucket": r.bucket})
                                tr.record("slot/admit", r.trace, now,
                                          adm_ms, kind="prefill",
                                          args={"engine": self.name,
                                                "slot": i, "bucket":
                                                row_bucket[j]})
                        for key, i, toks in to_register:
                            pool.register_prefix(key, i, toks)
                        now = time.monotonic()
                        n_evicted = 0
                        for j, (r, i, shared, prompt) in enumerate(admitted):
                            s = slots[i]
                            t = int(host_first[j])
                            cnt["admit_tokens"] += len(prompt) - shared
                            cnt["queue_wait_us"] += int(
                                (s["t0"] - r.enqueue_t) * 1e6)
                            cnt["ttft_us"] += int((now - r.enqueue_t) * 1e6)
                            if s.get("handoff"):
                                # produce: export the prompt's pages while
                                # they are still mapped and resolve with
                                # the KVHandoff (the first token rides
                                # along) — prefill replicas never decode,
                                # so the slot turns over immediately
                                L = len(s["hist"])
                                npg = -(-L // page)
                                idx = np.full((self._Gh,), -1, np.int32)
                                idx[:npg] = pool.table[i, :npg]
                                with profiler.RecordEvent(
                                        f"{self.name}/export"):
                                    # tuple-shaped for quantized pools
                                    kvh = jax.device_get(
                                        self._export(cache, idx))
                                s["out"].append(t)
                                if ten is not None and s.get("tenant"):
                                    ten.charge(s["tenant"], 1)
                                s["result"] = KVHandoff(
                                    np.asarray(s["hist"][:L], np.int32),
                                    t, kvh, L,
                                    bool(s["budget"] <= 1
                                         or (eos is not None
                                             and t == eos)))
                                self.metrics.incr("handoffs_out")
                                self._finish(s, now)
                                free_slot(i)
                                n_evicted += 1
                                continue
                            s["out"].append(t)
                            s["hist"].append(t)
                            if ten is not None and s.get("tenant"):
                                ten.charge(s["tenant"], 1)
                            if (len(s["out"]) >= s["budget"]
                                    or (eos is not None and t == eos)):
                                self._finish(s, now)
                                free_slot(i)
                                n_evicted += 1
                        cnt.update(admitted=len(admitted), batches=1,
                                   evicted=n_evicted,
                                   admit_steps=len(chunks),
                                   admit_rows=len(admitted),
                                   admit_row_slots=sum(
                                       c[0].shape[0] for c in chunks),
                                   admit_token_slots=sum(
                                       c[0].size for c in chunks))
                        if self._slot_state:
                            cnt.update(
                                state_slots_reset=len(admitted),
                                gdn_prefill_tokens=sum(
                                    len(p) for _, _, _, p in admitted),
                                gdn_prefill_token_slots=sum(
                                    c[0].size for c in chunks))
                    if take:
                        live = [i for i in range(B) if slots[i] is not None]
                        if ten is not None:
                            for r, _ in take:
                                ten.note_admitted(self._tenant_of(r))
                    elif (free and not closing and not back
                          and (carry or q.queue_depth > 0)):
                        if (ten is not None and carry
                                and q.queue_depth == 0
                                and all(ten.is_throttled(
                                    self._tenant_of(r))
                                    for r, _ in carry)):
                            # every waiting request belongs to an
                            # over-budget tenant: that is throttling by
                            # design, not S603 starvation
                            self.metrics.incr("tenant_throttled_steps")
                        else:
                            # free slots + waiting requests + nothing
                            # admitted: S603 starvation — and, with the
                            # page gauges on the same snapshot, S604's
                            # page-leak signal
                            self.metrics.incr("starved_steps")
                            if self._warm:
                                self.metrics.incr(
                                    "starved_steps_after_warm")
                    if (ten is not None and self._warm and carry
                            and not back
                            and any(slots[i] is None for i in range(B))):
                        # per-tenant starvation signal for S607: an
                        # IN-budget tenant still waiting while a slot
                        # sits IDLE after this step's admission pass
                        # (`free` is stale here — admission above just
                        # filled slots; a full batch is contention, not
                        # an isolation failure)
                        seen_tn = set()
                        for r, _ in carry:
                            tn = self._tenant_of(r)
                            if (tn is None or tn in seen_tn
                                    or ten.is_throttled(tn)):
                                continue
                            seen_tn.add(tn)
                            ten.note_starved(tn)
                        if seen_tn:
                            self.metrics.incr(
                                "tenant_starved_steps_after_warm")

                    # ---- unified decode/verify step ----
                    dispatched = bool(take)
                    if live:
                        ph.to("decode.pack")
                        if self._params is not swapped:
                            # new weights: from a harvested state
                            drain("decode.pack")
                            swapped = self._params
                        # pass 1 — propose: drafts only while the ring has
                        # spare slots (once positions reach C, every slot
                        # holds a live window position, and a multi-token
                        # step's later writes would destroy KV the
                        # earlier rows still gather — the sliding-window
                        # region decodes one token per step)
                        props: Dict[int, List[int]] = {}
                        for i in list(live):
                            s = slots[i]
                            p = int(pos[i])
                            kq = min(k_max, max(C - 1 - p, 0))
                            if kq and s.get("spec_cool", 0) > 0:
                                # per-sequence backoff: recent drafts all
                                # rejected — rest the proposer a while
                                s["spec_cool"] -= 1
                                kq = 0
                            props[i] = (self._ngram_drafts(s["hist"], kq)
                                        if kq else [])
                        # cost-aware go/no-go: the wide [B, T] verify
                        # trace charges every slot for one slot's drafts.
                        # Using the loop's own measured iteration costs,
                        # go wide only when the predicted accepted tokens
                        # (per-slot acceptance EMA) beat the break-even
                        # bar.  On accelerators wide ~ fast and the bar
                        # ~0 (always speculate); on a compute-bound host
                        # the loop turns selective automatically.
                        drafting = [i for i in live if props[i]]
                        if it_fast is None:  # warmup ran after loop start
                            it_fast = getattr(self, "_it_fast0", None)
                        if it_wide is None:
                            it_wide = getattr(self, "_it_wide0", None)
                        if (drafting and it_fast is not None
                                and it_wide is not None
                                and it_wide > it_fast):
                            bar = len(live) * (it_wide - it_fast) / it_fast
                            pred = sum(slots[i].get("spec_ema", k_max)
                                       for i in drafting)
                            if pred < bar:
                                for i in drafting:
                                    props[i] = []
                        # pass 2 — commit: page accounting + step inputs
                        ids = np.zeros((B, T), np.int32)
                        pp = np.full((B, T), -1, np.int32)
                        cow_pairs = []
                        for i in list(live):
                            s = slots[i]
                            if s is None:
                                continue
                            p = int(pos[i])
                            prop = props.get(i, [])
                            while slots[i] is not None:
                                try:
                                    for j in range(len(prop) + 1):
                                        pr = pool.ensure_writable(i, p + j)
                                        if pr is not None:
                                            cow_pairs.append(
                                                (pr[0], pr[1], i))
                                    break
                                except MemoryError:
                                    if flights:
                                        # an EOS in the step in flight
                                        # may free pages, and a victim is
                                        # chosen among harvested slots
                                        drain("decode.pack")
                                    else:
                                        preempt_newest()
                                    # drop the pending copies of a slot
                                    # that ended: its freed dst pages may
                                    # be re-allocated this very step
                                    cow_pairs = [
                                        t for t in cow_pairs
                                        if slots[t[2]] is not None]
                            s = slots[i]
                            if s is None:
                                continue  # preempted itself
                            for j in range(len(prop) + 1):
                                pool.pos_map[i, (p + j) % C] = p + j
                            # a token still in flight stays on the device:
                            # -1 takes it from `prev` (read or not by now,
                            # that column holds it)
                            ids[i, 0] = (s["hist"][-1]
                                         if s["sent"] == len(s["out"])
                                         else -1)
                            pp[i, 0] = p
                            for j, d in enumerate(prop):
                                ids[i, 1 + j] = d
                                pp[i, 1 + j] = p + 1 + j
                            s["_prop"] = prop
                        live = [i for i in range(B) if slots[i] is not None]
                    if live:
                        dispatch_cow(cow_pairs)
                        fault_point("serving.decode")
                        # no slot drafting this step -> the [B, 1] fast
                        # trace (same fn, same math on column 0; rejected
                        # columns simply don't exist to compute)
                        Td = (T if any(slots[i].get("_prop")
                                       for i in live) else 1)
                        packed = self._pack_step(ids[:, :Td], pp[:, :Td],
                                                 pool.pos_map, pool.table,
                                                 aidsv)
                        # only live slots map pages: release() clears a row
                        n_pages = int(np.count_nonzero(pool.table >= 0))
                        ph.to("decode.device", engine=self.name,
                              live=len(live), columns=Td)
                        out, cache = self._step(self._params, self._buffers,
                                                packed, prev, cache)
                        if ahead:
                            prev = out
                        # while the device runs: the pages the paged_decode
                        # kernel's sweep is bounded to, by the rule and
                        # from the arrays the program was given
                        n_swept = int(sweep_bound(key_visible(
                            pool.pos_map[:, None, :], pp[:, :Td, None], C),
                            self._page).sum())
                        cnt.update(decode_steps=1, live_slot_steps=len(live),
                                   decode_steps_ahead=len(flights),
                                   kv_pages_live_steps=n_pages,
                                   kv_pages_swept_steps=n_swept,
                                   kv_page_slots_steps=B * G,
                                   admit_hold_slot_steps=len(back))
                        if self._slot_state:
                            # every slot's rows, live or not: in and out
                            cnt["state_bytes_steps"] += (
                                2 * B * self._model.slot_state_bytes())
                        self._note_quant_step()
                        self.metrics.observe_occupancy(len(live) / B)
                        if self._lora_cap:
                            if self._warm:
                                self._tenancy_steps += 1
                            for i in live:
                                if aidsv[i] >= 0:
                                    self._adapter_hits[aidsv[i]] += 1
                        flight = _Flight(out, tuple(
                            (i, slots[i], int(pp[i, 0]),
                             slots[i].pop("_prop")) for i in live),
                            self._moe_last)
                        flights.append(flight)
                        for handle in (flight.out, flight.moe):
                            if handle is not None:
                                # on the host by the time it is read
                                handle.copy_to_host_async()
                        # by count: every row computes one token, and a
                        # budget that ends with it ends the slot here (its
                        # pages go to whatever is dispatched next; the
                        # future resolves at this step's harvest).  With
                        # drafts the count is the harvest's to make
                        for i in live:
                            s = slots[i]
                            pos[i] += 1
                            s["sent"] += 1
                            if ahead and s["sent"] >= s["budget"]:
                                free_slot(i)
                        # read the oldest step unread: the one before this
                        # where the loop runs ahead, else this one
                        while len(flights) > int(ahead):
                            dt = read_oldest() / 1e6
                            if Td == 1:
                                it_fast = (dt if it_fast is None
                                           else 0.8 * it_fast + 0.2 * dt)
                            else:
                                it_wide = (dt if it_wide is None
                                           else 0.8 * it_wide + 0.2 * dt)
                            self.metrics.set_gauge("decode_step_ms", dt)
                        dispatched = True

                    if not dispatched and not blocked_wait:
                        ph.to("wait")
                        time.sleep(0.002)  # deferred/idle: don't spin hot

                    ph.to("publish")
                    now = time.monotonic()
                    due = now - last_pub >= 0.1
                    if due:
                        last_pub = now
                        nlive = sum(1 for s in slots if s is not None)
                        age = q.oldest_wait_ms()
                        if carry:
                            age = max(age,
                                      (now - carry[0][0].enqueue_t) * 1e3)
                        self.metrics.set_gauge("slot_occupancy", nlive / B)
                        self.metrics.set_gauge("slots_free", B - nlive)
                        self.metrics.set_gauge("queue_age_ms", age)
                        ps = pool.stats()
                        self.metrics.set_gauge("kv_pages_free",
                                               ps["kv_pages_free"])
                        self.metrics.set_gauge("kv_pages_shared",
                                               ps["kv_pages_shared"])
                        self.metrics.set_gauge("kv_pages_leaked",
                                               ps["kv_pages_leaked"])
                        self.metrics.set_counter("cow_copies",
                                                 ps["cow_copies"])
                        self.metrics.set_queue_depth(
                            q.queue_depth + len(carry))
                        self.metrics.set_counter("compiles",
                                                 self.compile_count)
                        self._emit_tenancy(carry)
                    ph.flush()  # counters first: the snapshot holds them
                    if due or force_pub:
                        self.metrics.publish()
                except Exception as e:
                    ph.flush()
                    # Device failure mid-flight.  Greedy decode is
                    # deterministic, so a restart-from-scratch regenerates
                    # the exact same tokens: requeue live requests (bounded
                    # per request) and keep the loop alive, with fresh page
                    # accounting — the pool metadata and device pool are
                    # rebuilt together (registered prefixes re-register
                    # off future donors)
                    if self.breaker is not None:
                        self.breaker.record_failure(0)
                    survivors: List[tuple] = []
                    # ... and the rows of the steps in flight whose slots
                    # were freed at the dispatch (a budget's end by count):
                    # their tokens were never read
                    lost = {id(s): s for s in slots if s is not None}
                    lost.update((id(s), s) for f in flights
                                for _, s, _, _ in f.rows if not s.get("done"))
                    flights.clear()
                    slots[:] = [None] * B
                    for s in lost.values():
                        if is_transient(e) and s["restarts"] < max_restarts:
                            survivors.append((s["req"], s["restarts"] + 1))
                        else:
                            self.metrics.incr("errors")
                            if not s["req"].future.done():
                                s["req"].future.set_exception(e)
                    pos[:] = -1
                    aidsv[:] = -1
                    cache = prev = None
                    pool = self._pool = self._new_pool()
                    carry = survivors + carry
                    if survivors:
                        self.metrics.incr("restarts")
                    self.metrics.publish()
        finally:
            ph.to(None)
            ph.flush()
            q.consumer_done()

    # -- public API ----------------------------------------------------------
    def synthetic_inputs(self) -> np.ndarray:
        """A one-token prompt — the router's default health probe decodes
        one token through the real admission+decode executables."""
        return np.zeros((1,), np.int32)

    def submit(self, prompt_ids, max_new_tokens: int = 32,
               deadline_ms: Optional[float] = None,
               trace_ctx=None, prefix_key: Optional[str] = None,
               prefix_len: int = 0, handoff=None,
               tenant: Optional[str] = None,
               adapter_id: Optional[int] = None) -> Future:
        """Async generation; resolves to the ``[<=max_new_tokens]`` int32
        array of greedily decoded tokens (stops after ``eos_token_id``).
        ``trace_ctx`` optionally parents the queue/slot spans under a
        router trace.

        ``prefix_key`` + ``prefix_len`` declare
        ``prompt_ids[:prefix_len]`` as a shareable prefix (e.g. the
        system prompt) — the first such request prefills it once and
        registers its pages; later requests with the same key (and the
        same leading tokens — verified, divergence falls back to a cold
        admission) map those pages read-only, copy-on-write.

        ``handoff`` is the prefill/decode disaggregation seam.
        ``handoff=True`` on a ``role='prefill'`` engine
        resolves the future with a :class:`KVHandoff` — the prompt's KV
        pages plus the first token — instead of decoding.  Passing that
        :class:`KVHandoff` (with the same ``prompt_ids``) to a
        ``role='decode'`` engine adopts the pages and decodes the
        remaining ``max_new_tokens - 1`` tokens, bit-identical to the
        co-located path.  Plain submits (``handoff=None``) work on every
        role — that is what router health probes send.

        Multi-tenant serving: ``tenant`` tags the request for the
        engine's :class:`~.tenancy.TenantScheduler` (weighted-fair
        admission, token budgets, per-tenant metrics/spans) and
        ``adapter_id`` selects a LoRA table slot for every decode step
        of this request (``None`` resolves through the tenant's
        registered spec when a scheduler is attached; the default is
        ``-1`` — the base model, bitwise)."""
        if max_new_tokens < 1:
            raise InvalidArgumentError("max_new_tokens must be >= 1")
        if adapter_id is not None:
            aid = int(adapter_id)
            if aid != -1 and not 0 <= aid < self._lora_cap:
                raise InvalidArgumentError(
                    f"{self.name}: adapter_id {aid} outside the adapter "
                    f"table (capacity {self._lora_cap}; -1 = base model)")
        elif tenant is not None and self._tenancy is not None:
            aid = int(self._tenancy.adapter_id(tenant))
        else:
            aid = -1
        if self._slot_state:
            if handoff is not None:
                raise InvalidArgumentError(
                    f"{self.name}: handoff= with a model that keeps "
                    f"recurrent state per slot: the payload carries pages, "
                    f"not slot state")
            if prefix_key is not None:
                # pages mapped from a sibling would come without the state
                # at the prefix's boundary: served cold, on the record
                prefix_key = None
                self.metrics.incr("prefix_unshared")
        if handoff is not None:
            if handoff is True:
                if self._role != "prefill":
                    raise InvalidArgumentError(
                        f"{self.name}: handoff=True (produce) requires "
                        f"role='prefill', this engine is "
                        f"role={self._role!r}")
            elif isinstance(handoff, KVHandoff):
                if self._role != "decode":
                    raise InvalidArgumentError(
                        f"{self.name}: adopting a KVHandoff requires "
                        f"role='decode', this engine is "
                        f"role={self._role!r}")
                if int(handoff.length) > self._C:
                    raise InvalidArgumentError(
                        f"{self.name}: handoff length {handoff.length} "
                        f"exceeds cache_len ({self._C})")
            else:
                raise InvalidArgumentError(
                    f"handoff must be None, True, or a KVHandoff, got "
                    f"{type(handoff).__name__}")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        meta = (int(max_new_tokens), prefix_key, int(prefix_len), handoff,
                tenant, aid)
        return self._batcher.submit((prompt,), deadline_ms=deadline_ms,
                                    meta=meta, trace_ctx=trace_ctx)

    def generate(self, prompt_ids, max_new_tokens: int = 32,
                 timeout: Optional[float] = None, **kw) -> np.ndarray:
        """Blocking :meth:`submit` (extra keywords — ``adapter_id``,
        ``tenant``, ``prefix_key``… — pass through)."""
        return self.submit(prompt_ids, max_new_tokens, **kw).result(timeout)

    def reload_weights(self) -> None:
        """Re-snapshot weights from the live model (e.g. after
        ``paddle_tpu.load`` into it) — the next device dispatch serves
        them, zero recompiles (params are executable arguments).
        Quantized engines re-quantize the fresh float weights on the way
        in, so the tree shapes/dtypes the executables were traced against
        are preserved."""
        if self._quantized:
            from ..slim.quantization import quantize_model_trees
            self._params, self._buffers = quantize_model_trees(
                self._model, self._quantized)
        else:
            self._params = self._model.param_pytree()
            self._buffers = self._model.buffer_pytree()
        self._quant_active = self._tree_quant_active(self._params)
        self.metrics.publish({"weight_swap": 1})
        self._emit_quant()

    def stats(self) -> dict:
        snap = self.metrics.snapshot()
        snap["compile_count"] = self.compile_count
        snap["buckets"] = len(self._buckets)
        # constants: the benchmark's runners copy them into `engine_stats`
        # (they go with the two constructor keywords, ROADMAP.md D1c)
        snap["continuous"] = True
        snap["paged"] = True
        snap["role"] = self._role
        snap["quantization"] = self._quantized or "none"
        snap.update(self._pool.stats())
        return snap

    def close(self, drain: bool = True, timeout: Optional[float] = None):
        self._batcher.close(drain=drain, timeout=timeout)
        self._thread.join(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
