"""Serving observability — counters + latency quantiles on the event bus.

Every engine owns a :class:`ServingMetrics`; after each executed batch (and
on every shed/expiry) a full snapshot is published as a
``("serving", <engine-name>)`` event on ``framework.trace_events`` —
latest-value semantics like the ``executor_cache`` family, NOT deduped
signature events.  ``analysis.RetraceMonitor`` consumes the snapshots for
rule S601 (bucket-miss churn); dashboards read them straight off the bus.

Snapshot keys: ``requests, completed, shed, expired, errors,
bucket_misses, fallback_runs, compiles, batches, circuit_shed,
queue_depth, batch_occupancy, p50_ms, p99_ms, queue_p50_ms,
queue_p99_ms, execute_p50_ms, execute_p99_ms, tokens, tokens_per_s``.

:class:`GenerationEngine` adds the slot-scheduler family: counters
``admitted, evicted, decode_steps, restarts, starved_steps,
starved_steps_after_warm`` plus per-step gauges (``set_gauge``) such as
``slot_occupancy`` (live slots / batch), ``slots_free`` and
``queue_age_ms`` (age of the oldest queued request).  Rule S603 reads
the starvation counters.

The decode loop publishes the gauge ``decode_step_ms`` (the last
``decode.device`` interval) and the ``LOOP_COUNTERS`` family: its wall
time by phase in integer microseconds (``loop_us_<phase>``; the phases
tile every iteration and sum to ``loop_us_total``), the work it
dispatched (admission rows/tokens against the row slots and the
``[R(bucket), bucket]`` token slots of each prefill call, ``R`` that
bucket's rows by ``generation.admit_rows``;
live slots and live page-table entries against ``B`` and ``B x G`` per
decode step) and the summed queue wait and time to first token of the
requests it admitted.  The loop keeps one decode step in flight: it
dispatches step n+1 before it reads step n's tokens.  Work is still
counted once its dispatch has returned; what is derived from a step's
TOKENS (``evicted``, ``completed``, ``tokens``, the tenants' charges, the
experts' counts) is counted when the step is harvested and so trails the
dispatch by one step.  ``decode_steps_ahead`` counts the decode steps
dispatched while the step before them was still unread (over
``decode_steps``: how often the loop ran ahead; 0 for an engine that
speculates, which drafts from the tokens and so reads every step before
the next), ``decode_tokens_stale`` the tokens a step computed for a row
whose request the step before it had already ended (an ``eos_token_id``
seen one step late; dropped, never returned).  Beside each sum,
``loop_max_us_<phase>`` is the phase's longest single interval since the
engine started, so that a stall shows as one call of one phase and not
as a mean that crept.  The same phases are ``serve/<phase>`` spans on
the loop's thread in a profiler trace (:class:`LoopClock`).

With them comes the page-accounting family:
counters ``cow_copies`` (copy-on-write page copies), ``spec_drafted`` /
``spec_accepted`` (speculative-decoding draft economics) and
``preempted`` (slots evicted to reclaim pages), plus gauges
``kv_pages_free``, ``kv_pages_shared`` (refcount > 1) and
``kv_pages_leaked`` (held by no table and no prefix — rule S604's
signal).  The Prometheus bridge picks all of these up for free off the
same snapshot.

Engines serving MoE models (``GPTConfig.moe_experts > 0``) add the
expert-routing family (``MOE_COUNTERS`` + the ``moe_overflow_frac`` /
``moe_dead_experts`` gauges) — rule S606 reads it.

Multi-tenant engines add ``LORA_COUNTERS`` (adapter table hot-edits) and
``TENANCY_COUNTERS`` (budget preemption / throttling / in-budget
starvation — rule S607), plus the ``("engine", "tenant")``-labeled
histogram ``paddle_tpu_serving_tenant_latency_ms`` and counter
``paddle_tpu_serving_tenant_tokens_total`` via :meth:`observe_tenant` —
both behind ``MetricRegistry``'s label-cardinality cap.
"""
from __future__ import annotations

import collections
import math
import threading
import time
from typing import Deque, Dict, Mapping, Optional, Sequence

from .. import profiler
from ..framework import trace_events
from ..framework.locking import OrderedLock

__all__ = ["ServingMetrics", "LoopClock"]

#: counter keys every snapshot carries (zero-initialized)
_COUNTERS = ("requests", "completed", "shed", "expired", "errors",
             "bucket_misses", "fallback_runs", "compiles", "batches",
             "tokens", "circuit_shed", "drain_timeout")

#: slot-scheduler counters (continuous batching; see ``extra_counters``)
SLOT_COUNTERS = ("admitted", "evicted", "decode_steps", "restarts",
                 "starved_steps", "starved_steps_after_warm")

#: the phases that tile one iteration of the paged decode loop, in the
#: order they run: ``sched`` (close check, tenancy, expiry, queue poll,
#: choosing what to admit), ``admit.host`` (page accounting, prefill
#: inputs, CoW dispatch, first-token book-keeping), ``admit.device`` (the
#: prefill calls, one per chunk of rows, until the last one's first tokens
#: are on the host), ``decode.pack``
#: (drafts, page growth, step inputs), ``decode.device`` (the dispatch of
#: a step plus the blocking wait for the tokens of the step read next: the
#: one before it where the loop runs one step ahead, its own where it
#: cannot), ``harvest`` (accept/finish per row of the step just read: host
#: work only), ``publish``, ``wait`` (sleeps and blocking polls with
#: nothing live)
LOOP_PHASES = ("sched", "admit.host", "admit.device", "decode.pack",
               "decode.device", "harvest", "publish", "wait")
_PHASE_KEY = {p: "loop_us_" + p.replace(".", "_")
              for p in (*LOOP_PHASES, "total")}

_MAX_KEY = {p: "loop_max_us_" + p.replace(".", "_") for p in LOOP_PHASES}

#: paged-decode-loop counters (see the module docstring): wall time by
#: phase and each phase's longest interval, work counted once its
#: dispatch has returned (``admit_steps`` counts admission device calls,
#: one per chunk; ``admit_row_slots`` is the call's rows ``R(bucket)``
#: (``generation.admit_rows``) and ``admit_token_slots`` ``R(bucket) x
#: bucket`` per such call, so ``admit_rows / admit_row_slots`` is the rows
#: filled apart from the bucket's padding; ``kv_page_slots_steps`` is
#: ``B x G`` per decode step: the denominators of the useful shares;
#: ``kv_pages_swept_steps`` is the part of ``B x G`` inside the slots' sweep bounds, what the
#: ``paged_decode`` kernel walks; ``decode_steps_ahead`` /
#: ``decode_tokens_stale``: the module docstring; ``admit_rows_held``
#: counts the rows of a short last chunk put back to wait for the slot that
#: fills it, once a row however many iterations it waits, so over
#: ``admit_rows`` it is the share of requests that waited for a partner;
#: ``admit_hold_slot_steps`` the decode steps dispatched meanwhile, one per
#: free slot so held: the slot-steps the hold cost), and the summed
#: per-request times whose count is ``admit_rows``
LOOP_COUNTERS = (*_PHASE_KEY.values(), *_MAX_KEY.values(),
                 "admit_steps", "admit_rows", "admit_row_slots",
                 "admit_tokens", "admit_token_slots", "live_slot_steps",
                 "kv_pages_live_steps", "kv_pages_swept_steps",
                 "kv_page_slots_steps",
                 "decode_steps_ahead", "decode_tokens_stale",
                 "admit_rows_held", "admit_hold_slot_steps",
                 "queue_wait_us", "ttft_us")

#: what the loop reads back of its own record, summed since it started
#: (:attr:`LoopClock.sums`): its mean decode step and admission call are
#: the two sides of the admission hold's break-even
#: (``generation.hold_pays``)
_KEPT = ("loop_us_decode_device", "decode_steps",
         "loop_us_admit_device", "admit_steps")

#: page-accounting counters (see ``extra_counters``)
PAGED_COUNTERS = ("cow_copies", "spec_drafted", "spec_accepted",
                  "preempted")

#: prefill/decode disaggregation counters: hand-offs a
#: prefill-role engine exported (``handoffs_out``) and a decode-role
#: engine adopted (``handoffs_in``)
HANDOFF_COUNTERS = ("handoffs_out", "handoffs_in")

#: expert-routing counters (MoE models; see ``extra_counters``): routed
#: / capacity-dropped token totals plus post-warmup sampled/overflow
#: step counts.  Together with the ``moe_overflow_frac`` and
#: ``moe_dead_experts`` gauges these are rule S606's signal (sustained
#: post-warmup expert overflow, or experts that never receive a token).
MOE_COUNTERS = ("moe_routed_tokens", "moe_dropped_tokens",
                "moe_experts_touched", "moe_layer_steps",
                "moe_sampled_steps_after_warm",
                "moe_overflow_steps_after_warm",
                # (token, choice) pairs the decode steps' routers made, and
                # those of them whose expert this engine's model holds (all
                # of them unless the model holds a share of each layer)
                "moe_pairs_routed", "moe_pairs_local")

#: quantized-serving counters (``GenerationEngine(quantized=...)``):
#: post-warmup decode steps served while the bound weight tree was NOT
#: quantized (a float tree slipped past the quantize hook, so every step
#: silently pays dequantize-free float math at quantized prices) — rule
#: Q801's engine-side signal.
QUANT_COUNTERS = ("quant_fallback_steps_after_warm",)

#: batched multi-LoRA counters (``GPTConfig.lora_capacity > 0``): adapter
#: table hot-edits through ``install_adapter`` / ``remove_adapter`` — the
#: closed-compile-set gate asserts compiles stay flat while these move.
LORA_COUNTERS = ("adapter_installs", "adapter_removals")

#: multi-tenant scheduler counters (``GenerationEngine(tenancy=...)``):
#: slots preempted because their tenant ran over its token budget
#: (``tenant_preempted``), steps where every waiting request belonged to
#: an over-budget tenant (``tenant_throttled_steps`` — throttling by
#: design, kept distinct from S603 starvation), and post-warmup steps
#: where an IN-budget tenant waited with slots free
#: (``tenant_starved_steps_after_warm`` — rule S607's signal).
TENANCY_COUNTERS = ("tenant_preempted", "tenant_throttled_steps",
                    "tenant_starved_steps_after_warm")

#: slot-state counters (a model that declares ``slot_state``: recurrent
#: state per slot beside the K/V pages): admitted rows, each of which
#: started from the zero state whatever its slot held
#: (``state_slots_reset``); real and padded prompt tokens through the
#: chunked scan, one admission call counted once whatever the number of
#: layers (``gdn_prefill_tokens`` / ``gdn_prefill_token_slots``); bytes of
#: slot state the decode steps read and wrote, every slot of every step
#: (``state_bytes_steps``); requests that named a ``prefix_key`` and were
#: served without sharing (``prefix_unshared``: a shared prefix would
#: need a snapshot of the state at its boundary).
STATE_COUNTERS = ("state_slots_reset", "gdn_prefill_tokens",
                  "gdn_prefill_token_slots", "state_bytes_steps",
                  "prefix_unshared")

#: where the model declares ``admit_page_walk`` (an admission's attention is
#: the page walk of ``ops/paged_attention.py``): per admission call, the
#: (query tile, key block) pairs the kernel walks, each tile to its own
#: bound (``admit_attn_blocks_walked``), and the tiles of the call times
#: each slot's bound, the square it walked before a tile had a bound of
#: its own (``admit_attn_blocks_square``); both by
#: ``ops.paged_attention.sweep_bound`` of the arrays the program was given.
ADMIT_WALK_COUNTERS = ("admit_attn_blocks_walked", "admit_attn_blocks_square")


def _quantile(sorted_vals, q: float) -> float:
    """Nearest-rank quantile with the CEIL rank convention: the q-th
    quantile is element ``ceil(q*n)`` (1-based).  The old ``int(q*n)``
    floor-and-use-as-0-based-index form over-read the tail for small
    windows — e.g. p50 of [1,2,3,4] returned 3 (rank 3 of 4 = p75), and
    any q < 1 could land on the max."""
    n = len(sorted_vals)
    if not n:
        return 0.0
    i = min(max(math.ceil(q * n) - 1, 0), n - 1)
    return float(sorted_vals[i])


class ServingMetrics:
    """Thread-safe counters, gauges, and a bounded latency reservoir."""

    def __init__(self, name: str = "serving#0", window: int = 512,
                 extra_counters: Sequence[str] = ()):
        self.name = name
        self._lock = OrderedLock("ServingMetrics._lock")
        # extra_counters zero-initializes caller-specific keys (the
        # router's failover/hedge/drain family) so every snapshot carries
        # the full schema even before the first increment — consumers
        # (bridge gauges, analysis rules) never see a key flicker in
        self._counters: Dict[str, int] = {
            k: 0 for k in (*_COUNTERS, *extra_counters)}
        self._latency_ms: Deque[float] = collections.deque(maxlen=window)
        self._occupancy: Deque[float] = collections.deque(maxlen=window)
        self._queue_ms: Deque[float] = collections.deque(maxlen=window)
        self._execute_ms: Deque[float] = collections.deque(maxlen=window)
        self._queue_depth = 0
        self._token_time_s = 0.0
        self._gauges: Dict[str, float] = {}

    def incr(self, key: str, n: int = 1):
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def add(self, counts: Mapping[str, int]):
        """Advance several counters under one lock acquisition (the
        decode loop's per-iteration batch)."""
        with self._lock:
            c = self._counters
            for key, n in counts.items():
                c[key] = c.get(key, 0) + int(n)

    def set_counter(self, key: str, value: int):
        with self._lock:
            self._counters[key] = int(value)

    def set_queue_depth(self, depth: int):
        with self._lock:
            self._queue_depth = int(depth)

    def set_gauge(self, key: str, value: float):
        """Latest-value gauge folded into every snapshot (the continuous
        decode loop's per-step slot occupancy / free-slot / queue-age
        family rides this)."""
        with self._lock:
            self._gauges[key] = float(value)

    def observe_occupancy(self, frac: float):
        """One occupancy sample (0..1) for the ``batch_occupancy``
        average — the slot scheduler's per-step equivalent of
        :meth:`observe_batch`'s size/capacity sample."""
        with self._lock:
            self._occupancy.append(float(frac))

    def observe_batch(self, size: int, capacity: int, queue_depth: int):
        with self._lock:
            self._counters["batches"] += 1
            self._counters["completed"] += size
            self._occupancy.append(size / max(capacity, 1))
            self._queue_depth = int(queue_depth)

    def observe_latency_ms(self, ms: float):
        with self._lock:
            self._latency_ms.append(float(ms))
        from .. import observability

        if observability.enabled():
            # the SLO engine's latency objectives read this histogram's
            # cumulative buckets (Objective.latency); only completion
            # winners reach here, so hedge losers never double-count
            observability.default_registry().histogram(
                "paddle_tpu_serving_latency_ms",
                "end-to-end per-request latency (submit to completion)",
                ("engine",)).labels(self.name).observe(ms)

    def observe_tenant(self, tenant: str, ms: float, tokens: int):
        """Per-tenant completion observation: latency histogram + token
        counter labeled ``(engine, tenant)``.  The label sets route
        through ``MetricRegistry``'s cardinality cap, so a tenant-id
        flood lands in the ``__overflow__`` child instead of blowing up
        Prometheus — per-tenant SLO objectives read the histogram
        (``TenantScheduler.slo_objectives``)."""
        from .. import observability

        if not observability.enabled():
            return
        reg = observability.default_registry()
        reg.histogram(
            "paddle_tpu_serving_tenant_latency_ms",
            "end-to-end per-request latency by tenant",
            ("engine", "tenant")).labels(self.name, tenant).observe(ms)
        reg.counter(
            "paddle_tpu_serving_tenant_tokens_total",
            "tokens generated by tenant",
            ("engine", "tenant")).labels(self.name, tenant).inc(int(tokens))

    def observe_tokens(self, n: int, seconds: float):
        with self._lock:
            self._counters["tokens"] += int(n)
            self._token_time_s += float(seconds)

    def observe_span(self, queue_ms: float, execute_ms: float):
        """Per-request span breakdown from the batcher: time queued
        (submit → batch dispatch) vs time executing (runner call share).
        Feeds the snapshot quantiles and — when the observability
        registry is live — the ``paddle_tpu_serving_queue_ms`` /
        ``_execute_ms`` histograms labeled by engine."""
        with self._lock:
            self._queue_ms.append(float(queue_ms))
            self._execute_ms.append(float(execute_ms))
        from .. import observability

        if observability.enabled():
            reg = observability.default_registry()
            reg.histogram(
                "paddle_tpu_serving_queue_ms",
                "per-request time queued before batch dispatch",
                ("engine",)).labels(self.name).observe(queue_ms)
            reg.histogram(
                "paddle_tpu_serving_execute_ms",
                "per-request batch execution time",
                ("engine",)).labels(self.name).observe(execute_ms)

    def snapshot(self) -> dict:
        with self._lock:
            lat = sorted(self._latency_ms)
            occ = list(self._occupancy)
            qms = sorted(self._queue_ms)
            xms = sorted(self._execute_ms)
            snap = dict(self._counters)
            snap.update(self._gauges)
            snap["queue_depth"] = self._queue_depth
            snap["batch_occupancy"] = (sum(occ) / len(occ)) if occ else 0.0
            snap["p50_ms"] = _quantile(lat, 0.50)
            snap["p99_ms"] = _quantile(lat, 0.99)
            snap["queue_p50_ms"] = _quantile(qms, 0.50)
            snap["queue_p99_ms"] = _quantile(qms, 0.99)
            snap["execute_p50_ms"] = _quantile(xms, 0.50)
            snap["execute_p99_ms"] = _quantile(xms, 0.99)
            snap["tokens_per_s"] = (snap["tokens"] / self._token_time_s
                                    if self._token_time_s > 0 else 0.0)
        return snap

    def publish(self, extra: Optional[dict] = None):
        """Emit the snapshot on the trace_events bus (a single falsy check
        when nothing subscribes — zero cost on the serve path)."""
        if not trace_events.active():
            return
        snap = self.snapshot()
        if extra:
            snap.update(extra)
        trace_events.notify(("serving", self.name), snap)


class LoopClock:
    """Cuts a serving loop's iterations into contiguous phases.

    :meth:`to` closes the open phase and opens the next with ONE clock
    read, so the phases tile the loop's time.  Each phase is a
    ``profiler.RecordEvent`` named ``serve/<phase>`` (a
    ``TraceAnnotation``: on the device trace's clock, on the loop's
    thread; keyword arguments become the event's metadata) and the same
    interval under ``loop_us_<phase>``; ``loop_us_total`` is the time any
    phase was open.  :meth:`flush`, once an iteration, adds them and
    whatever the loop put into ``counts`` to the metrics under one lock;
    the open phase stays open across it.  Sub-microsecond remainders
    carry over, so the integer counters do not drift from the clock.
    ``loop_max_us_<phase>`` is the phase's longest single interval so far
    (a flush does not cut it); the counter advances by what that record
    rose, so its delta over a window says how far an interval inside the
    window outlasted every one before it.  A request's future resolves
    inside an iteration, so the counters trail it by the rest of that
    iteration.  ``sums`` keeps what the loop itself reads back (``_KEPT``),
    summed over every flush."""

    def __init__(self, metrics: "ServingMetrics"):
        self._metrics = metrics
        self.counts: Dict[str, int] = collections.Counter()
        self.sums: Dict[str, int] = dict.fromkeys(_KEPT, 0)
        self._ns = dict.fromkeys(_PHASE_KEY, 0)
        self._open_ns = 0  # the open phase's interval up to the last flush
        self._max_us = dict.fromkeys(LOOP_PHASES, 0)
        self._phase: Optional[str] = None
        self._span: Optional[profiler.RecordEvent] = None
        self._t = time.monotonic_ns()

    def _lap(self) -> int:
        now = time.monotonic_ns()
        dt, self._t = now - self._t, now
        if self._phase is not None:
            self._ns[self._phase] += dt
            self._ns["total"] += dt
        return dt

    def to(self, phase: Optional[str], **args) -> int:
        """Open ``phase`` (``None``: only close the open one); returns the
        nanoseconds since the last phase change or flush."""
        dt = self._lap()
        if self._span is not None:
            self._span.__exit__(None, None, None)
            us = (self._open_ns + dt) // 1000
            if us > self._max_us[self._phase]:
                self.counts[_MAX_KEY[self._phase]] += \
                    us - self._max_us[self._phase]
                self._max_us[self._phase] = us
        self._phase, self._span, self._open_ns = phase, None, 0
        if phase is not None:
            self._span = profiler.RecordEvent("serve/" + phase, **args)
            self._span.__enter__()
        return dt

    def flush(self):
        self._open_ns += self._lap()
        c = self.counts
        for p, ns in self._ns.items():
            c[_PHASE_KEY[p]], self._ns[p] = divmod(ns, 1000)
        for k in _KEPT:
            self.sums[k] += c[k]
        self._metrics.add(c)
        c.clear()
