"""Retrace hazard detector — catches jit signature explosions at run time.

``jit.StaticFunction`` and ``static.graph.Executor`` publish one event per
call / per compiled signature on ``framework.trace_events``.  The
:class:`RetraceMonitor` subscribes, counts *distinct* signatures per site,
and past a configurable budget diffs the signature stream to identify WHICH
argument's shape, dtype, or static-value churn caused the explosion — the
diagnostic a user otherwise reconstructs by hand from minutes-long compile
stalls.

Usage::

    from paddle_tpu.analysis import RetraceMonitor
    with RetraceMonitor(budget=8) as mon:
        train_loop()
    print(render_text(mon.diagnostics()))
"""
from __future__ import annotations

import threading
from typing import Dict, List, Tuple

from ..framework import trace_events
from .diagnostics import Diagnostic, DiagnosticCollector, Location

__all__ = ["RetraceMonitor"]


def _churn_axes(values) -> str:
    """Describe how a sequence of per-signature values varies."""
    uniq = list(dict.fromkeys(values))
    shown = ", ".join(map(str, uniq[:4]))
    if len(uniq) > 4:
        shown += f", … ({len(uniq)} distinct)"
    return shown


class RetraceMonitor:
    """Context manager collecting per-site trace signatures.

    ``budget``: distinct signatures per site before the site is reported.
    The default 8 tolerates the legitimate signature set of a train loop
    (train/eval × a couple of batch geometries) while catching the
    pathological one-signature-per-step pattern within the first dozen
    steps."""

    def __init__(self, budget: int = 8):
        self.budget = int(budget)
        self._lock = threading.Lock()
        self._sites: Dict[Tuple[str, str], List[dict]] = {}
        self._seen: Dict[Tuple[str, str], set] = {}
        # ("executor_cache", name) counter snapshots: latest value per
        # executor, NOT deduped signature events (rule R403)
        self._cache_sites: Dict[str, dict] = {}
        # ("serving", name) engine snapshots: same latest-value semantics
        # (rules S601 / S602 / S603 / S604 / S606 — router snapshots
        # carry "router": 1)
        self._serving_sites: Dict[str, dict] = {}
        # ("router", "<router>[<i>]") per-replica snapshots: latest state /
        # outstanding / counters per replica (rule S602 context)
        self._router_sites: Dict[str, dict] = {}
        # ("autotune", name) search snapshots: latest per client (rule K701)
        self._autotune_sites: Dict[str, dict] = {}
        # ("resilience", retry:<name>|circuit:<name>|fault:<site>) counter
        # snapshots: latest per policy / per circuit key (rule F801)
        self._resilience_sites: Dict[str, dict] = {}
        # ("steptrace", name) training-telemetry snapshots: latest per loop
        # (rules M901 / M902)
        self._steptrace_sites: Dict[str, dict] = {}
        # ("slo", name) SLO-engine snapshots: latest per engine (rule M903)
        self._slo_sites: Dict[str, dict] = {}
        # ("pool", name) replica-pool actuator snapshots: latest per pool
        # (rule S605 — post-warmup scale thrash)
        self._pool_sites: Dict[str, dict] = {}
        # ("supervisor", name) divergence-guard counter snapshots: latest
        # per supervisor (rule F802)
        self._supervisor_sites: Dict[str, dict] = {}
        # gang watchdog / gang-collective snapshots (rule F803)
        self._gang_sites: Dict[str, dict] = {}
        # ("amp", name) grad-scaler snapshots: latest per scaler
        self._amp_sites: Dict[str, dict] = {}
        # ("quant", name) quantization snapshots: latest per site — slim
        # calibration (PTQ/QAT observer coverage) and quantized serving
        # engines (post-warmup dequantize-fallback steps).  Rule Q801.
        self._quant_sites: Dict[str, dict] = {}
        # ("concurrency", lock) lock-sanitizer snapshots: latest per lock
        # name, published on every C1004/C1005 violation (framework/
        # locking.py); the violation details ride last_rule/last_message
        self._concurrency_sites: Dict[str, dict] = {}
        # ("tenancy", engine) multi-tenant scheduler snapshots: latest
        # per engine — per-tenant starvation/budget state plus LoRA
        # adapter-table liveness.  Rule S607.
        self._tenancy_sites: Dict[str, dict] = {}

    # -- subscription --------------------------------------------------------
    def install(self):
        trace_events.register(self._on_event)
        return self

    def uninstall(self):
        trace_events.unregister(self._on_event)

    __enter__ = install

    def __exit__(self, *exc):
        self.uninstall()

    def _on_event(self, site, info):
        key = tuple(site)
        if key[0] == "executor_cache":
            # counter snapshot: keep only the latest per executor — routing
            # these through the signature dedup below would mint a distinct
            # "signature" per counter tick and inflate R402
            with self._lock:
                self._cache_sites[key[1]] = dict(info)
            return
        if key[0] == "serving":
            with self._lock:
                self._serving_sites[key[1]] = dict(info)
            return
        if key[0] == "router":
            # per-replica counter snapshot: latest value wins — deduping
            # would mint one "signature" per counter tick and leak router
            # telemetry into the R401/R402 budgets
            with self._lock:
                self._router_sites[key[1]] = dict(info)
            return
        if key[0] == "autotune":
            # search snapshot: latest counters per client — deduping would
            # drop the counter ticks K701 exists to observe
            with self._lock:
                self._autotune_sites[key[1]] = dict(info)
            return
        if key[0] == "resilience":
            # retry/circuit/fault counter snapshots: latest value wins;
            # circuit transitions carry per-key cumulative counters, so
            # keep one slot per (breaker, key)
            name = key[1]
            if isinstance(info, dict) and info.get("kind") == "circuit":
                name = f"{name}[{info.get('key')}]"
            with self._lock:
                self._resilience_sites[name] = dict(info)
            return
        if key[0] == "steptrace":
            # training-telemetry snapshot: cumulative sums, latest wins
            with self._lock:
                self._steptrace_sites[key[1]] = dict(info)
            return
        if key[0] == "slo":
            # SLO-engine tick snapshot: cumulative counters, latest wins
            with self._lock:
                self._slo_sites[key[1]] = dict(info)
            return
        if key[0] == "pool":
            # replica-pool actuator snapshot: cumulative counters, latest
            # wins (S605 reads the thrash counters)
            with self._lock:
                self._pool_sites[key[1]] = dict(info)
            return
        if key[0] == "supervisor":
            # divergence-guard counter snapshot: cumulative, latest wins
            with self._lock:
                self._supervisor_sites[key[1]] = dict(info)
            return
        if key[0] == "gang":
            # gang watchdog / host-lane collective snapshot: cumulative
            # counters (gang_restores, post_restore_lost, op timeouts),
            # latest wins (rule F803)
            with self._lock:
                self._gang_sites[key[1]] = dict(info)
            return
        if key[0] == "amp":
            # grad-scaler snapshot (scale, skipped steps): latest wins
            with self._lock:
                self._amp_sites[key[1]] = dict(info)
            return
        if key[0] == "quant":
            # quantization snapshot (calibration coverage / engine
            # fallback counters): cumulative, latest wins (rule Q801)
            with self._lock:
                self._quant_sites[key[1]] = dict(info)
            return
        if key[0] == "concurrency":
            # lock-sanitizer snapshot per lock name: cumulative counters,
            # latest wins (rules C1004 / C1005)
            with self._lock:
                self._concurrency_sites[key[1]] = dict(info)
            return
        if key[0] == "tenancy":
            # multi-tenant scheduler snapshot: cumulative per-tenant
            # counters + adapter-table liveness, latest wins (rule S607)
            with self._lock:
                self._tenancy_sites[key[1]] = dict(info)
            return
        sig = _freeze(info)
        with self._lock:
            seen = self._seen.setdefault(key, set())
            if sig in seen:
                return
            seen.add(sig)
            self._sites.setdefault(key, []).append(info)

    # -- analysis ------------------------------------------------------------
    def distinct_signatures(self, kind: str, name: str) -> int:
        return len(self._sites.get((kind, name), ()))

    def cache_stats(self, name: str = None):
        """Latest compile-cache counter snapshot(s) observed: the dict for
        one executor (``name`` like ``"executor#1"``), or all of them."""
        with self._lock:
            if name is not None:
                return dict(self._cache_sites.get(name, {}))
            return {k: dict(v) for k, v in self._cache_sites.items()}

    def serving_stats(self, name: str = None):
        """Latest serving-engine snapshot(s) observed (queue depth, batch
        occupancy, latency quantiles, bucket misses…): the dict for one
        engine (``name`` like ``"engine#1"``), or all of them."""
        with self._lock:
            if name is not None:
                return dict(self._serving_sites.get(name, {}))
            return {k: dict(v) for k, v in self._serving_sites.items()}

    def router_stats(self, replica: str = None):
        """Latest per-replica router snapshot(s) observed (state,
        outstanding, probe/flap/hedge counters): the dict for one replica
        (``replica`` like ``"router#1[0]"``), or all of them."""
        with self._lock:
            if replica is not None:
                return dict(self._router_sites.get(replica, {}))
            return {k: dict(v) for k, v in self._router_sites.items()}

    def autotune_stats(self, name: str = None):
        """Latest measured-search snapshot(s) observed (resolution event,
        chosen config, counter totals): the dict for one client of
        ``tuning.engine`` (its ``name``), or all of them."""
        with self._lock:
            if name is not None:
                return dict(self._autotune_sites.get(name, {}))
            return {k: dict(v) for k, v in self._autotune_sites.items()}

    def resilience_stats(self, name: str = None):
        """Latest resilience snapshot(s) observed — retry counters per
        policy (``"retry:engine#1.runner"``), circuit transitions per
        breaker key (``"circuit:engine#1[0]"``), fault-point firings
        (``"fault:checkpoint.write"``): one dict, or all of them."""
        with self._lock:
            if name is not None:
                return dict(self._resilience_sites.get(name, {}))
            return {k: dict(v) for k, v in self._resilience_sites.items()}

    def steptrace_stats(self, name: str = None):
        """Latest training-telemetry snapshot(s) observed (step counts,
        data-wait vs dispatch vs device time, rates, MFU, HBM high-water):
        the dict for one loop (``name`` like ``"train"``), or all of
        them."""
        with self._lock:
            if name is not None:
                return dict(self._steptrace_sites.get(name, {}))
            return {k: dict(v) for k, v in self._steptrace_sites.items()}

    def slo_stats(self, name: str = None):
        """Latest SLO-engine snapshot(s) observed (ticks, alerts,
        per-objective burn rates, scale-signal counters): the dict for
        one engine (``name`` like ``"slo#1"``), or all of them."""
        with self._lock:
            if name is not None:
                return dict(self._slo_sites.get(name, {}))
            return {k: dict(v) for k, v in self._slo_sites.items()}

    def pool_stats(self, name: str = None):
        """Latest replica-pool actuator snapshot(s) observed (scale
        ups/downs, deferral counters, thrash events, replica gauges):
        the dict for one pool (``name`` like ``"pool#1"``), or all of
        them."""
        with self._lock:
            if name is not None:
                return dict(self._pool_sites.get(name, {}))
            return {k: dict(v) for k, v in self._pool_sites.items()}

    def supervisor_stats(self, name: str = None):
        """Latest training-supervisor counter snapshot(s) observed
        (rollbacks, repeat trips, skipped batches, exact resumes, watchdog
        trips, fatal divergences): the dict for one supervisor (``name``
        like ``"supervisor"``), or all of them."""
        with self._lock:
            if name is not None:
                return dict(self._supervisor_sites.get(name, {}))
            return {k: dict(v) for k, v in self._supervisor_sites.items()}

    def gang_stats(self, name: str = None):
        """Latest gang snapshot(s) observed: a per-host watchdog's
        gang-restore counters (``name`` like ``"watch.p0"`` —
        ``gang_restores`` / ``post_restore_lost`` / the lost ranks) or a
        gang collective lane's op counters (``name`` like ``"gang"``).
        The dict for one site, or all of them."""
        with self._lock:
            if name is not None:
                return dict(self._gang_sites.get(name, {}))
            return {k: dict(v) for k, v in self._gang_sites.items()}

    def amp_stats(self, name: str = None):
        """Latest grad-scaler snapshot(s) observed (loss scale, skipped
        steps, good/bad step counters): the dict for one scaler (``name``
        like ``"grad_scaler"``), or all of them."""
        with self._lock:
            if name is not None:
                return dict(self._amp_sites.get(name, {}))
            return {k: dict(v) for k, v in self._amp_sites.items()}

    def quant_stats(self, name: str = None):
        """Latest quantization snapshot(s) observed: ``kind='calibration'``
        (slim PTQ/QAT observer coverage — ``layers`` / ``calibrated`` /
        ``uncalibrated_layers``) or ``kind='engine'`` (a quantized serving
        engine's mode + post-warmup fallback step counter).  The dict for
        one site (``name`` like ``"ptq"`` or an engine name), or all of
        them."""
        with self._lock:
            if name is not None:
                return dict(self._quant_sites.get(name, {}))
            return {k: dict(v) for k, v in self._quant_sites.items()}

    def concurrency_stats(self, name: str = None):
        """Latest lock-sanitizer snapshot(s) observed (cumulative
        acquire/edge/cycle/long-hold counters plus the violation that
        triggered the publish): the dict for one lock name (``name`` like
        ``"Router._lock"``), or all of them."""
        with self._lock:
            if name is not None:
                return dict(self._concurrency_sites.get(name, {}))
            return {k: dict(v)
                    for k, v in self._concurrency_sites.items()}

    def tenancy_stats(self, name: str = None):
        """Latest multi-tenant scheduler snapshot(s) observed (per-tenant
        admission/budget/starvation state plus LoRA adapter-table
        liveness): the dict for one engine, or all of them."""
        with self._lock:
            if name is not None:
                return dict(self._tenancy_sites.get(name, {}))
            return {k: dict(v) for k, v in self._tenancy_sites.items()}

    def diagnostics(self) -> List[Diagnostic]:
        out = DiagnosticCollector()
        with self._lock:
            sites = {k: list(v) for k, v in self._sites.items()}
        for (kind, name), sigs in sites.items():
            if len(sigs) <= self.budget:
                continue
            causes = (self._diff_jit(sigs) if kind == "jit"
                      else self._diff_executor(sigs))
            rule = "R401" if kind == "jit" else "R402"
            what = ("to_static function" if kind == "jit"
                    else "Executor program")
            out.add(rule,
                    f"{what} {name!r} compiled {len(sigs)} distinct "
                    f"signatures (budget {self.budget}); churn: "
                    f"{'; '.join(causes) if causes else 'unknown'}",
                    location=Location(file=name, function=name),
                    hint="pad inputs to a fixed shape bucket, cast feeds "
                         "to one dtype, and hoist Python-value arguments "
                         "out of the traced signature")
        with self._lock:
            cache_sites = {k: dict(v) for k, v in self._cache_sites.items()}
        for name, stats in cache_sites.items():
            evictions = int(stats.get("evictions", 0))
            if evictions <= self.budget:
                continue
            out.add("R403",
                    f"{name} evicted {evictions} compiled runners "
                    f"(budget {self.budget}; capacity "
                    f"{stats.get('capacity')}, {stats.get('misses')} "
                    f"misses / {stats.get('hits')} hits) — the working "
                    f"set of run signatures exceeds the cache, so steps "
                    f"recompile instead of reusing executables",
                    location=Location(file=name, function=name),
                    hint="raise FLAGS_executor_cache_capacity (or "
                         "Executor(cache_capacity=...)), reduce distinct "
                         "feed geometries, or enable "
                         "sysconfig.enable_persistent_compilation_cache() "
                         "so evicted entries recompile from the on-disk "
                         "XLA cache")
        with self._lock:
            serving_sites = {k: dict(v)
                             for k, v in self._serving_sites.items()}
        for name, stats in serving_sites.items():
            misses = int(stats.get("bucket_misses", 0))
            if misses <= self.budget:
                continue
            fallbacks = int(stats.get("fallback_runs", 0))
            tail = (f"; {fallbacks} served by the unbatched polymorphic "
                    f"fallback (one compile per distinct shape)"
                    if fallbacks else "; rejected at submit")
            out.add("S601",
                    f"serving engine {name} saw {misses} bucket misses "
                    f"(budget {self.budget}) out of "
                    f"{stats.get('requests', 0)} requests{tail} — request "
                    f"shapes are leaking outside the configured bucket "
                    f"set, reopening the compile set the buckets exist "
                    f"to close",
                    location=Location(file=name, function=name),
                    hint="add buckets covering the observed shapes (or "
                         "widen existing ones) so every request pads into "
                         "the closed executable set; keep "
                         "allow_bucket_fallback for rare stragglers only")
        for name, stats in serving_sites.items():
            if not stats.get("router"):
                continue  # engine snapshot, not a router's
            flaps = int(stats.get("replica_flaps_after_warm", 0))
            if flaps >= 3:
                out.add("S602",
                        f"router {name} saw {flaps} replica health flaps "
                        f"after serving warmup ({stats.get('failovers', 0)} "
                        f"failovers, {stats.get('healthy', 0)}/"
                        f"{stats.get('replicas', 0)} replicas healthy) — a "
                        f"replica that keeps re-admitting and re-tripping "
                        f"bounces its share of traffic through failover "
                        f"retries instead of staying shed",
                        location=Location(file=name, function=name),
                        hint="raise the breaker cooldown / half-open probe "
                             "count (Router circuit_kw=...) so recovery "
                             "needs sustained health, or fix the replica "
                             "(device health, OOM pressure) before "
                             "re-admitting it")
            denied = int(stats.get("hedge_denied_after_warm", 0))
            if denied > self.budget:
                out.add("S602",
                        f"router {name} denied {denied} hedged requests "
                        f"after serving warmup (budget {self.budget}; "
                        f"{stats.get('hedges', 0)} hedges sent, "
                        f"{stats.get('hedge_wins', 0)} won) — the hedge "
                        f"delay keeps firing on ordinary traffic, so the "
                        f"budget cap is the only thing stopping the fleet "
                        f"from serving every request twice",
                        location=Location(file=name, function=name),
                        hint="raise hedge_delay_ms (or leave it p99-"
                             "derived and fix the latency regression "
                             "moving the p99); hedges should be rare "
                             "tail-cutters, not a steady second stream")
        for name, stats in serving_sites.items():
            if stats.get("router"):
                continue  # engine snapshots only
            starved = int(stats.get("starved_steps_after_warm", 0))
            depth = int(stats.get("queue_depth", 0))
            if starved > self.budget and depth > 0:
                out.add("S603",
                        f"serving engine {name} ticked {starved} starved "
                        f"decode steps after warmup (budget {self.budget}) "
                        f"with {depth} request(s) still queued and "
                        f"{stats.get('slots_free', '?')} slot(s) free — "
                        f"admission is sustainedly deferred (typically an "
                        f"open circuit breaker after device failures), so "
                        f"queued requests age toward their deadlines while "
                        f"decode capacity sits idle",
                        location=Location(file=name, function=name),
                        hint="check the engine's circuit breaker (repeated "
                             "transient failures keep it open — fix the "
                             "device fault or lower "
                             "FLAGS_circuit_cooldown_ms) and the restart "
                             "counters; if the queue is simply deeper than "
                             "the slot count can drain, add batch_size "
                             "slots or another replica")
            # S604: paged-KV page-pool exhaustion that is a LEAK, not
            # load — admission deferred with zero free pages while pages
            # sit refcounted that no live slot table and no registered
            # prefix references.  Genuine pressure (free=0, leaked=0)
            # stays S603 territory; leaked>0 means eviction returned a
            # slot but not its pages.
            leaked = int(stats.get("kv_pages_leaked", 0))
            if (starved > self.budget and leaked > 0
                    and int(stats.get("kv_pages_free", -1)) == 0):
                out.add("S604",
                        f"serving engine {name} deferred admission for "
                        f"{starved} steps after warmup with 0 free KV "
                        f"pages while {leaked} page(s) are still "
                        f"refcounted by no slot table and no shared "
                        f"prefix — a page leak: evicted slots returned "
                        f"to the scheduler without returning their pages "
                        f"to the free list, so the pool shrinks until "
                        f"admission deadlocks",
                        location=Location(file=name, function=name),
                        hint="audit PagePool release/decref pairing "
                             "(every admit/ensure_writable allocation "
                             "must be released exactly once at eviction "
                             "or preemption) and drop stale shared "
                             "prefixes (PagePool.drop_prefix) — leaked "
                             "pages never return on their own; restart "
                             "the engine to rebuild the pool as a "
                             "stopgap")
            # S606: sustained post-warmup expert-routing pathology on an
            # MoE engine — either the capacity buckets overflow on most
            # decode steps (tokens silently dropped from their chosen
            # experts) or some experts never receive a token at all
            # (dead: their parameters are pure memory/HBM waste).  A few
            # overflow steps are normal traffic skew; a majority is a
            # provisioning bug.
            sampled = int(stats.get("moe_sampled_steps_after_warm", 0))
            if sampled >= 8:
                overflow = int(stats.get(
                    "moe_overflow_steps_after_warm", 0))
                dead = int(stats.get("moe_dead_experts", 0))
                routed = int(stats.get("moe_routed_tokens", 0))
                if overflow / sampled >= 0.5:
                    out.add("S606",
                            f"serving engine {name} overflowed expert "
                            f"capacity on {overflow} of {sampled} decode "
                            f"steps after warmup "
                            f"({stats.get('moe_dropped_tokens', 0)} "
                            f"token-expert assignments dropped of "
                            f"{routed} routed) — the router's load is "
                            f"sustainedly exceeding the static capacity "
                            f"buckets, so tokens silently lose their "
                            f"chosen experts and quality degrades "
                            f"batch-dependently",
                            location=Location(file=name, function=name),
                            hint="raise moe_capacity_factor (static "
                                 "capacity = ceil(k*N*cf/E)) or rebalance "
                                 "the router (train longer with the "
                                 "load-balance loss, or raise "
                                 "moe_balance_weight)")
                elif dead > 0 and routed > 0:
                    out.add("S606",
                            f"serving engine {name} has {dead} dead "
                            f"expert(s): zero tokens routed to them "
                            f"across {sampled} post-warmup decode steps "
                            f"({routed} token-expert assignments total) "
                            f"— their parameters occupy HBM on every "
                            f"device of the expert axis without "
                            f"contributing a FLOP",
                            location=Location(file=name, function=name),
                            hint="retrain with a higher "
                                 "moe_balance_weight (the Switch loss "
                                 "pushes routing toward uniform), lower "
                                 "moe_experts to the population actually "
                                 "used, or add router jitter "
                                 "(moe_jitter) so cold experts see "
                                 "exploration traffic")
        with self._lock:
            pool_sites = {k: dict(v) for k, v in self._pool_sites.items()}
        for name, stats in pool_sites.items():
            # S605: post-warmup scale thrash — the autoscaling loop
            # reversed itself inside its own thrash window more than
            # once after warmup, i.e. the actuator is amplifying noise
            # instead of tracking load.  One reversal can be a genuine
            # load edge; repeated reversals mean the hysteresis/cooldown
            # dials are too tight for the signal's variance.
            thrash = int(stats.get("thrash_events_after_warm", 0))
            if thrash >= 2:
                out.add("S605",
                        f"replica pool {name} reversed scaling direction "
                        f"{thrash} times after warmup inside its thrash "
                        f"window ({stats.get('scale_ups', 0)} up(s) / "
                        f"{stats.get('scale_downs', 0)} down(s), bounds "
                        f"{stats.get('min_replicas', '?')}.."
                        f"{stats.get('max_replicas', '?')}) — each "
                        f"reversal cold-starts or drains a replica for "
                        f"nothing, burning warmup compiles and churning "
                        f"the fleet while the load never changed",
                        location=Location(file=name, function=name),
                        hint="damp the loop: raise cooldown_s or the "
                             "up/down_consecutive streaks on the "
                             "ReplicaPool, widen the SloEngine burn "
                             "thresholds (scale_down_burn), or pin "
                             "min_replicas at the observed steady-state "
                             "fleet size")
        with self._lock:
            autotune_sites = {k: dict(v)
                              for k, v in self._autotune_sites.items()}
        for name, stats in autotune_sites.items():
            counters = stats.get("counters", {})
            late = int(counters.get("searches_after_warm", 0))
            if late <= 0:
                continue
            # every config space of the measured-search engine (sharding
            # plans, serving dials) publishes on the same bus, and a
            # post-warmup search is a hot-path stall whichever it came from
            space = stats.get("space", "")
            what = {"plan": "sharding plan",
                    "serving": "serving config"}.get(space, space)
            detail = {"plan": "timed train-step",
                      "serving": "timed trace-replay"}.get(space, "measured")
            out.add("K701",
                    f"{what} {name!r} ran {late} {detail} "
                    f"search(es) after serving warmup (last key "
                    f"{stats.get('key')!r}) — a tuning cache miss in the "
                    f"hot path stalls live requests behind compile+measure "
                    f"of every candidate",
                    location=Location(file=name, function=name),
                    hint="pre-warm the tuner: resolve each search key at "
                         "its serving shapes before engine.warmup(), and "
                         "ship the FLAGS_kernel_tuning_cache file so "
                         "production processes start with every key "
                         "resolved")
        with self._lock:
            res_sites = {k: dict(v)
                         for k, v in self._resilience_sites.items()}
        for name, stats in res_sites.items():
            kind = stats.get("kind")
            if kind == "retry":
                late = int(stats.get("retries_after_warm", 0))
                if late <= self.budget:
                    continue
                out.add("F801",
                        f"retry policy {name!r} retried {late} transient "
                        f"failures after serving warmup (budget "
                        f"{self.budget}; {stats.get('giveups', 0)} "
                        f"giveups) — a retry storm in the hot path hides "
                        f"a persistently failing device behind added "
                        f"latency instead of surfacing it",
                        location=Location(file=name, function=name),
                        hint="find the fault behind the retries (device "
                             "health, OOM pressure); lower "
                             "FLAGS_transient_max_retries or let the "
                             "circuit breaker shed the traffic instead")
            elif kind == "circuit":
                flaps = int(stats.get("opens_after_warm", 0))
                if flaps < 3:
                    continue
                out.add("F801",
                        f"circuit {name} opened {flaps} times after "
                        f"serving warmup ({stats.get('sheds', 0)} requests "
                        f"shed) — flapping means the cooldown keeps "
                        f"admitting probes into a fault that never "
                        f"cleared",
                        location=Location(file=name, function=name),
                        hint="raise FLAGS_circuit_cooldown_ms (probe "
                             "less often) or fix the underlying bucket "
                             "failure; a circuit that reopens every "
                             "cooldown is a fault, not protection")
        with self._lock:
            step_sites = {k: dict(v)
                          for k, v in self._steptrace_sites.items()}
        for name, stats in step_sites.items():
            steps = int(stats.get("steps_post_warm", 0))
            data_ms = float(stats.get("data_wait_ms", 0.0))
            busy_ms = (float(stats.get("dispatch_ms", 0.0))
                       + float(stats.get("device_ms", 0.0)))
            if steps > self.budget and data_ms > busy_ms:
                total = data_ms + busy_ms
                share = data_ms / total if total > 0 else 0.0
                out.add("M901",
                        f"training loop {name!r} spent "
                        f"{data_ms:.0f}ms waiting on the input pipeline "
                        f"vs {busy_ms:.0f}ms dispatching+computing over "
                        f"{steps} post-warmup steps ({share:.0%} of step "
                        f"time) — the device is idle while the host "
                        f"fetches data",
                        location=Location(file=name, function=name),
                        hint="raise DataLoader prefetch_depth / "
                             "num_workers, move preprocessing off the "
                             "step path, or batch more examples per "
                             "dispatch (Executor.run_steps)")
            peak = float(stats.get("hbm_peak_bytes", 0.0))
            limit = float(stats.get("hbm_limit_bytes", 0.0))
            frac = float(stats.get("hbm_threshold", 0.9))
            if limit > 0 and peak / limit >= frac:
                out.add("M902",
                        f"training loop {name!r} peaked at "
                        f"{peak / 2**30:.2f}GiB HBM of "
                        f"{limit / 2**30:.2f}GiB available "
                        f"({peak / limit:.0%}, alert fraction "
                        f"{frac:.0%}) — one larger batch or a fresh "
                        f"allocation away from OOM",
                        location=Location(file=name, function=name),
                        hint="shard or offload optimizer state (ZeRO), "
                             "enable rematerialization, lower the batch "
                             "size, or raise FLAGS_hbm_high_water_frac "
                             "if this headroom is intentional")
        with self._lock:
            slo_sites = {k: dict(v) for k, v in self._slo_sites.items()}
        for name, stats in slo_sites.items():
            late = int(stats.get("alerts_after_warm", 0))
            if late <= 0:
                continue
            burning = stats.get("alerting") or "objective(s)"
            out.add("M903",
                    f"SLO engine {name!r} fired {late} burn-rate "
                    f"alert(s) after serving warmup ({burning} burning at "
                    f"up to {float(stats.get('max_burn', 0.0)):.1f}x "
                    f"budget; last scale signal "
                    f"{stats.get('last_signal', 'none')!r}) — sustained "
                    f"post-warmup budget burn means the fleet is eating "
                    f"its error budget on live traffic, not on startup "
                    f"transients",
                    location=Location(file=name, function=name),
                    hint="scale up (wire SloEngine.bind_router / "
                         "Router.register_scale_hook into the deployment "
                         "layer) or find the regression behind the burn "
                         "(latency: check K701/F801/S60x; availability: "
                         "check shed and circuit counters)")
        with self._lock:
            sup_sites = {k: dict(v)
                         for k, v in self._supervisor_sites.items()}
        for name, stats in sup_sites.items():
            repeats = int(stats.get("repeat_trips", 0))
            if repeats < 1:
                continue
            out.add("F802",
                    f"training supervisor {name!r} re-diverged "
                    f"{repeats} time(s) after rolling back to the same "
                    f"checkpoint ({stats.get('rollbacks', 0)} rollbacks, "
                    f"{stats.get('skipped_batches', 0)} batches skipped, "
                    f"{stats.get('fatal_divergences', 0)} fatal) — a "
                    f"rollback loop means the divergence is reproducible "
                    f"from the restored state, so restarting cannot fix "
                    f"it: the cause is the model/optimizer state or the "
                    f"data, not a transient fault",
                    location=Location(file=name, function=name),
                    hint="widen the poison window "
                         "(TrainingSupervisor(skip_batches=...)) if a bad "
                         "data shard spans several batches; otherwise "
                         "lower the learning rate / loss scale or inspect "
                         "the checkpoint itself — the restored state is "
                         "already on the divergence trajectory")
        with self._lock:
            gang_sites = {k: dict(v) for k, v in self._gang_sites.items()}
        for name, stats in gang_sites.items():
            restores = int(stats.get("gang_restores", 0))
            stuck = int(stats.get("post_restore_lost", 0))
            if restores >= 3:
                out.add("F803",
                        f"gang watchdog {name!r} performed {restores} "
                        f"gang restores (last lost rank(s): "
                        f"{list(stats.get('lost', ()))}) — the gang keeps "
                        f"dying and restarting; every restore rolls every "
                        f"host back to the last agreed checkpoint, so a "
                        f"restore loop makes zero forward progress while "
                        f"looking busy",
                        location=Location(file=name, function=name),
                        hint="find the host that keeps dying (its own "
                             "watchdog metrics name the exit codes); the "
                             "storm breaker (storm_window/storm_restarts, "
                             "exit 77) bounds the loop but only fixing "
                             "the dying host ends it")
            elif stuck >= 1 and restores >= 1:
                out.add("F803",
                        f"gang watchdog {name!r} saw rank(s) still lost "
                        f"after a completed gang restore ({stuck} "
                        f"repeat-loss event(s), {restores} restores) — a "
                        f"peer that never comes back means the gang "
                        f"re-forms short and every collective will wait "
                        f"on a dead rank until the watchdog trips",
                        location=Location(file=name, function=name),
                        hint="the lost rank's host is down or partitioned "
                             "(not just its trainer): replace the host or "
                             "relaunch with the surviving world size — "
                             "restarting survivors again cannot revive it")
        with self._lock:
            quant_sites = {k: dict(v)
                           for k, v in self._quant_sites.items()}
        for name, stats in quant_sites.items():
            kind = stats.get("kind")
            if kind == "engine":
                # Q801 (engine side): a quantized engine serving
                # post-warmup decode steps with a FLOAT weight tree bound
                # — every step silently runs full-precision math (the
                # dequantize fallback), paying quantized HBM prices for
                # float throughput
                late = int(stats.get("fallback_steps_after_warm", 0))
                if late <= 0:
                    continue
                out.add("Q801",
                        f"quantized serving engine {name} "
                        f"(mode={stats.get('mode')!r}) served {late} "
                        f"post-warmup decode step(s) with a "
                        f"non-quantized weight tree bound — the Linear "
                        f"hot paths silently took the float leg, so the "
                        f"engine runs at full precision while reporting "
                        f"(and provisioning for) {stats.get('mode')!r}",
                        location=Location(file=name, function=name),
                        hint="rebind quantized trees: swap_weights with "
                             "a slim.export_quantized artifact of the "
                             "same mode, or reload_weights() (quantized "
                             "engines re-quantize on reload); a bare "
                             "tree assignment bypasses the quantize hook")
            elif kind == "calibration":
                # Q801 (calibration side): observers that never saw data
                # — their layers would quantize off a default/stale range
                stale = int(stats.get("uncalibrated_layers", 0))
                if stale <= 0:
                    continue
                out.add("Q801",
                        f"quantization calibration {name!r} left {stale} "
                        f"of {stats.get('layers', '?')} observed layer(s) "
                        f"uncalibrated (no activations recorded) — "
                        f"quantizing them would clip/scale off a never-"
                        f"fitted range and silently wreck those layers' "
                        f"numerics",
                        location=Location(file=name, function=name),
                        hint="run calibration batches through "
                             "PTQ.collect() (or more QAT train steps) "
                             "until every observed layer has statistics "
                             "before calling quantize()/convert()")
        with self._lock:
            conc_sites = {k: dict(v)
                          for k, v in self._concurrency_sites.items()}
        for name, stats in conc_sites.items():
            rule = stats.get("last_rule")
            if rule not in ("C1004", "C1005"):
                continue
            out.add(rule,
                    f"lock sanitizer: {stats.get('last_message', name)} "
                    f"(cumulative: {int(stats.get('cycles', 0))} "
                    f"cycle(s), {int(stats.get('long_holds', 0))} "
                    f"long hold(s))",
                    location=Location(file=name, function=name),
                    hint="see framework/locking.py — fix the acquisition "
                         "order (C1004) or shrink the critical section / "
                         "construct the lock with warn=False when the "
                         "long hold is by design (C1005)")
        with self._lock:
            ten_sites = {k: dict(v) for k, v in self._tenancy_sites.items()}
        for name, stats in ten_sites.items():
            steps = int(stats.get("decode_steps_after_warm", 0))
            # S607 (scheduler side): an IN-budget tenant sustainedly
            # starved after warmup — the weighted-fair order is being
            # defeated (misconfigured weights, a carry full of another
            # tenant's work, or slots pinned by long requests), which is
            # exactly the isolation failure the scheduler exists to
            # prevent.  Over-budget tenants waiting is throttling by
            # design and never fires this.
            for tn, ts in (stats.get("tenants") or {}).items():
                starved = int(ts.get("starved_after_warm", 0))
                if starved <= self.budget or ts.get("over_budget"):
                    continue
                out.add("S607",
                        f"tenant {tn!r} on engine {name} waited through "
                        f"{starved} post-warmup admission passes (budget "
                        f"{self.budget}) while IN budget "
                        f"(weight {ts.get('weight')}, "
                        f"{ts.get('queued', 0)} request(s) queued, "
                        f"{ts.get('admitted', 0)} admitted so far) — "
                        f"weighted-fair admission is failing to protect "
                        f"this tenant's share",
                        location=Location(file=name, function=name),
                        hint="raise the tenant's TenantSpec weight, cap "
                             "the competing tenants' token budgets, or "
                             "add batch_size slots — sustained in-budget "
                             "starvation means demand exceeds the fair "
                             "share the current dials can grant")
            # S607 (adapter side): installed LoRA table entries that no
            # post-warmup decode step ever gathered — dead weights
            # occupying adapter-table HBM on every step's gather
            dead = int(stats.get("adapters_dead", 0))
            if dead > 0 and steps >= 50:
                out.add("S607",
                        f"engine {name} carries {dead} installed LoRA "
                        f"adapter(s) never matched by any request across "
                        f"{steps} post-warmup decode steps "
                        f"({stats.get('adapters_installed', 0)} "
                        f"installed) — dead table entries ride every "
                        f"step's adapter gather and hold table capacity "
                        f"without serving a tenant",
                        location=Location(file=name, function=name),
                        hint="remove_adapter(slot) the unused entries "
                             "(hot, zero recompiles) or fix the tenant "
                             "spec adapter_id wiring so traffic actually "
                             "reaches them")
        return out.diagnostics

    @staticmethod
    def _diff_jit(sigs: List[dict]) -> List[str]:
        causes = []
        n_args = max(len(s.get("args", ())) for s in sigs)
        for i in range(n_args):
            entries = [s["args"][i] for s in sigs
                       if len(s.get("args", ())) > i]
            shapes = [e[1] for e in entries if e[0] == "array"]
            dtypes = [e[2] for e in entries if e[0] == "array"]
            statics = [e[1] for e in entries if e[0] in ("static", "weak")]
            if len(set(shapes)) > 1:
                causes.append(f"arg {i} shape varies: "
                              f"{_churn_axes(shapes)}")
            if len(set(dtypes)) > 1:
                causes.append(f"arg {i} dtype varies: "
                              f"{_churn_axes(dtypes)}")
            if len(set(statics)) > 1:
                causes.append(f"arg {i} static value varies: "
                              f"{_churn_axes(statics)}")
        trainings = [s.get("training") for s in sigs]
        if len(set(trainings)) > 2:
            causes.append("training flag flips repeatedly")
        return causes

    @staticmethod
    def _diff_executor(sigs: List[dict]) -> List[str]:
        causes = []
        feed_names = {n for s in sigs for n in s.get("feeds", {})}
        for n in sorted(feed_names):
            entries = [s["feeds"][n] for s in sigs if n in s.get("feeds", {})]
            shapes = [e[0] for e in entries]
            dtypes = [e[1] for e in entries]
            if len(set(shapes)) > 1:
                causes.append(f"feed {n!r} shape varies: "
                              f"{_churn_axes(shapes)}")
            if len(set(dtypes)) > 1:
                causes.append(f"feed {n!r} dtype varies: "
                              f"{_churn_axes(dtypes)}")
        fetches = [s.get("fetch") for s in sigs]
        if len(set(fetches)) > 1:
            causes.append(f"fetch set varies ({len(set(fetches))} distinct)")
        versions = [s.get("version") for s in sigs]
        if len(set(versions)) > 1:
            causes.append("program grew new ops between runs "
                          f"({len(set(versions))} versions) — ops recorded "
                          "inside the step loop")
        return causes


def _freeze(obj):
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    return obj
