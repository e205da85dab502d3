"""Multi-replica serving control plane — health-checked request router.

The reference framework's Paddle Serving stack put a fleet of
AnalysisPredictor workers behind one endpoint; this module is the
TPU-native equivalent for :class:`~paddle_tpu.serving.InferenceEngine` /
:class:`~paddle_tpu.serving.GenerationEngine` replicas.  One engine crash
(or one stalled device) must not take the serving path down:

* **balancing** — least-outstanding-requests, or power-of-two-choices
  (``policy="p2c"``, the default: pick two random healthy replicas, send
  to the less loaded — near-optimal balance without a global scan);
* **health** — active (a periodic synthetic probe per replica via
  ``engine.synthetic_inputs()``) and passive (request outcomes feed ONE
  ``resilience.CircuitBreaker`` keyed by replica index); an error-rate
  trip marks the replica ``UNHEALTHY``, the cooldown's half-open probes
  re-admit it;
* **failover** — a transient/``UnavailableError`` failure on one replica
  transparently resubmits to another (bounded by the caller's deadline
  and the set of already-attempted replicas), so a replica crash loses
  zero *accepted* requests;
* **hedged requests** — optionally, a duplicate dispatch to a second
  replica after a hedge delay (default: the router's observed p99),
  first result wins; hedge volume is capped by
  ``hedge_budget_frac * requests`` so a latency regression cannot double
  the fleet's load;
* **zero-downtime drain** — :meth:`drain` stops admissions to one
  replica and waits out its in-flight requests;
  :meth:`swap_weights_rolling` drains → swaps → re-probes → re-admits
  one replica at a time (the rest keep serving);
  :meth:`install_sigterm_drain` drains ALL replicas on SIGTERM via
  ``resilience.preemption`` before exiting with the clean-preemption
  code.

Observability: router counters ride ``("serving", <router>)`` snapshots
(``failovers``, ``hedges``/``hedge_wins``/``hedge_denied``,
``replica_flaps``, ``drains``, ``weight_swaps``); per-replica state /
outstanding / probe counters ride ``("router", "<router>[<i>]")`` events
(labeled gauges through the observability bridge).  Analysis rule S602
flags replica flapping and hedge storms after warmup; fault injection
plugs in at the new ``router.dispatch`` site.
"""
from __future__ import annotations

import functools
import threading
import time
import weakref
from concurrent.futures import Future, InvalidStateError
from random import Random
from typing import Callable, Dict, List, Optional, Sequence

from ..framework import trace_events
from ..framework.locking import OrderedLock
from ..framework.errors import (
    ExecutionTimeoutError,
    InvalidArgumentError,
    UnavailableError,
    is_transient,
)
from ..observability import tracing as _tracing
from ..resilience import circuit as _circuit
from ..resilience import retry as _retry_mod
from ..resilience.circuit import CircuitBreaker
from ..resilience.faults import fault_point
from .metrics import ServingMetrics
from .replica import DRAINED, DRAINING, HEALTHY, UNHEALTHY, Replica

__all__ = ["Router"]

_router_counter = [0]

#: router-specific counter schema (zero-initialized in every snapshot)
_ROUTER_COUNTERS = (
    "accepted", "rejected", "failovers", "dispatch_failovers",
    "hedges", "hedge_wins", "hedge_denied", "hedges_after_warm",
    "hedge_denied_after_warm", "replica_flaps", "replica_flaps_after_warm",
    "probes", "probe_failures", "readmissions", "drains", "drain_timeouts",
    "weight_swaps", "scale_up_signals", "scale_down_signals",
    "scale_steady_signals", "scale_hook_errors",
    "replicas_added", "replicas_removed", "peer_evictions",
)

#: live routers, for the profiler "Serving router" summary section
_routers: "weakref.WeakSet" = weakref.WeakSet()


class _Flight:
    """One logical request moving through the router: the caller-facing
    future plus the attempt bookkeeping failover/hedging needs."""

    __slots__ = ("inputs", "kw", "future", "t0", "deadline_t", "attempted",
                 "live", "last_exc", "hedge_timer", "lock", "span")

    def __init__(self, inputs, kw, t0, deadline_t):
        self.inputs = inputs
        self.kw = kw
        self.future: Future = Future()
        self.t0 = t0
        self.deadline_t = deadline_t
        self.attempted = set()   # replica indices tried (failover exclusion)
        self.live = 0            # attempts currently in flight
        self.last_exc = None
        self.hedge_timer = None
        self.lock = OrderedLock("Router._Flight.lock")
        self.span = None         # tracing root span (None unless tracing on)


class Router:
    """Front N serving-engine replicas behind one ``submit``/``infer``.

    ``engines`` — the replica engines (anything with
    ``submit(inputs, deadline_ms=..., **kw) -> Future``; the stock
    ``InferenceEngine``/``GenerationEngine`` qualify).  ``policy`` —
    ``"p2c"`` (power-of-two-choices) or ``"least"`` (full
    least-outstanding scan).  ``probe_interval_s`` — active-health period
    (``None`` disables the background thread; :meth:`probe_now` stays
    available).  ``probe_fn(engine)`` overrides the default synthetic
    probe (``engine.infer(engine.synthetic_inputs())``).  ``hedge`` /
    ``hedge_delay_ms`` / ``hedge_budget_frac`` — hedged-request dials
    (delay ``None`` derives from the router's observed p99).
    ``circuit_kw`` passes through to the per-replica
    :class:`~paddle_tpu.resilience.CircuitBreaker` (window, threshold,
    cooldown, probes, clock).  ``clock`` and ``timer_factory`` are
    injectable for deterministic tests.
    """

    def __init__(self, engines: Sequence, *, name: Optional[str] = None,
                 policy: str = "p2c",
                 failover: bool = True,
                 probe_interval_s: Optional[float] = 5.0,
                 probe_fn: Optional[Callable] = None,
                 probe_timeout_s: float = 30.0,
                 hedge: bool = False,
                 hedge_delay_ms: Optional[float] = None,
                 hedge_budget_frac: float = 0.1,
                 circuit_kw: Optional[dict] = None,
                 seed: int = 0,
                 close_engines: bool = True,
                 clock: Callable[[], float] = time.monotonic,
                 timer_factory: Optional[Callable] = None):
        engines = list(engines)
        if not engines:
            raise InvalidArgumentError("Router needs at least one engine")
        if policy not in ("p2c", "least"):
            raise InvalidArgumentError(
                f"unknown balancing policy {policy!r} (want 'p2c'/'least')")
        if not 0.0 <= float(hedge_budget_frac) <= 1.0:
            raise InvalidArgumentError("hedge_budget_frac must be in [0, 1]")
        if name is None:
            _router_counter[0] += 1
            name = f"router#{_router_counter[0]}"
        self.name = name
        self._policy = policy
        self._failover = bool(failover)
        self._replicas: List[Replica] = [
            Replica(e, i, name) for i, e in enumerate(engines)]
        # membership is dynamic (add_replica/remove_replica): indices are
        # STABLE identities, never recycled — the circuit breaker, the
        # balancing exclusion sets and in-flight callbacks all key on them
        self._by_index: Dict[int, Replica] = {
            r.index: r for r in self._replicas}
        self._next_index = len(engines)
        # Lock order (checked by the C10xx lint + runtime sanitizer):
        # _probe_gate is the OUTER lock (held across whole sweeps and
        # warmup), _lock the INNER one (membership/balancing snapshots,
        # microseconds).  _lock is never held while taking _probe_gate.
        self._lock = OrderedLock("Router._lock")
        self._rng = Random(int(seed))
        self._clock = clock
        self._closing = False
        self._close_engines = bool(close_engines)
        self.metrics = ServingMetrics(name, extra_counters=_ROUTER_COUNTERS)
        self.breaker = CircuitBreaker(f"{name}.replicas",
                                      **(circuit_kw or {}))

        # -- health probing --
        self._probe_fn = probe_fn or self._default_probe
        self._probe_timeout_s = float(probe_timeout_s)
        self._probe_ok = probe_fn is not None or all(
            hasattr(e, "synthetic_inputs")
            and (hasattr(e, "infer") or hasattr(e, "generate"))
            for e in engines)
        self._probe_interval_s = probe_interval_s
        if probe_interval_s is not None and not self._probe_ok:
            raise InvalidArgumentError(
                f"{name}: active probing needs engines with "
                f"synthetic_inputs() + infer()/generate(), or an explicit "
                f"probe_fn=")
        self._stop = threading.Event()
        # lock-order: _probe_gate is held across probe dispatch and whole
        # engine warmups BY DESIGN — it exists to serialize sweeps vs
        # warmup tracing, so its holds are legitimately long (warn=False
        # keeps it cycle-checked without C1005 noise)
        self._probe_gate = OrderedLock("Router._probe_gate", warn=False)
        self._health_thread: Optional[threading.Thread] = None
        if probe_interval_s is not None:
            self._health_thread = threading.Thread(
                target=self._health_loop, name=f"{name}-health", daemon=True)
            self._health_thread.start()

        # -- gang peer liveness (bind_peer_liveness) --
        self._peer_liveness = None

        # -- hedging --
        self._hedge = bool(hedge)
        self._hedge_delay_ms = (float(hedge_delay_ms)
                                if hedge_delay_ms is not None else None)
        self._hedge_budget_frac = float(hedge_budget_frac)
        self._timer_factory = (timer_factory
                               or (lambda d, fn: threading.Timer(d, fn)))

        # -- SLO scale hooks (observability.slo feeds on_scale_signal) --
        self._scale_hooks: List[Callable] = []
        _routers.add(self)

    # -- introspection -------------------------------------------------------
    @property
    def replicas(self) -> List[Replica]:
        return list(self._replicas)

    def replica(self, index: int) -> Replica:
        return self._by_index[index]

    def healthy_count(self) -> int:
        return sum(1 for r in list(self._replicas) if r.state == HEALTHY)

    def stats(self) -> dict:
        snap = self.metrics.snapshot()
        snap.update(self._router_extra())
        snap["replicas_detail"] = {r.name: r.snapshot()
                                   for r in list(self._replicas)}
        return snap

    def _router_extra(self) -> dict:
        return {"router": 1, "replicas": len(self._replicas),
                "healthy": self.healthy_count(),
                "hedge_budget_frac": self._hedge_budget_frac}

    def _publish(self) -> None:
        if trace_events.active():
            self.metrics.publish(self._router_extra())

    def _state_summary(self) -> str:
        return ", ".join(f"{r.name}={r.state}"
                         for r in list(self._replicas))

    # -- balancing -----------------------------------------------------------
    def _pick(self, excluded) -> Optional[int]:
        """Choose a replica for the next attempt, or None when no healthy
        replica remains outside ``excluded``."""
        with self._lock:
            cands = [r for r in self._replicas
                     if r.index not in excluded and r.admits()]
            if not cands:
                return None
            if self._policy == "least" or len(cands) <= 2:
                return min(cands,
                           key=lambda r: (r.outstanding, r.index)).index
            a, b = self._rng.sample(cands, 2)
            return (a if (a.outstanding, a.index) <= (b.outstanding, b.index)
                    else b).index

    # -- dispatch / failover -------------------------------------------------
    @staticmethod
    def _failover_ok(exc: BaseException) -> bool:
        """Replica-side failures worth resubmitting elsewhere: sheds and
        transient device errors.  Client errors (bad shapes) and expired
        deadlines propagate to the caller untouched."""
        return isinstance(exc, UnavailableError) or is_transient(exc)

    def _dispatch(self, fl: _Flight, kind: str, sync: bool = False) -> bool:
        """One attempt (``primary``/``failover``/``hedge``): pick a
        replica, submit, register the completion callback.  Sync mode
        (the caller's submit) raises on failure; async mode fails the
        flight's future — except for hedges, which are opportunistic and
        abort silently (the primary attempt still owns the flight)."""
        last = fl.last_exc
        while True:
            if fl.deadline_t is not None and self._clock() >= fl.deadline_t:
                exc = last if last is not None else ExecutionTimeoutError(
                    f"{self.name}: deadline exhausted during {kind} "
                    f"dispatch")
                if kind == "hedge":
                    return False
                if sync:
                    raise exc
                self._fail(fl, exc)
                return False
            idx = self._pick(fl.attempted)
            if idx is None:
                exc = last if last is not None else UnavailableError(
                    f"{self.name}: no healthy replica available "
                    f"({self._state_summary()})")
                if kind == "hedge":
                    return False
                if sync:
                    raise exc
                self._fail(fl, exc)
                return False
            with self._lock:
                rep = self._by_index.get(idx)
            if rep is None:
                continue  # removed between pick and dispatch: repick
            fl.attempted.add(idx)
            remaining = None
            if fl.deadline_t is not None:
                remaining = max((fl.deadline_t - self._clock()) * 1e3, 0.0)
            # one sibling span per attempt — primary/failover/hedge all
            # share the root, annotated with their outcome on close
            tr = _tracing._active
            aspan = (tr.start_span("router/dispatch", fl.span.context(),
                                   kind=kind, replica=rep.name)
                     if tr is not None and fl.span is not None else None)
            try:
                fault_point("router.dispatch")
                if aspan is not None:
                    # trace_ctx only when an attempt span exists: engines
                    # unaware of tracing never see the kwarg
                    fut = rep.engine.submit(fl.inputs,
                                            deadline_ms=remaining,
                                            trace_ctx=aspan.context(),
                                            **fl.kw)
                else:
                    fut = rep.engine.submit(fl.inputs,
                                            deadline_ms=remaining, **fl.kw)
            except Exception as e:  # noqa: BLE001 — classified below
                last = e
                if aspan is not None:
                    aspan.end(
                        outcome=f"dispatch_error:{type(e).__name__}")
                if self._failover_ok(e):
                    self._record_outcome(rep, ok=False)
                    self.metrics.incr("dispatch_failovers")
                    continue  # next candidate
                if kind == "hedge":
                    return False
                if sync:
                    raise
                self._fail(fl, e)
                return False
            with fl.lock:
                fl.live += 1
            rep.begin(kind)
            fut.add_done_callback(
                functools.partial(self._on_done, fl, rep, kind, aspan))
            return True

    def _on_done(self, fl: _Flight, rep: Replica, kind: str, aspan,
                 fut: Future) -> None:
        exc = fut.exception()
        rep.end(ok=exc is None)
        with fl.lock:
            fl.live -= 1
            live = fl.live
        if exc is None:
            self._record_outcome(rep, ok=True)
            try:
                fl.future.set_result(fut.result())
            except InvalidStateError:
                # another attempt already won this flight — the losing
                # attempt keeps its span (outcome=lost) but must not
                # touch completion counters or latency quantiles
                if aspan is not None:
                    aspan.end(outcome="lost")
                rep.count("lost_races")
                return
            timer = fl.hedge_timer
            if timer is not None:
                try:
                    timer.cancel()
                except Exception:  # noqa: BLE001 — cancel is best-effort
                    pass
            self.metrics.incr("completed")
            if kind == "hedge":
                self.metrics.incr("hedge_wins")
            self.metrics.observe_latency_ms((self._clock() - fl.t0) * 1e3)
            if aspan is not None:
                aspan.end(outcome="ok")
            if fl.span is not None:
                fl.span.end(outcome="ok", winner=kind)
            self._publish()
            return
        if aspan is not None:
            aspan.end(outcome=f"error:{type(exc).__name__}")
        eligible = self._failover_ok(exc)
        if eligible:
            self._record_outcome(rep, ok=False)
        with fl.lock:
            fl.last_exc = exc
        if fl.future.done():
            return
        if live > 0:
            return  # a hedge/primary sibling is still running — let it win
        if eligible and self._failover:
            self.metrics.incr("failovers")
            self._dispatch(fl, kind="failover", sync=False)
            return
        self._fail(fl, exc)

    def _fail(self, fl: _Flight, exc: BaseException) -> None:
        self.metrics.incr("errors")
        try:
            fl.future.set_exception(exc)
        except InvalidStateError:
            pass
        if fl.span is not None:  # idempotent: a won flight already closed
            fl.span.end(outcome=f"error:{type(exc).__name__}")
        self._publish()

    # -- passive health ------------------------------------------------------
    def _record_outcome(self, rep: Replica, ok: bool) -> None:
        if rep.state != HEALTHY:
            # stragglers finishing on an UNHEALTHY/DRAINING replica must
            # not pollute the half-open probe accounting — recovery is
            # probe-driven
            return
        if ok:
            self.breaker.record_success(rep.index)
            return
        self.breaker.record_failure(rep.index)
        if self.breaker.state(rep.index) != _circuit.CLOSED:
            self._mark_unhealthy(rep)

    def _mark_unhealthy(self, rep: Replica) -> None:
        old = rep.set_state(UNHEALTHY)
        if old == UNHEALTHY:
            return
        self.metrics.incr("replica_flaps")
        if _retry_mod.is_warm():
            self.metrics.incr("replica_flaps_after_warm")
        self._publish()

    # -- active health -------------------------------------------------------
    def _default_probe(self, engine) -> None:
        sample = engine.synthetic_inputs()
        t = self._probe_timeout_s
        if hasattr(engine, "generate"):
            # deadline-bound the queued side too: a probe against a busy
            # continuous-batching engine self-expires instead of lingering
            # as a ghost request that later burns a decode slot
            engine.submit(sample, 1, deadline_ms=t * 1e3).result(t)
        else:
            engine.infer(sample, timeout=t)

    def _run_probe(self, rep: Replica) -> bool:
        self.metrics.incr("probes")
        rep.count("probes")
        try:
            self._probe_fn(rep.engine)
            return True
        except Exception:  # noqa: BLE001 — any probe failure is a vote
            self.metrics.incr("probe_failures")
            rep.count("probe_failures")
            return False

    def bind_peer_liveness(self, monitor, replica_to_process) -> None:
        """Wire a gang peer monitor into replica health: a replica whose
        owning host process goes lost (``monitor.lost_workers()``) is
        marked unhealthy on the next sweep — milliseconds after the
        heartbeat verdict — instead of waiting for its probe/request
        timeouts to burn down.  ``replica_to_process`` maps replica index
        → ``process_index`` of the host that owns that engine (replicas
        on THIS host need no entry).  Recovery stays probe-driven: when
        the host returns and its engine answers probes again, the normal
        half-open path readmits the replica."""
        self._peer_liveness = (monitor, dict(replica_to_process))

    def _peer_sweep(self) -> None:
        if self._peer_liveness is None:
            return
        monitor, mapping = self._peer_liveness
        try:
            lost = set(monitor.lost_workers())
        except Exception:  # noqa: BLE001 — liveness is advisory
            return
        if not lost:
            return
        for rep in list(self._replicas):
            if mapping.get(rep.index) in lost and rep.state == HEALTHY:
                self.metrics.incr("peer_evictions")
                self._mark_unhealthy(rep)

    def probe_now(self) -> None:
        """One synchronous health sweep (the background thread runs this
        every ``probe_interval_s``): active-probe healthy replicas, and
        offer half-open recovery probes to unhealthy ones."""
        from ..distributed import heartbeat
        heartbeat.maybe_beat()  # serving liveness rides the same transport
        with self._probe_gate:
            self._peer_sweep()
            self._probe_sweep()

    def _probe_sweep(self) -> None:
        for rep in list(self._replicas):
            if self._closing:
                return
            st = rep.state
            if st in (DRAINING, DRAINED):
                continue
            if st == UNHEALTHY:
                if not self.breaker.allow(rep.index):
                    continue  # still cooling down (the shed is counted)
                if not self._probe_ok:
                    # no synthetic probe available: optimistic half-open —
                    # re-admit and let live traffic vote
                    rep.set_state(HEALTHY)
                    self.metrics.incr("readmissions")
                    continue
                if self._run_probe(rep):
                    self.breaker.record_success(rep.index)
                    if self.breaker.state(rep.index) == _circuit.CLOSED:
                        rep.set_state(HEALTHY)
                        self.metrics.incr("readmissions")
                else:
                    self.breaker.record_failure(rep.index)  # re-opens
            elif self._probe_ok:
                self._record_outcome(rep, ok=self._run_probe(rep))
            rep.publish()
        self._publish()

    def _health_loop(self) -> None:
        # Event.wait, not time.sleep: close() interrupts the pause
        while not self._stop.wait(self._probe_interval_s):
            try:
                self.probe_now()
            except Exception:  # noqa: BLE001 — a sweep must never kill
                pass           # the health thread

    # -- hedging -------------------------------------------------------------
    def _hedge_delay_s(self) -> Optional[float]:
        if self._hedge_delay_ms is not None:
            return self._hedge_delay_ms / 1e3
        p99 = self.metrics.snapshot()["p99_ms"]
        return p99 / 1e3 if p99 > 0 else None

    def _maybe_schedule_hedge(self, fl: _Flight) -> None:
        if not self._hedge or len(self._replicas) < 2:
            return
        if fl.future.done():
            return  # synchronous completion: nothing left to hedge
        delay = self._hedge_delay_s()
        if delay is None or delay <= 0:
            return  # no latency signal yet — nothing to hedge against
        timer = self._timer_factory(delay, lambda: self._fire_hedge(fl))
        fl.hedge_timer = timer
        if hasattr(timer, "daemon"):
            timer.daemon = True
        timer.start()

    def _fire_hedge(self, fl: _Flight) -> None:
        if fl.future.done() or self._closing:
            return
        snap = self.metrics.snapshot()
        # budget: at least one hedge is always allowed, then the hedge
        # count may not exceed hedge_budget_frac of admitted requests —
        # a fleet-wide latency shift cannot double the offered load
        if snap["hedges"] >= max(1.0,
                                 self._hedge_budget_frac * snap["requests"]):
            self.metrics.incr("hedge_denied")
            if _retry_mod.is_warm():
                self.metrics.incr("hedge_denied_after_warm")
            self._publish()
            return
        self.metrics.incr("hedges")
        if _retry_mod.is_warm():
            self.metrics.incr("hedges_after_warm")
        self._dispatch(fl, kind="hedge", sync=False)

    # -- public API ----------------------------------------------------------
    def submit(self, inputs, deadline_ms: Optional[float] = None,
               **engine_kw) -> Future:
        """Route one request to a healthy replica; returns a Future of
        that engine's per-request result.  Raises (request NOT accepted)
        only when no healthy replica will take it; once accepted, replica
        failures fail over transparently within the caller's deadline."""
        if self._closing:
            raise UnavailableError(f"{self.name}: router closed")
        self.metrics.incr("requests")
        t0 = self._clock()
        deadline_t = (t0 + deadline_ms / 1e3
                      if deadline_ms is not None else None)
        fl = _Flight(inputs, engine_kw, t0, deadline_t)
        tr = _tracing._active
        if tr is not None:
            fl.span = tr.start_trace("router/submit", kind="request",
                                     router=self.name)
        try:
            self._dispatch(fl, kind="primary", sync=True)
        except Exception as e:
            self.metrics.incr("rejected")
            if fl.span is not None:
                fl.span.end(outcome=f"rejected:{type(e).__name__}")
            self._publish()
            raise
        self.metrics.incr("accepted")
        self._maybe_schedule_hedge(fl)
        return fl.future

    def infer(self, inputs, timeout: Optional[float] = None, **engine_kw):
        """Blocking :meth:`submit`."""
        return self.submit(inputs, **engine_kw).result(timeout)

    # -- SLO scale signals ---------------------------------------------------
    def register_scale_hook(self, fn: Callable) -> Callable:
        """Register ``fn(signal)`` for every :meth:`on_scale_signal`
        delivery (the seam a fleet autoscaler plugs into); returns ``fn``
        so it can be used as a decorator."""
        self._scale_hooks.append(fn)
        return fn

    def on_scale_signal(self, signal) -> None:
        """Accept one ``observability.slo.ScaleSignal`` (the registration
        hook ``SloEngine.bind_router`` wires up): count it, publish the
        non-steady verdicts, and fan out to the registered hooks.  The
        router does not resize itself — replica count is the deployment
        layer's call; this is the audited hand-off point."""
        key = {"up": "scale_up_signals", "down": "scale_down_signals"}.get(
            getattr(signal, "direction", "steady"), "scale_steady_signals")
        self.metrics.incr(key)
        errs = 0
        for fn in list(self._scale_hooks):
            try:
                fn(signal)
            except Exception:  # noqa: BLE001 — a broken hook must not
                errs += 1      # break delivery to the other hooks, but a
                #                dead autoscaler has to be VISIBLE:
                #                scale_hook_errors rides router_stats()
        if errs:
            self.metrics.incr("scale_hook_errors", errs)
        if key != "scale_steady_signals" or errs:
            self._publish()

    def warmup(self) -> int:
        """Warm every replica engine (close its compile set), then run one
        probe sweep; returns the summed compile count."""
        # _probe_gate keeps the background sweep out while engines trace:
        # a probe compiling through a replica's batcher thread concurrently
        # with warmup tracing (possibly over a shared model) leaks tracers
        total = 0
        with self._probe_gate:
            for rep in list(self._replicas):
                if hasattr(rep.engine, "warmup"):
                    total += int(rep.engine.warmup() or 0)
        if self._probe_ok:
            self.probe_now()
        return total

    # -- fleet membership (the ReplicaPool actuator's primitives) ------------
    def add_replica(self, engine, *, probe: bool = True) -> int:
        """Grow the fleet by one engine, entering through the half-open
        probe/admit path: the replica joins in DRAINED state (invisible
        to balancing), then :meth:`admit` probes it and flips it HEALTHY
        — live traffic never sees a replica that has not answered a
        probe.  The caller is responsible for warming the engine OFF the
        serving path first (``ReplicaPool`` does AOT warmup before
        calling this).  Returns the new replica's stable index; raises
        ``UnavailableError`` (and backs the replica out) when the
        admission probe fails."""
        if self._closing:
            raise UnavailableError(f"{self.name}: router closed")
        if engine is None:
            raise InvalidArgumentError("add_replica needs an engine")
        probe_able = (self._probe_fn is not self._default_probe
                      or (hasattr(engine, "synthetic_inputs")
                          and (hasattr(engine, "infer")
                               or hasattr(engine, "generate"))))
        if self._probe_interval_s is not None and not probe_able:
            raise InvalidArgumentError(
                f"{self.name}: active probing is on — a new replica needs "
                f"synthetic_inputs() + infer()/generate()")
        with self._lock:
            idx = self._next_index
            self._next_index += 1
            rep = Replica(engine, idx, self.name)
            rep.set_state(DRAINED)  # joins via admit(), not directly
            self._replicas.append(rep)
            self._by_index[idx] = rep
        self.metrics.incr("replicas_added")
        if not self.admit(idx, probe=probe and self._probe_ok):
            with self._lock:
                self._by_index.pop(idx, None)
                try:
                    self._replicas.remove(rep)
                except ValueError:  # pragma: no cover - concurrent remove
                    pass
            self._publish()
            raise UnavailableError(
                f"{self.name}: new replica {rep.name} failed its "
                f"admission probe and was backed out")
        return idx

    def remove_replica(self, index: int, *, drain: bool = True,
                       timeout: Optional[float] = None,
                       close_engine: bool = False) -> bool:
        """Retire replica ``index`` through the graceful-drain machinery:
        stop admissions, wait out its in-flight requests, then drop it
        from the fleet (its circuit-breaker key resets; the index is
        never recycled).  On drain timeout the replica is restored to
        HEALTHY and the method returns False — a capacity hole beats
        lost in-flight work.  ``close_engine=True`` also closes the
        engine after removal (the pool closes engines it owns)."""
        rep = self._by_index[index]
        if drain and rep.state != DRAINED:
            if not self.drain(index, timeout=timeout):
                rep.set_state(HEALTHY)
                self._publish()
                return False
        with self._lock:
            self._by_index.pop(index, None)
            try:
                self._replicas.remove(rep)
            except ValueError:  # pragma: no cover - concurrent remove
                pass
        self.breaker.reset(index)
        self.metrics.incr("replicas_removed")
        if close_engine:
            close = getattr(rep.engine, "close", None)
            if close is not None:
                try:
                    close(drain=drain, timeout=timeout)
                except TypeError:
                    close()
        self._publish()
        return True

    # -- drain / rolling swap ------------------------------------------------
    def drain(self, index: int, timeout: Optional[float] = None) -> bool:
        """Stop admissions to replica ``index`` and wait out its
        in-flight requests.  Returns False on timeout (state stays
        DRAINING; the replica keeps finishing its backlog)."""
        rep = self._by_index[index]
        rep.set_state(DRAINING)
        self.metrics.incr("drains")
        ok = rep.wait_idle(timeout)
        if ok:
            rep.set_state(DRAINED)
        else:
            self.metrics.incr("drain_timeouts")
        self._publish()
        return ok

    def admit(self, index: int, probe: bool = True) -> bool:
        """Re-admit a drained/unhealthy replica: optional synthetic
        probe, then a fresh circuit window and HEALTHY state.  Returns
        False (replica stays out) when the probe fails."""
        rep = self._by_index[index]
        if probe and self._probe_ok and not self._run_probe(rep):
            return False
        self.breaker.reset(rep.index)
        rep.set_state(HEALTHY)
        self.metrics.incr("readmissions")
        self._publish()
        return True

    def drain_all(self, timeout: Optional[float] = None) -> bool:
        """Stop admissions everywhere, then wait out every replica's
        in-flight requests (the SIGTERM path)."""
        reps = list(self._replicas)
        for rep in reps:
            rep.set_state(DRAINING)
        self.metrics.incr("drains", len(reps))
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        ok = True
        for rep in reps:
            remaining = None
            if deadline is not None:
                remaining = max(deadline - time.monotonic(), 0.0)
            if rep.wait_idle(remaining):
                rep.set_state(DRAINED)
            else:
                ok = False
                self.metrics.incr("drain_timeouts")
        self._publish()
        return ok

    def swap_weights_rolling(self, params_file: Optional[str] = None, *,
                             swap_fn: Optional[Callable] = None,
                             drain_timeout: Optional[float] = None,
                             probe: bool = True) -> int:
        """Zero-downtime rolling weight update: one replica at a time —
        stop admissions, finish in-flight, swap (``engine.swap_weights
        (params_file)`` or ``swap_fn(engine)``), re-probe, re-admit —
        while the remaining replicas keep serving.  No request ever
        observes a half-swapped replica (the drain barrier) and the swap
        compiles nothing (weights stay executable arguments)."""
        if swap_fn is None:
            if params_file is None:
                raise InvalidArgumentError(
                    "swap_weights_rolling needs params_file= or swap_fn=")

            def swap_fn(engine):
                engine.swap_weights(params_file)
        swapped = 0
        for rep in list(self._replicas):
            if not self.drain(rep.index, timeout=drain_timeout):
                # abort: an un-swapped replica serving old weights beats
                # a hole in capacity
                rep.set_state(HEALTHY)
                raise UnavailableError(
                    f"{self.name}: rolling swap aborted — {rep!r} did not "
                    f"drain within {drain_timeout}s")
            try:
                swap_fn(rep.engine)
            except Exception:
                rep.set_state(HEALTHY)  # swap validates before it mutates
                raise
            if not self.admit(rep.index, probe=probe):
                raise UnavailableError(
                    f"{self.name}: rolling swap halted — {rep.name} failed "
                    f"its re-admission probe and stays drained")
            swapped += 1
            self.metrics.incr("weight_swaps")
        self._publish()
        return swapped

    def install_sigterm_drain(self, timeout: Optional[float] = None,
                              checkpoint=None):
        """SIGTERM → drain every replica (admissions stop, in-flight
        requests finish) → optional final checkpoint → exit with the
        clean-preemption code ``resilience.preemption`` and the watchdog
        agree on.  Returns the installed handler (uninstall() to
        remove)."""
        from ..resilience.preemption import PreemptionHandler
        return PreemptionHandler(
            checkpoint,
            on_preempt=lambda: self.drain_all(timeout)).install()

    # -- shutdown ------------------------------------------------------------
    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        """Stop admissions and the health thread; optionally drain every
        replica, then close the engines (when the router owns them)."""
        self._closing = True
        self._stop.set()
        if self._health_thread is not None:
            self._health_thread.join(
                timeout=(self._probe_interval_s or 0) + 1)
            self._health_thread = None
        if drain:
            self.drain_all(timeout)
        if self._close_engines:
            for rep in list(self._replicas):
                close = getattr(rep.engine, "close", None)
                if close is None:
                    continue
                try:
                    close(drain=drain, timeout=timeout)
                except TypeError:
                    close()
        self._publish()
        # a closed router is not a live one: it leaves the profiler's
        # summary now, not whenever the collector gets to it
        _routers.discard(self)

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# -- profiler "Serving router" summary section --------------------------------
def _summary_section() -> str:
    lines = []
    for r in sorted(list(_routers), key=lambda r: r.name):
        snap = r.metrics.snapshot()
        lines.append(
            f"  router {r.name:<16} replicas {len(r.replicas)} "
            f"(healthy {r.healthy_count()})  requests {snap['requests']:>6}"
            f"  failovers {snap['failovers'] + snap['dispatch_failovers']:>4}"
            f"  hedges {snap['hedges']:>4} ({snap['hedge_wins']} wins, "
            f"{snap['hedge_denied']} denied)  flaps "
            f"{snap['replica_flaps']:>3}  drains {snap['drains']:>3}  "
            f"swaps {snap['weight_swaps']:>3}")
    if not lines:
        return ""
    return "\n".join(["Serving router"] + lines)


def _register_profiler_section() -> None:
    from .. import profiler
    profiler.register_summary_section(_summary_section)


_register_profiler_section()
