"""Generic measured-search engine — the core the sharding-plan tuner and
the serving-config tuner share.

The search loop (it began as PR 4's search over Pallas tile sizes; the
kernels left it in PR 48, each for a rule of its shape, because a race
between near-ties draws differently in every cold checkout) takes ANY
config space:

* **candidate enumeration** is the client's (a list, or a lazy callable
  so cache hits never pay enumeration);
* **validity pre-filter** rejects candidates before any compile (the
  plan client filters through ``analysis.check_plan.is_valid_plan``);
* **compile + time on the real backend** via :func:`measure_ms` — an
  untimed warm call first (absorbs compilation), then best-of-N wall
  times, so dispatch jitter can't crown a flaky winner;
* **persistent JSON cache** keyed ``space | client key | device kind``
  where the client key carries the shape bucket and (for distributed
  spaces) the mesh — entries carry ``version``/``space``/``name``
  fields (schema v2); stale entries (pre-versioned ones, and a
  ``kernel`` entry from before PR 48) are ignored, never a crash, and
  :func:`clear_cache` can scope a wipe to one space;
* **counters and trace events**: every resolution publishes an
  ``("autotune", name)`` event with the space attached, so
  ``analysis.RetraceMonitor`` raises K701 for ANY measured search after
  :func:`mark_warm` — plan or serving — and the profiler grows one
  "Measured search" summary section covering both.

Clients: ``tuning.plan_space`` (``"plan"``), ``tuning.serving_space``
(``"serving"``).
"""
from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..framework import trace_events
from ..framework.errors import InvalidArgumentError
from ..framework.flags import flag

__all__ = [
    "SCHEMA_VERSION", "SPACES", "resolve", "measure_ms", "cache_path",
    "clear_cache", "get_counters", "reset_counters", "mark_warm", "is_warm",
    "reset_warm", "bucket_shape", "next_pow2", "device_kind", "mesh_key",
    "CandidateError",
]

#: disk-cache entry schema.  v1 entries (PR 4's kernel-only format, no
#: ``version``/``space`` fields) are ignored on load — a stale cache
#: degrades to a re-search, never a crash.
SCHEMA_VERSION = 2

#: the registered config spaces (informational; the engine accepts any
#: space string, these are the ones shipped in-tree)
SPACES = ("plan", "serving")

_lock = threading.RLock()
_mem_cache: Dict[str, dict] = {}          # spaced key -> config
_heuristic_cache: Dict[str, dict] = {}    # spaced key -> untimed default
_counters: Dict[str, Dict[str, int]] = {}  # client name -> counters
_spaces: Dict[str, str] = {}               # client name -> space
_warm = False                              # set by serving warmup; see K701

_disk_state = {"path": None, "entries": None}  # lazily-loaded JSON cache

_COUNTER_KEYS = ("hits", "disk_hits", "searches", "heuristic",
                 "configs_timed", "search_failures", "searches_after_warm",
                 "prefiltered")


class CandidateError(Exception):
    """Raised by a measure callback to reject one candidate (fails to
    lower, violates a latency budget, …) without aborting the search."""


# -- keys --------------------------------------------------------------------
def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def bucket_shape(shape) -> Tuple[int, ...]:
    """Shape bucket for cache keys: each dim rounds up to a power of two,
    so nearby geometries (ragged batches, serving buckets) share one
    entry.  Clients clamp configs to the real shape at use time, so a
    winner from a larger bucket member stays valid."""
    return tuple(next_pow2(d) for d in shape)


def device_kind() -> str:
    import jax

    try:
        return jax.devices()[0].device_kind
    except Exception:  # backend not initialized / unreachable
        return jax.default_backend()


def mesh_key(mesh=None) -> str:
    """Stable mesh component for plan/serving cache keys: axis sizes in
    canonical order (``pipe1.data8.sharding1.sep1.model1``).  Accepts any
    object with a ``.shape`` mapping (a real ``jax.sharding.Mesh`` or a
    test stub); ``None`` reads the active global mesh."""
    if mesh is None:
        from ..distributed.mesh import get_mesh

        mesh = get_mesh()
    shape = dict(mesh.shape)
    return ".".join(f"{a}{shape[a]}" for a in sorted(shape))


def _spaced(space: str, key: str) -> str:
    return f"{space}|{key}"


# -- persistent cache --------------------------------------------------------
def cache_path() -> Optional[str]:
    """Resolved on-disk cache path (``FLAGS_kernel_tuning_cache`` — one
    file holds every space's winners), or ``None`` when persistence is
    disabled."""
    val = str(flag("kernel_tuning_cache") or "").strip()
    if val.lower() in ("0", "off", "none", "false", "disabled"):
        return None
    if not val:
        from ..sysconfig import cache_root

        return os.path.join(cache_root(), "kernel_tuning.json")
    return val


def _valid_entry(v) -> bool:
    """Schema filter: v2+ entries only.  PR-4-era kernel entries carry no
    ``version`` field — they key differently anyway (no space prefix), so
    they are dropped rather than trusted across the schema change; so is
    a ``kernel`` entry of the tile search that PR 48 removed."""
    return (isinstance(v, dict) and "config" in v
            and isinstance(v.get("version"), int)
            and v["version"] >= SCHEMA_VERSION
            and v.get("space") != "kernel")


def _disk_entries() -> Dict[str, dict]:
    """The loaded disk cache, reloaded when the flag re-points it.
    Stale-schema entries are ignored (never a crash)."""
    path = cache_path()
    if path is None:
        return {}
    if _disk_state["path"] != path or _disk_state["entries"] is None:
        entries = {}
        try:
            with open(path) as f:
                data = json.load(f)
            if isinstance(data, dict):
                entries = {k: v for k, v in data.get("entries", {}).items()
                           if _valid_entry(v)}
        except (OSError, ValueError):
            entries = {}
        _disk_state["path"] = path
        _disk_state["entries"] = entries
    return _disk_state["entries"]


def _disk_store(spaced_key: str, space: str, name: str, config: dict,
                best_ms: float) -> None:
    path = cache_path()
    if path is None:
        return
    entries = dict(_disk_entries())
    # merge with concurrent writers: reread before rewrite (stale-schema
    # entries on disk are dropped, not re-persisted)
    try:
        with open(path) as f:
            on_disk = json.load(f).get("entries", {})
        if isinstance(on_disk, dict):
            entries = {**{k: v for k, v in on_disk.items()
                          if _valid_entry(v)}, **entries}
    except (OSError, ValueError):
        pass
    entry = {"space": space, "name": name, "config": dict(config),
             "best_ms": round(float(best_ms), 4),
             "version": SCHEMA_VERSION}
    entries[spaced_key] = entry
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": SCHEMA_VERSION, "entries": entries}, f,
                      indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        return  # read-only cache dir: winners stay process-local
    _disk_state["path"] = path
    _disk_state["entries"] = entries


def _entry_space(key: str, entry: dict) -> str:
    return entry.get("space") or key.split("|", 1)[0]


def clear_cache(memory: bool = True, disk: bool = False,
                space: Optional[str] = None) -> None:
    """Drop tuned winners.  ``disk=True`` also clears the JSON file;
    ``space`` scopes the wipe to one config space (``"plan"`` /
    ``"serving"``) so re-tuning sharding plans doesn't cost the serving
    winners, and vice versa."""
    with _lock:
        if memory:
            if space is None:
                _mem_cache.clear()
                _heuristic_cache.clear()
            else:
                pre = _spaced(space, "")
                for cache in (_mem_cache, _heuristic_cache):
                    for k in [k for k in cache if k.startswith(pre)]:
                        del cache[k]
        _disk_state["path"] = None
        _disk_state["entries"] = None
    if not disk:
        return
    path = cache_path()
    if path is None:
        return
    if space is None:
        try:
            os.remove(path)
        except OSError:
            pass
        return
    # scope-aware disk clear: rewrite the file without that space's
    # entries (stale-schema entries are dropped along the way)
    try:
        with open(path) as f:
            on_disk = json.load(f).get("entries", {})
    except (OSError, ValueError):
        return
    keep = {k: v for k, v in on_disk.items()
            if _valid_entry(v) and _entry_space(k, v) != space}
    try:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": SCHEMA_VERSION, "entries": keep}, f,
                      indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


# -- counters / warm state ---------------------------------------------------
def _bump(name: str, field: str, n: int = 1) -> Dict[str, int]:
    c = _counters.setdefault(name, {k: 0 for k in _COUNTER_KEYS})
    c[field] += n
    return c


def get_counters(name: Optional[str] = None) -> Dict:
    """Counter snapshot(s): one client's dict, or ``{name: dict}``."""
    with _lock:
        if name is not None:
            return dict(_counters.get(name, {k: 0 for k in _COUNTER_KEYS}))
        return {k: dict(v) for k, v in _counters.items()}


def reset_counters() -> None:
    with _lock:
        _counters.clear()


def mark_warm() -> None:
    """Declare tuning warmup over (serving engines call this after
    ``warmup()``): any measured search past this point — a sharding
    plan, serving dials — is tuning work on a hot path, a cache
    miss the pre-warmed JSON cache should have absorbed, and is flagged
    by analysis rule K701."""
    global _warm
    with _lock:
        _warm = True


def is_warm() -> bool:
    return _warm


def reset_warm() -> None:
    """Reset the warm flag (tests / engine restarts)."""
    global _warm
    with _lock:
        _warm = False


def _publish(space: str, name: str, event: str, key: str, config: dict,
             **extra):
    with _lock:
        counters = dict(_counters.get(name, {k: 0 for k in _COUNTER_KEYS}))
        warm = _warm
    if trace_events.active():
        info = {"event": event, "key": key, "config": dict(config),
                "space": space, "warm": warm, "counters": counters}
        info.update(extra)
        trace_events.notify(("autotune", name), info)


# -- measurement -------------------------------------------------------------
def measure_ms(fn: Callable, args: Sequence = (), repeats: int = 3) -> float:
    """Wall-time ``fn(*args)``: one UNTIMED warm call first (absorbs
    compile + first-dispatch costs), then best-of-``repeats`` — a single
    timing would let dispatch jitter crown a flaky winner.  Results with
    device buffers are blocked on, so async dispatch can't hide work."""
    import jax

    def run():
        out = fn(*args)
        if out is not None:
            try:
                jax.block_until_ready(out)
            except (TypeError, ValueError):
                pass  # host-only result: fn blocked internally
        return out

    run()  # warm: compile + first dispatch, never timed
    best = math.inf
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _canonical(cfg: dict) -> dict:
    return {k: int(v) if isinstance(v, (bool, np.integer, int)) else v
            for k, v in cfg.items()}


def dedup_candidates(cands: Sequence[dict], default: dict) -> List[dict]:
    """Canonicalize + dedup a candidate list; the default is always in
    the running (appended last so an explicit duplicate keeps its spot)."""
    seen, out = set(), []
    for c in list(cands) + [default]:
        c = _canonical(c)
        sig = tuple(sorted((k, repr(v)) for k, v in c.items()))
        if sig not in seen:
            seen.add(sig)
            out.append(c)
    return out


# -- resolution --------------------------------------------------------------
def resolve(space: str, name: str, key: str, *,
            candidates: Union[Sequence[dict], Callable[[], Sequence[dict]]],
            measure: Callable[[dict], float],
            heuristic: Union[dict, Callable[[], dict]],
            measurable: bool,
            prefilter: Optional[Callable[[dict], bool]] = None,
            details: Optional[dict] = None) -> dict:
    """Resolve one config: in-memory hit → disk hit → measured search →
    untimed default.

    ``candidates`` — the config dicts to race (or a callable returning
    them, evaluated only when a search actually runs); the default MUST
    be in the list so the search can never do worse than the hand-set
    config.  ``measure(cand) -> ms`` times one candidate (lower is
    better; raise :class:`CandidateError` to reject it).  ``heuristic``
    is the untimed default used off-backend.  A search in which EVERY
    measured candidate raises re-raises the first candidate's error (a
    loser among winners is skipped and counted in ``search_failures``).
    ``prefilter(cand) -> bool`` drops invalid candidates before any
    compile.  ``details`` (optional dict) is filled with the search
    outcome (event, best_ms, default_ms, per-candidate timings) for
    gates that assert on measurements."""
    if not space or "|" in space:
        raise InvalidArgumentError(f"bad search space name {space!r}")
    skey = _spaced(space, key)

    def note(**kw):
        if details is not None:
            details.update(kw)

    with _lock:
        _spaces[name] = space
        cfg = _mem_cache.get(skey)
        if cfg is None and not measurable:
            cfg = _heuristic_cache.get(skey)
        if cfg is not None:
            _bump(name, "hits")
    if cfg is not None:
        _publish(space, name, "hit", key, cfg)
        note(event="hit", config=dict(cfg))
        return dict(cfg)

    default = heuristic() if callable(heuristic) else dict(heuristic)
    default = _canonical(default)

    if not measurable:
        with _lock:
            _heuristic_cache[skey] = dict(default)
            _bump(name, "heuristic")
        _publish(space, name, "heuristic", key, default)
        note(event="heuristic", config=dict(default))
        return dict(default)

    disk = _disk_entries().get(skey)
    if disk is not None:
        cfg = dict(disk["config"])
        with _lock:
            _mem_cache[skey] = cfg
            _bump(name, "disk_hits")
        _publish(space, name, "disk_hit", key, cfg)
        note(event="disk_hit", config=dict(cfg),
             best_ms=disk.get("best_ms"))
        return dict(cfg)

    # -- measured search ------------------------------------------------------
    from .. import profiler

    cands = dedup_candidates(
        candidates() if callable(candidates) else candidates, default)
    dsig = tuple(sorted((k, repr(v)) for k, v in default.items()))
    best_cfg, best_ms, default_ms = dict(default), math.inf, None
    timed, dropped, timings, first_error = 0, 0, [], None
    with profiler.RecordEvent(f"measured_search/{space}/{name}"):
        for cand in cands:
            if prefilter is not None and not prefilter(cand):
                dropped += 1
                with _lock:
                    _bump(name, "prefiltered")
                continue
            try:
                ms = float(measure(cand))
            except Exception as e:  # a loser among winners: skip + count
                if first_error is None:
                    first_error = e
                with _lock:
                    _bump(name, "search_failures")
                timings.append({"config": dict(cand), "ms": None})
                continue
            timed += 1
            timings.append({"config": dict(cand), "ms": round(ms, 4)})
            if tuple(sorted((k, repr(v)) for k, v in cand.items())) == dsig:
                default_ms = ms
            if ms < best_ms:
                best_cfg, best_ms = dict(cand), ms
    if timed == 0 and first_error is not None:
        # EVERY candidate failed to compile or run: that is a broken
        # kernel (or a space whose filters admit nothing the compiler
        # takes), not a tuning outcome.  Returning the heuristic here
        # would only move the same failure into the caller's jit, far
        # from its cause.
        raise first_error
    if timed == 0:  # the prefilter dropped everything: untimed default
        with _lock:
            _bump(name, "heuristic")
        _publish(space, name, "heuristic", key, default,
                 note="all candidates prefiltered")
        note(event="heuristic", config=dict(default),
             n_candidates=len(cands), n_prefiltered=dropped,
             timings=timings)
        return dict(default)
    with _lock:
        _mem_cache[skey] = dict(best_cfg)
        _bump(name, "searches")
        _bump(name, "configs_timed", timed)
        if _warm:
            _bump(name, "searches_after_warm")
    _disk_store(skey, space, name, best_cfg, best_ms)
    _publish(space, name, "search", key, best_cfg,
             best_ms=round(best_ms, 4), n_candidates=len(cands),
             n_timed=timed, n_prefiltered=dropped)
    note(event="search", config=dict(best_cfg),
         best_ms=round(best_ms, 4),
         default_ms=None if default_ms is None else round(default_ms, 4),
         n_candidates=len(cands), n_timed=timed, n_prefiltered=dropped,
         timings=timings)
    return dict(best_cfg)


# -- profiler summary section ------------------------------------------------
_section_base: Dict[str, Dict[str, int]] = {}


def _on_profiler_reset() -> None:
    with _lock:
        _section_base.clear()
        _section_base.update({k: dict(v) for k, v in _counters.items()})


def _summary_section() -> str:
    """Counter deltas since the profiler was last reset, one row per
    tuned client across every space, as a table the
    ``profiler.summary()`` host-event report appends."""
    with _lock:
        rows = []
        for name in sorted(_counters):
            base = _section_base.get(name, {})
            d = {k: _counters[name][k] - base.get(k, 0)
                 for k in _COUNTER_KEYS}
            if any(d.values()):
                rows.append((_spaces.get(name, ""), name, d))
    if not rows:
        return ""
    path = cache_path() or "<in-memory only>"
    w = max(len(r[1]) for r in rows) + 2
    sw = max(len(r[0]) for r in rows) + 2
    lines = [f"Measured search (cache: {path})",
             f"{'Space':<{sw}}{'Name':<{w}}{'Searches':>10}{'Timed':>8}"
             f"{'Hits':>8}{'Disk':>8}{'Heur':>8}{'Filt':>6}{'AfterWarm':>11}"]
    for space, name, d in rows:
        lines.append(
            f"{space:<{sw}}{name:<{w}}{d['searches']:>10}"
            f"{d['configs_timed']:>8}{d['hits']:>8}{d['disk_hits']:>8}"
            f"{d['heuristic']:>8}{d['prefiltered']:>6}"
            f"{d['searches_after_warm']:>11}")
    return "\n".join(lines)


def _register_profiler_section() -> None:
    from .. import profiler

    profiler.register_summary_section(_summary_section,
                                      on_reset=_on_profiler_reset)


_register_profiler_section()
