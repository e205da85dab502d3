"""Global flag registry.

TPU-native re-design of the reference's gflags system
(reference: paddle/fluid/platform/flags.cc:33-560 defines ~30 FLAGS_*;
python/paddle/fluid/framework.py:5676 ``set_flags``; flags are overridable
via FLAGS_* environment variables at import time, see
paddle/fluid/platform/init.cc).

Here flags are a typed in-process registry. Environment variables named
``FLAGS_<name>`` seed the initial value (same convention as the reference).
XLA-level knobs (memory fraction etc.) are owned by the XLA runtime; the
flags kept here are the framework-behavior ones.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict

from .errors import NotFoundError, InvalidArgumentError

__all__ = ["define_flag", "set_flags", "get_flags", "flag"]

_REGISTRY: Dict[str, dict] = {}


def _parse_bool(s: str) -> bool:
    return s.lower() in ("1", "true", "yes", "on")


def define_flag(name: str, default: Any, help_str: str = "", type_: type | None = None):
    """Register a flag. Env var FLAGS_<name> overrides the default."""
    t = type_ or type(default)
    value = default
    env = os.environ.get(f"FLAGS_{name}")
    if env is not None:
        if t is bool:
            value = _parse_bool(env)
        else:
            value = t(env)
    _REGISTRY[name] = {"value": value, "default": default, "type": t, "help": help_str}
    return value


def set_flags(flags: Dict[str, Any]):
    """Parity: ``paddle.set_flags`` (python/paddle/fluid/framework.py:5676)."""
    for name, value in flags.items():
        if name not in _REGISTRY:
            raise NotFoundError(f"Unknown flag {name!r}")
        t = _REGISTRY[name]["type"]
        if t is bool and isinstance(value, str):
            value = _parse_bool(value)
        try:
            _REGISTRY[name]["value"] = t(value)
        except (TypeError, ValueError) as e:
            raise InvalidArgumentError(f"Bad value for flag {name}: {value!r}") from e


def get_flags(names) -> Dict[str, Any]:
    """Parity: ``paddle.get_flags``."""
    if isinstance(names, str):
        names = [names]
    out = {}
    for name in names:
        if name not in _REGISTRY:
            raise NotFoundError(f"Unknown flag {name!r}")
        out[name] = _REGISTRY[name]["value"]
    return out


def flag(name: str) -> Any:
    """Fast single-flag read for internal use."""
    return _REGISTRY[name]["value"]


# ---------------------------------------------------------------------------
# Core flags (subset of platform/flags.cc that still makes sense on TPU).
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False,
            "Sweep op outputs for NaN/Inf during training "
            "(ref: FLAGS_check_nan_inf, platform/flags.cc:44).")
define_flag("sort_sum_gradient", False,
            "Deterministic gradient accumulation order "
            "(ref: FLAGS_sort_sum_gradient, platform/flags.cc:521). "
            "On XLA gradients are already deterministic; flag kept for API parity.")
define_flag("benchmark", False,
            "Synchronous benchmarking mode: block_until_ready after each step "
            "(ref: FLAGS_benchmark).")
define_flag("paddle_num_threads", 1,
            "Host-side worker threads for data feeding "
            "(ref: FLAGS_paddle_num_threads).")
define_flag("use_system_allocator", False,
            "Ignored on TPU: buffers are owned by the XLA runtime "
            "(ref: FLAGS_use_system_allocator).")
define_flag("eager_delete_tensor_gb", 0.0,
            "Ignored on TPU: XLA owns buffer lifetimes; kept for parity "
            "(ref: FLAGS_eager_delete_tensor_gb).")
define_flag("log_level", 0, "Verbosity for paddle_tpu host-side logging.")
define_flag("executor_cache_capacity", 64,
            "LRU capacity of each Executor's compiled-runner cache. Every "
            "distinct (program version, feed signature, fetch set) pins one "
            "XLA executable; unbounded growth is a slow leak, a too-small "
            "cap recompiles every run (surfaced as analysis rule R403).")
define_flag("persistent_compilation_cache", False,
            "Enable JAX's persistent compilation cache so repeated process "
            "launches skip XLA recompiles. The directory is "
            "JAX_COMPILATION_CACHE_DIR when that is set, else the fixed "
            "<checkout>/.cache/xla; see "
            "sysconfig.enable_persistent_compilation_cache().")
define_flag("kernel_tuning_cache", "",
            "Persistent cache of the measured searches' winners (JSON; "
            "sharding plans and serving configs: a kernel's tile is a "
            "rule of its shape and is searched nowhere). Empty picks the "
            "default <checkout>/.cache/kernel_tuning.json; '0'/'off' "
            "disables persistence (winners live for the process only); "
            "any other value is the cache file path. Pre-warm it by "
            "running representative shapes once, then ship the file — "
            "restarts and serving engines pay zero re-tuning.")
define_flag("measured_search", "on",
            "Measured search over sharding plans and serving configs "
            "(tuning/plan_space.py, tuning/serving_space.py): 'on' lets "
            "tune_plan/tune_serving compile+time candidates on the real "
            "backend when a caller asks; 'off' returns the hand-set "
            "defaults untimed. Both spaces share "
            "FLAGS_kernel_tuning_cache for persisted winners.")
define_flag("fused_epilogues", True,
            "Let the BERT/GPT hot paths call the fused Pallas epilogues "
            "(LayerNorm+residual, softmax-cross-entropy) on TPU. Off "
            "falls back to the plain XLA ops everywhere.")
define_flag("paged_flash", True,
            "Let the paged serving decode path dispatch to the Pallas "
            "paged-flash-decode kernel (ops/paged_attention.py) on TPU. "
            "Off keeps the gather-then-attend reference path everywhere "
            "(always the CPU path — it is the bit-identical fallback).")
define_flag("fault_plan", "",
            "Deterministic fault injection plan (resilience/faults.py). "
            "Semicolon-separated rules of comma-separated key=value "
            "fields, e.g. 'site=checkpoint.write,nth=3,error="
            "TransientDeviceError;site=serving.runner,p=0.1,seed=7'. "
            "Keys: site (required — a named fault_point), nth (fire on "
            "exactly the Nth call), every (fire on every Nth call), p + "
            "seed (seeded per-call probability), times (max fires), "
            "error (class from framework.errors or builtins; default "
            "TransientDeviceError), latency_ms (inject latency instead "
            "of raising). Empty (default): every fault_point is a no-op "
            "falsy check — zero hot-path cost, bit-identical runs.")
define_flag("collective_timeout_s", 0.0,
            "Collective/straggler watchdog deadline in seconds "
            "(distributed/collective.py): non-zero, every host-level "
            "collective (all_reduce, all_gather, barrier, ...) runs under "
            "a deadline and a wedged call raises TransientDeviceError "
            "into the retry/restart path instead of hanging the rank "
            "forever.  0.0 (default): disabled — the hook is a single "
            "falsy flag check, zero hot-path cost.  Set it well above "
            "the slowest legitimate collective (including the compile "
            "on first call).")
define_flag("transient_max_retries", 3,
            "Max attempts (1 = no retry) for operations retried on "
            "transient device errors (errors.is_transient): Executor.run "
            "dispatch, the async checkpoint writer, and serving batch "
            "execution. See resilience.RetryPolicy.from_flags().")
define_flag("retry_backoff_ms", 100.0,
            "Base delay of the exponential backoff between transient-"
            "error retries (doubles per attempt, +/-25% seeded jitter, "
            "capped at 20x the base).")
define_flag("circuit_failure_threshold", 0.5,
            "Serving circuit breaker (resilience/circuit.py): open a "
            "bucket's circuit when its failure rate over the last "
            "FLAGS_circuit_window batches reaches this fraction.")
define_flag("circuit_window", 8,
            "Number of most-recent batch outcomes per bucket the circuit "
            "breaker evaluates the failure rate over (it never opens "
            "before observing a full window).")
define_flag("circuit_cooldown_ms", 1000.0,
            "How long an open circuit sheds before letting half-open "
            "probe batches through to test recovery.")
define_flag("circuit_half_open_probes", 1,
            "Probe batches admitted in the half-open state; all must "
            "succeed to close the circuit, any failure re-opens it.")
define_flag("kv_page_size", 16,
            "Tokens per KV page of GenerationEngine's page pool "
            "(serving/generation.py). Smaller pages waste less "
            "memory on the last partial page per sequence but grow the "
            "page table; must divide the engine's max_len.")
define_flag("speculative_k", 4,
            "GenerationEngine's speculative decoding draft length: an n-gram "
            "proposer (prompt-lookup) drafts up to k tokens per slot and "
            "one batched verify step accepts the longest matching prefix "
            "— token-identical to plain greedy, up to k+1 tokens per "
            "step when drafts hit. 0 disables speculation.")
define_flag("metrics_port", 0,
            "Prometheus text-exposition endpoint for the observability "
            "registry (observability/exporters.py): 0 disables (default), "
            "-1 binds an ephemeral port (read it back from "
            "observability.status()), any other value is the TCP port. "
            "Picked up by the first Executor via "
            "observability.maybe_enable_from_flags().")
define_flag("metrics_jsonl", "",
            "Base path of the periodic JSONL metrics sink; written as "
            "<base>.p<process_index>.jsonl (one file per host process — "
            "observability.merge_jsonl collates them). Empty (default) "
            "disables the sink.")
define_flag("metrics_jsonl_interval_s", 10.0,
            "Seconds between JSONL metric snapshots (plus one final "
            "snapshot at close).")
define_flag("hbm_high_water_frac", 0.9,
            "Analysis rule M902 fires when the HBM high-water mark "
            "(peak_bytes_in_use) reaches this fraction of the device's "
            "bytes_limit — the early warning before a real OOM.")
define_flag("trace_requests", False,
            "End-to-end request tracing (observability/tracing.py): on, "
            "Router.submit opens a root span per accepted request and "
            "the replica-dispatch / batcher-queue / decode-slot layers "
            "record child spans into a bounded per-process ring buffer "
            "(merged into profiler.export_chrome_tracing output). Off "
            "(default), every hook is a single falsy check. Picked up "
            "by observability.maybe_enable_from_flags().")
define_flag("trace_buffer_cap", 65536,
            "Capacity of the request-tracing span ring buffer; the "
            "oldest spans are dropped first past the cap (drops are "
            "counted in Tracer.stats()).")
define_flag("lock_sanitizer", False,
            "Runtime lock-order sanitizer (framework/locking.py): on, "
            "every OrderedLock/OrderedRLock/OrderedCondition acquire "
            "checks the cumulative cross-thread acquisition-order graph "
            "and records a C1004 violation on a would-be cycle (instead "
            "of deadlocking), and every release checks the hold time "
            "against FLAGS_lock_hold_warn_ms (C1005). Off (default), "
            "acquire/release adds a single falsy check. Static "
            "companion: python -m paddle_tpu.analysis --concurrency.")
define_flag("lock_hold_warn_ms", 500.0,
            "Lock-hold duration (milliseconds) past which the lock "
            "sanitizer records a C1005 long-hold violation on release. "
            "Condition.wait time does not count (the wait releases the "
            "lock). <= 0 disables the hold check.")
