"""Measured Pallas kernel autotuning — the ``"kernel"`` client of the
generic measured-search engine in ``paddle_tpu.tuning.engine``.

The hand kernels in this package ship tile-size defaults that were tuned
on one shape class (flash attention's 512-blocks on 32k sequences, the
conv+BN epilogue's 512x256 on ResNet layers).  FlashAttention-class
kernels are famously block-size-sensitive.  The Triton/AutoTVM answer — a small template space,
compile + time each candidate on the real shapes, memoize the winner —
lives in the engine; this module keeps what is kernel-specific:

* candidate generators respect Mosaic's (8, 128) f32 tile (sublane
  multiples of 8, lane multiples of 128) and a VMEM-footprint estimate,
  so every candidate can actually lower;
* the search runs on the REAL backend with synthetic data of the real
  shapes/dtypes; off-TPU (interpret mode, CI) the registered heuristic
  default is returned without timing — interpret-mode timings would tune
  for the wrong machine;
* winners are memoized in-process and in the shared JSON cache keyed by
  ``(kernel, shape bucket, dtype, device kind)`` so training restarts and
  serving engines pay zero re-tuning (``FLAGS_kernel_tuning_cache`` —
  the same file also holds sharding-plan and serving-config winners);
* every resolution publishes an ``("autotune", kernel)`` event on
  ``framework.trace_events`` (hit / disk_hit / search / heuristic, plus
  counter snapshots) — ``analysis.RetraceMonitor`` turns a measured
  search after ``mark_warm()`` into rule K701, the serving-hot-path twin
  of R403/S601 — and a "Measured search" section rides along in
  ``profiler.summary()``.

Usage::

    @autotune("my_kernel", params=("block_m",), space=my_space,
              heuristic=lambda x: {"block_m": 512})
    def _my_kernel(x, *, block_m):
        return pl.pallas_call(...)(x)

    _my_kernel(x)                  # tuned (or heuristic off-TPU)
    _my_kernel(x, block_m=128)     # explicit override, no tuning
    _my_kernel.config(x)           # resolve the config without running
"""
from __future__ import annotations

import concurrent.futures
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..framework import device as _device
from ..framework.errors import InvalidArgumentError
from ..framework.flags import flag
from ..tuning import engine as _engine
from ..tuning.engine import (  # noqa: F401  (re-exported API)
    _COUNTER_KEYS,
    cache_path,
    clear_cache,
    get_counters,
    is_warm,
    mark_warm,
    measure_ms,
    reset_counters,
    reset_warm,
)

__all__ = [
    "autotune", "TunedKernel", "tile_candidates", "vmem_fits",
    "cache_path", "clear_cache", "get_counters", "reset_counters",
    "mark_warm", "is_warm", "reset_warm", "registered_kernels",
    "fused_epilogues_eligible", "mesh_admits_kernels",
]

# -- Mosaic tiling / VMEM constants ------------------------------------------
SUBLANE = 8      # f32 sublane tile; candidate row blocks are multiples
LANE = 128       # lane tile; candidate column blocks are multiples
VMEM_BYTES = 16 * 1024 * 1024  # per-core VMEM (v4/v5e/v5p all ~16 MB)
#: the zero every BlockSpec index map returns for a whole dim.  The package
#: turns x64 on, so a Python ``0`` there becomes an i64 — which Mosaic
#: cannot return from an index map (``func.return (i32, i64)``).
I0 = np.int32(0)
#: fraction of VMEM a candidate's resident blocks may claim — the rest is
#: double-buffering headroom for the pipelined DMA in/out streams
VMEM_BUDGET_FRAC = 0.7

_REGISTRY: Dict[str, "TunedKernel"] = {}

_bucket_shape = _engine.bucket_shape
_device_kind = _engine.device_kind


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def tile_candidates(n: int, *, multiple: int = SUBLANE,
                    base: Sequence[int] = (64, 128, 256, 512, 1024),
                    ) -> List[int]:
    """Candidate block sizes for a length-``n`` dimension: the power-of-two
    ladder clamped to the PADDED length (``round_up(n, multiple)``) so a
    short dimension — a serving bucket, a small model — never pays
    full-width padded tiles, each rounded to the Mosaic ``multiple``."""
    if n <= 0:
        raise InvalidArgumentError(f"tile_candidates: bad dim {n}")
    cap = _round_up(n, multiple)
    out = sorted({max(multiple, min(_round_up(b, multiple), cap))
                  for b in base})
    return out


def vmem_fits(nbytes: int, frac: float = VMEM_BUDGET_FRAC) -> bool:
    """True iff a candidate's resident VMEM blocks fit the budget."""
    return nbytes <= int(VMEM_BYTES * frac)


def _is_arraylike(a) -> bool:
    return hasattr(a, "shape") and hasattr(a, "dtype")


def registered_kernels() -> List[str]:
    return sorted(_REGISTRY)


# -- measured search ---------------------------------------------------------
def _synthetic_args(args):
    """Concrete stand-ins mirroring each array arg's shape/dtype (the real
    args may be tracers when tuning triggers inside a jit trace): floats
    draw standard normal, ints are zeros (always in-range for labels)."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    out = []
    for a in args:
        if _is_arraylike(a):
            dt = np.dtype(a.dtype)  # ml_dtypes (bfloat16 etc.) included
            if np.issubdtype(dt, np.integer):
                out.append(jnp.zeros(tuple(a.shape), dtype=dt))
            else:
                out.append(jnp.asarray(
                    rng.standard_normal(tuple(a.shape)).astype(np.float32),
                    dtype=dt))
        else:
            out.append(a)
    return out


def _time_once(fn, args) -> float:
    """Compile + best-of-3 wall time (ms) for one candidate (the untimed
    warm call and best-of-N live in ``engine.measure_ms``)."""
    import jax

    return measure_ms(jax.jit(fn), args, repeats=3)


def _outside_trace(fn: Callable):
    """Run ``fn()`` outside whatever jit trace the caller is in.  Tuning
    usually triggers INSIDE a model's jit trace, where every ``jnp`` call
    — building the stand-in arrays, calling the candidate — is staged
    into the outer program: the candidates would be traced, never
    compiled or run, and the "timing" would be tracing time.  JAX's trace
    state is thread-local, so a fresh thread sees none of it: the
    stand-ins are concrete and each candidate is a real, separately
    compiled execution on the backend.  (``ensure_compile_time_eval``
    does not do: it leaks into the candidate's own kernel trace.)"""
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
        return pool.submit(fn).result()


class TunedKernel:
    """A kernel whose tile parameters the autotuner owns.

    ``fn(*args, **kwargs, **config)`` is the measurable unit — it must
    accept the config params as keyword arguments and run end-to-end
    (including any padding the config implies).  ``space(*args,
    **kwargs)`` yields candidate config dicts (already Mosaic-aligned and
    VMEM-filtered); ``heuristic(*args, **kwargs)`` is the untimed default
    — it MUST reproduce the kernel's pre-autotuner behavior so the
    default config stays bit-compatible.  ``key_kwargs`` names the
    non-array kwargs that change the compiled kernel (e.g. ``causal``)
    and so belong in the cache key."""

    def __init__(self, fn: Callable, name: str, params: Tuple[str, ...],
                 space: Callable, heuristic: Callable,
                 key_kwargs: Tuple[str, ...] = ()):
        functools.update_wrapper(self, fn)
        self.fn = fn
        self.name = name
        self.params = tuple(params)
        self.space = space
        self.heuristic = heuristic
        self.key_kwargs = tuple(key_kwargs)
        if name in _REGISTRY:
            raise InvalidArgumentError(
                f"autotune kernel {name!r} registered twice")
        _REGISTRY[name] = self

    # -- key -----------------------------------------------------------------
    def cache_key(self, *args, **kwargs) -> str:
        """Stable string key: kernel | per-array (pow2-bucketed shape,
        dtype) | key kwargs | device kind."""
        parts = [self.name]
        for a in args:
            if _is_arraylike(a):
                bucket = "x".join(map(str, _bucket_shape(a.shape)))
                parts.append(f"{bucket}:{np.dtype(a.dtype).name}")
            else:
                parts.append(repr(a))
        for k in self.key_kwargs:
            parts.append(f"{k}={kwargs.get(k)!r}")
        parts.append(_device_kind())
        return "|".join(parts)

    def candidates(self, *args, **kwargs) -> List[dict]:
        """The (deduped) candidate configs for these args; the heuristic
        default is always in the running."""
        kw = {k: v for k, v in kwargs.items() if k not in self.params}
        return _engine.dedup_candidates(self.space(*args, **kw),
                                        self.heuristic(*args, **kw))

    # -- resolution ----------------------------------------------------------
    def config(self, *args, **kwargs) -> dict:
        """Resolve the config for these args without running the kernel:
        in-memory hit -> disk hit -> measured search (TPU, or mode
        'force') -> heuristic default."""
        kw = {k: v for k, v in kwargs.items() if k not in self.params}
        key = self.cache_key(*args, **kw)
        mode = str(flag("kernel_autotune")).lower()
        measurable = mode == "force" or (
            mode != "off" and _device.on_tpu())
        synth = None  # built once, only if a search actually measures

        def measure(cand: dict) -> float:
            merged = {**kw, **cand}

            def timed() -> float:
                nonlocal synth
                if synth is None:
                    synth = _synthetic_args(args)
                return _time_once(
                    lambda *a, _m=merged: self.fn(*a, **_m), synth)

            return _outside_trace(timed)

        return _engine.resolve(
            "kernel", self.name, key,
            candidates=lambda: self.space(*args, **kw),
            measure=measure,
            heuristic=lambda: self.heuristic(*args, **kw),
            measurable=measurable)

    def resolve(self, *args, **kwargs) -> dict:
        """The full config for these args: explicit (non-None) values of
        the tile params in ``kwargs`` win, the rest come from
        :meth:`config` — which is skipped when every param is explicit."""
        overrides = {k: kwargs[k] for k in self.params
                     if kwargs.get(k) is not None}
        if len(overrides) == len(self.params):
            return overrides
        return {**self.config(*args, **kwargs), **overrides}

    # -- call ----------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        cfg = self.resolve(*args, **kwargs)
        for k in self.params:
            kwargs.pop(k, None)
        return self.fn(*args, **kwargs, **cfg)

    def __repr__(self):
        return f"<TunedKernel {self.name} params={self.params}>"


def autotune(name: str, *, params: Sequence[str], space: Callable,
             heuristic: Callable, key_kwargs: Sequence[str] = ()):
    """Register ``fn`` as an autotuned kernel (see :class:`TunedKernel`)."""

    def deco(fn):
        return TunedKernel(fn, name, tuple(params), space, heuristic,
                           tuple(key_kwargs))

    return deco


# -- model-integration gates -------------------------------------------------
def mesh_admits_kernels() -> bool:
    """``pallas_call`` has no GSPMD partitioning rule: JAX refuses to lower
    a Mosaic kernel inside ANY jit that spans more than one device
    ("Mosaic kernels cannot be automatically partitioned"), whichever axis
    is sharded — ``data`` as much as ``model``.  The model hot paths trace
    under the global mesh, so their kernel gates open only on a one-device
    mesh; multi-chip meshes keep the XLA paths until the kernels are
    wrapped in ``shard_map``."""
    from ..distributed.mesh import get_mesh

    return get_mesh().size == 1


def fused_epilogues_eligible(feature_dim: Optional[int] = None) -> bool:
    """Should a model hot path call the fused Pallas epilogues?  Mirrors
    the flash-attention gate: a real TPU backend (interpret mode loses),
    lane-aligned feature dim, and a mesh that admits kernels
    (:func:`mesh_admits_kernels`)."""
    if not flag("fused_epilogues") or not _device.on_tpu():
        return False
    if feature_dim is not None and feature_dim % LANE != 0:
        return False
    return mesh_admits_kernels()
