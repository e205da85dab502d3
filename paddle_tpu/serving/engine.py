"""Dynamic-batching inference engine over bucketed AOT executables.

``InferenceEngine`` fronts an exported ``paddle_tpu.inference`` artifact
with the micro-batcher: requests are routed to the smallest fitting shape
bucket, padded, batched, and executed by ONE ahead-of-time compiled
executable per bucket.  After :meth:`warmup` the compile set is closed —
``compile_count == len(buckets)`` no matter what shapes live traffic
throws at it (the invariant the retrace-hazard rules demand).

Weights stay ARGUMENTS of the executables, so :meth:`swap_weights` picks
up a new ``.pdiparams`` side-file between batches with zero recompiles
and no request ever observing a half-swapped model.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..framework.errors import InvalidArgumentError
from ..framework.locking import OrderedLock
from ..inference import Predictor
from ..resilience import CircuitBreaker, RetryPolicy
from ..resilience import retry as _retry_mod
from .batcher import MicroBatcher, Request
from .bucketing import BucketSet
from .metrics import ServingMetrics

__all__ = ["InferenceEngine"]

_FALLBACK = -1
_engine_counter = [0]


class InferenceEngine:
    """Serve an exported model under dynamic batching.

    Parameters mirror the two serving dials plus robustness knobs:
    ``buckets`` (the closed shape set — see serving.bucketing),
    ``max_batch_size`` / ``max_queue_delay_ms`` (throughput vs latency),
    ``max_queue_depth`` (load shedding), ``allow_bucket_fallback``
    (serve bucket misses through the slow batch-polymorphic path instead
    of rejecting — each distinct miss shape costs a fresh compile, which
    is what analysis rule S601 flags), ``circuit_breaker`` (per-bucket
    closed/open/half-open degradation: a persistently failing bucket
    sheds with ``UnavailableError`` instead of burning device slots) and
    ``retry_transient`` (re-run a batch once per transient device error
    before failing its futures — see ``FLAGS_transient_max_retries``).
    """

    @classmethod
    def from_tuned(cls, path_prefix: str, config: Dict, **overrides):
        """Build an engine from a measured-search serving config (a
        ``tuning.serving_space`` winner): ``buckets`` plus
        ``max_batch_size``/``batch_size`` and ``max_queue_delay_ms`` map
        onto constructor arguments; keyword ``overrides`` win."""
        kw = {}
        batch = config.get("max_batch_size", config.get("batch_size"))
        if batch is not None:
            kw["max_batch_size"] = int(batch)
        if config.get("max_queue_delay_ms") is not None:
            kw["max_queue_delay_ms"] = float(config["max_queue_delay_ms"])
        kw.update(overrides)
        return cls(path_prefix, config["buckets"], **kw)

    def __init__(self, path_prefix: str, buckets: Sequence, *,
                 max_batch_size: int = 8, max_queue_delay_ms: float = 5.0,
                 max_queue_depth: int = 256, pad_value=0,
                 allow_bucket_fallback: bool = False,
                 unpad_outputs: bool = True,
                 device: Optional[str] = None,
                 params_file: Optional[str] = None,
                 circuit_breaker: bool = True,
                 retry_transient: bool = True,
                 name: Optional[str] = None):
        if name is None:
            _engine_counter[0] += 1
            name = f"engine#{_engine_counter[0]}"
        self.name = name
        self._pred = Predictor(path_prefix, device=device,
                               params_file=params_file)
        self._buckets = BucketSet(buckets, pad_value=pad_value)
        self._max_batch = int(max_batch_size)
        self._allow_fallback = bool(allow_bucket_fallback)
        self._unpad = bool(unpad_outputs)
        self._exe_lock = OrderedLock("InferenceEngine._exe_lock")
        self._executables: Dict[int, object] = {}
        self._fallback_shapes = set()
        self.metrics = ServingMetrics(name)
        self.breaker = (CircuitBreaker(name) if circuit_breaker else None)
        self._batcher = MicroBatcher(
            self._route, self._run_batch,
            max_batch_size=max_batch_size,
            max_queue_delay_ms=max_queue_delay_ms,
            max_queue_depth=max_queue_depth,
            capacity=self._bucket_capacity,
            metrics=self.metrics,
            breaker=self.breaker,
            retry=(RetryPolicy.from_flags(name=f"{name}.runner")
                   if retry_transient else None),
            name=name)

    # -- routing / compile set ----------------------------------------------
    def _bucket_capacity(self, bucket: int) -> int:
        if bucket == _FALLBACK:
            return 1  # polymorphic path runs unbatched
        return self._buckets.buckets[bucket].batch_size or self._max_batch

    def _route(self, inputs: Sequence) -> int:
        shapes = tuple(tuple(np.shape(x)) for x in inputs)
        idx = self._buckets.route(shapes)
        if idx >= 0:
            return idx
        self.metrics.incr("bucket_misses")
        self.metrics.publish()
        if self._allow_fallback:
            return _FALLBACK
        raise InvalidArgumentError(
            f"{self.name}: request shapes {shapes} fit none of the "
            f"{len(self._buckets)} configured buckets "
            f"{[b.shapes for b in self._buckets.buckets]} — add a bucket "
            f"covering them (or allow_bucket_fallback=True to serve "
            f"misses unbatched at one compile per distinct shape)")

    def _executable(self, bucket: int):
        with self._exe_lock:
            exe = self._executables.get(bucket)
        if exe is not None:
            return exe
        b = self._buckets.buckets[bucket]
        cap = self._bucket_capacity(bucket)
        exe = self._pred.aot_compile(
            [(cap,) + s for s in b.shapes])
        with self._exe_lock:
            # a concurrent compile of the same bucket keeps the first one
            exe = self._executables.setdefault(bucket, exe)
            self.metrics.incr("compiles")
        return exe

    @property
    def compile_count(self) -> int:
        """Bucket executables built so far (fallback compiles are counted
        separately in ``stats()['fallback_runs']``)."""
        with self._exe_lock:
            return len(self._executables)

    def warmup(self) -> int:
        """Compile every configured bucket up front so first requests pay
        serve latency, not compile latency.  Returns the (now closed)
        executable count."""
        for i in range(len(self._buckets)):
            self._executable(i)
        from ..tuning import engine as _tuning
        _tuning.mark_warm()  # later measured searches are hot-path (K701)
        _retry_mod.mark_warm()  # later retry storms / flaps are F801
        return self.compile_count

    # -- execution -----------------------------------------------------------
    def _run_batch(self, bucket: int, requests: List[Request]) -> List[List[np.ndarray]]:
        if bucket == _FALLBACK:
            outs = []
            for r in requests:
                self.metrics.incr("fallback_runs")
                outs.append(self._pred.run(
                    [np.asarray(x)[None] for x in r.inputs]))
            return [[o[0] for o in out] for out in outs]
        cap = self._bucket_capacity(bucket)
        padded = [self._buckets.pad_request(bucket, r.inputs)
                  for r in requests]
        stacked = []
        for j in range(len(padded[0])):
            col = np.stack([p[j] for p in padded])
            if col.shape[0] < cap:  # pad batch rows: shapes stay closed
                widths = [(0, cap - col.shape[0])] + [(0, 0)] * (col.ndim - 1)
                col = np.pad(col, widths)
            stacked.append(col)
        from .. import profiler

        with profiler.RecordEvent(f"{self.name}/bucket[{bucket}]"):
            outs = self._pred.run_compiled(self._executable(bucket), stacked)
        return [self._slice_out(bucket, outs, i, r)
                for i, r in enumerate(requests)]

    def _slice_out(self, bucket: int, outs: List[np.ndarray], i: int,
                   req: Request) -> List[np.ndarray]:
        """Row ``i`` of each output, with padded axes sliced back to the
        request's original dims where they are recognizable: output axis
        ``j`` is sliced when it POSITIONALLY matches a padded input-0
        bucket dim (``out.shape[j] == bucket_dim[j] != request_dim[j]``)
        — the seq-model case, where outputs lead with the padded sequence
        axes.  Disable with ``unpad_outputs=False`` when output layout
        does not follow the input's."""
        row = [o[i] for o in outs]
        if not self._unpad:
            return row
        want = self._buckets.buckets[bucket].shapes[0]
        got = req.shapes[0]
        out = []
        for o in row:
            idx = [slice(None)] * o.ndim
            for j in range(min(o.ndim, len(want))):
                if o.shape[j] == want[j] and want[j] != got[j]:
                    idx[j] = slice(0, got[j])
            out.append(o[tuple(idx)])
        return out

    # -- public API ----------------------------------------------------------
    def synthetic_inputs(self, bucket: int = 0) -> List[np.ndarray]:
        """Zero-filled inputs exactly matching bucket ``bucket``'s shapes
        and the artifact's declared dtypes — the router's default health
        probe: it exercises the real routed/padded/compiled path without
        depending on live traffic."""
        b = self._buckets.buckets[bucket]
        dtypes = self._pred.input_dtypes()
        return [np.zeros(s, dtypes[i] if i < len(dtypes) else np.float32)
                for i, s in enumerate(b.shapes)]

    def submit(self, inputs: Sequence,
               deadline_ms: Optional[float] = None,
               trace_ctx=None) -> Future:
        """Async inference: one UNBATCHED request (no leading batch dim);
        resolves to the list of per-request outputs.  ``trace_ctx``
        optionally parents the batcher spans under a router trace."""
        return self._batcher.submit(inputs, deadline_ms=deadline_ms,
                                    trace_ctx=trace_ctx)

    def infer(self, inputs: Sequence,
              timeout: Optional[float] = None) -> List[np.ndarray]:
        """Blocking :meth:`submit`."""
        return self.submit(inputs).result(timeout)

    def swap_weights(self, params_file: str) -> None:
        """Hot weight-swap (see ``Predictor.swap_weights``): batches
        formed after this call run the new weights, with zero recompiles."""
        self._pred.swap_weights(params_file)
        self.metrics.publish({"weight_swap": 1})

    def stats(self) -> dict:
        snap = self.metrics.snapshot()
        snap["compile_count"] = self.compile_count
        snap["buckets"] = len(self._buckets)
        return snap

    def close(self, drain: bool = True, timeout: Optional[float] = None):
        self._batcher.close(drain=drain, timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
