"""Cross-process engine transport — serve an engine from another host.

The :class:`~paddle_tpu.serving.router.Router` fronts anything with
``submit()/infer()/synthetic_inputs()``; in a pod those engines live in
*other processes*.  This module is the host-lane RPC that bridges them:

* :class:`EngineServer` wraps a local engine and serves requests arriving
  as files in a shared directory (the same ``PADDLE_TPU_GANG_DIR``
  filesystem lane the gang collectives ride — see distributed/gang.py).
* :class:`RemoteEngineProxy` is the client half: it quacks like an
  engine (``submit`` → Future, ``infer``, ``synthetic_inputs``) so a
  Router on one host can balance, probe, hedge and fail over across
  engines owned by every host in the gang.

Transport is deliberately minimal — atomic file writes (tmp +
``os.replace``), one file per request and one per response, pickle
payloads — because its job is the pod smoke and shared-filesystem pods,
not a production message bus.  What *is* production-shaped is the
failure contract: a dead or wedged server surfaces as
:class:`UnavailableError` within the request deadline, which is exactly
the error class the Router's failover/circuit machinery feeds on, and
:meth:`Router.bind_peer_liveness` can evict a lost host's replicas
milliseconds after the gang heartbeat verdict instead of waiting for
deadlines to burn down.
"""
from __future__ import annotations

import os
import pickle
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional

from ..framework.errors import InvalidArgumentError, UnavailableError

__all__ = ["EngineServer", "RemoteEngineProxy"]

_POLL_S = 0.01


def _atomic_write(path: str, payload: bytes) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(payload)
    os.replace(tmp, path)


def _try_read(path: str) -> Optional[bytes]:
    try:
        with open(path, "rb") as f:
            return f.read()
    except OSError:
        return None


class EngineServer:
    """Serve a local engine over a shared directory.

    ``root`` — the RPC directory (all gang members see it); ``name`` —
    this server's identity, unique per gang (convention:
    ``engine.p<process_index>``).  On :meth:`start` the server publishes
    a ``hello.<name>`` file carrying its pickled synthetic inputs so
    proxies can answer ``synthetic_inputs()`` without a round trip, then
    a daemon thread picks up ``req.<name>.*`` files, runs
    ``engine.infer``, and writes the matching ``rsp.<name>.*``.
    Exceptions from the engine travel back pickled and re-raise
    client-side.
    """

    def __init__(self, engine, root: str, name: str = "engine"):
        if not name or os.sep in name:
            raise InvalidArgumentError(
                f"EngineServer name {name!r} must be a non-empty flat token")
        self.engine = engine
        self.root = root
        self.name = name
        os.makedirs(root, exist_ok=True)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.served = 0

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "EngineServer":
        _atomic_write(
            os.path.join(self.root, f"hello.{self.name}"),
            pickle.dumps(self.engine.synthetic_inputs()))
        self._thread = threading.Thread(
            target=self._loop, name=f"engine-server-{self.name}", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    # -- serving loop --------------------------------------------------------
    def serve_once(self) -> int:
        """Handle every pending request file once; returns requests served
        this pass (the loop thread calls this; tests may too)."""
        prefix = f"req.{self.name}."
        try:
            names = sorted(n for n in os.listdir(self.root)
                           if n.startswith(prefix) and ".tmp." not in n)
        except OSError:
            return 0
        n = 0
        for fname in names:
            path = os.path.join(self.root, fname)
            raw = _try_read(path)
            if raw is None:
                continue
            try:
                os.unlink(path)  # claim: at-most-once per request file
            except OSError:
                continue
            req_id = fname[len(prefix):]
            try:
                inputs, kw = pickle.loads(raw)
                result = (True, self.engine.infer(inputs, **kw))
            except Exception as exc:  # noqa: BLE001 — travels to client
                result = (False, exc)
            _atomic_write(os.path.join(self.root, f"rsp.{self.name}.{req_id}"),
                          pickle.dumps(result))
            self.served += 1
            n += 1
        return n

    def _loop(self) -> None:
        while not self._stop.is_set():
            if self.serve_once() == 0:
                time.sleep(_POLL_S)

    def __enter__(self) -> "EngineServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


class RemoteEngineProxy:
    """Client half: an engine facade over a remote :class:`EngineServer`.

    Satisfies the Router's replica contract — ``submit(inputs,
    deadline_ms=..., trace_ctx=...) -> Future``, blocking ``infer``, and
    ``synthetic_inputs()`` (read from the server's hello file, so the
    Router's default health probe exercises the full cross-process
    path).  A response that misses its deadline resolves the Future with
    :class:`UnavailableError` — the retryable class the Router's
    failover and circuit breaker key on — and the request file is
    withdrawn so a later revival of the server does not execute stale
    work.
    """

    def __init__(self, root: str, name: str, *,
                 timeout_s: float = 30.0, hello_timeout_s: float = 60.0):
        self.root = root
        self.name = name
        self.timeout_s = float(timeout_s)
        self._hello_timeout_s = float(hello_timeout_s)
        self._synth: Optional[list] = None
        self._seq = 0
        self._lock = threading.Lock()
        self._pending: Dict[str, tuple] = {}  # req_id -> (Future, deadline)
        self._stop = threading.Event()
        self._poller: Optional[threading.Thread] = None

    # -- engine facade -------------------------------------------------------
    def synthetic_inputs(self, bucket: int = 0) -> list:
        if self._synth is None:
            deadline = time.monotonic() + self._hello_timeout_s
            path = os.path.join(self.root, f"hello.{self.name}")
            while True:
                raw = _try_read(path)
                if raw is not None:
                    self._synth = pickle.loads(raw)
                    break
                if time.monotonic() >= deadline:
                    raise UnavailableError(
                        f"remote engine {self.name!r}: no hello file under "
                        f"{self.root} after {self._hello_timeout_s:g}s — "
                        f"server never started?")
                time.sleep(_POLL_S)
        return self._synth

    def submit(self, inputs, deadline_ms: Optional[float] = None,
               trace_ctx=None, **kw) -> Future:
        del trace_ctx  # spans do not cross the process boundary
        timeout_s = (deadline_ms / 1e3 if deadline_ms is not None
                     else self.timeout_s)
        fut: Future = Future()
        with self._lock:
            self._seq += 1
            req_id = f"{os.getpid()}-{self._seq}"
            self._pending[req_id] = (fut, time.monotonic() + timeout_s)
            if self._poller is None:
                self._poller = threading.Thread(
                    target=self._poll_loop,
                    name=f"remote-engine-{self.name}", daemon=True)
                self._poller.start()
        _atomic_write(os.path.join(self.root, f"req.{self.name}.{req_id}"),
                      pickle.dumps((list(inputs), kw)))
        return fut

    def infer(self, inputs, timeout: Optional[float] = None, **kw):
        return self.submit(
            inputs,
            deadline_ms=None if timeout is None else timeout * 1e3,
            **kw).result()

    # -- response poller -----------------------------------------------------
    def _poll_loop(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                pending = dict(self._pending)
            if not pending:
                time.sleep(_POLL_S)
                continue
            now = time.monotonic()
            for req_id, (fut, deadline) in pending.items():
                raw = _try_read(os.path.join(
                    self.root, f"rsp.{self.name}.{req_id}"))
                if raw is not None:
                    try:
                        os.unlink(os.path.join(
                            self.root, f"rsp.{self.name}.{req_id}"))
                    except OSError:
                        pass
                    with self._lock:
                        self._pending.pop(req_id, None)
                    ok, payload = pickle.loads(raw)
                    if ok:
                        fut.set_result(payload)
                    else:
                        fut.set_exception(payload)
                elif now >= deadline:
                    # withdraw the request so a revived server cannot run
                    # it later; then fail fast with the retryable class
                    try:
                        os.unlink(os.path.join(
                            self.root, f"req.{self.name}.{req_id}"))
                    except OSError:
                        pass
                    with self._lock:
                        self._pending.pop(req_id, None)
                    fut.set_exception(UnavailableError(
                        f"remote engine {self.name!r} did not answer "
                        f"request {req_id} within the deadline — host dead "
                        f"or wedged"))
            time.sleep(_POLL_S)

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        del drain, timeout
        self._stop.set()
        if self._poller is not None:
            self._poller.join(timeout=5)
