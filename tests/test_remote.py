"""Cross-process serving: file-RPC engine transport + router peer liveness.

Reference capability: serving a pod where the Router fronts engines living
in OTHER host processes (fleet inference placement).  The transport is
:mod:`paddle_tpu.serving.remote` (same shared-directory contract as the
gang's FileTransport); host-death detection is the gang's
PeerHeartbeatMonitor wired into ``Router.bind_peer_liveness``.  The real
multi-process path (SIGKILLed server host, zero lost requests) runs in
``tools/pod_smoke.py``; these tests pin the in-process contracts.
"""
import threading
import time

import numpy as np
import pytest

from paddle_tpu.framework.errors import UnavailableError
from paddle_tpu.serving import EngineServer, RemoteEngineProxy, Router


class _FakeEngine:
    """Minimal engine surface: synthetic_inputs + infer (+ submit for the
    Router's dispatch path)."""

    def __init__(self, tag="e", fail=False):
        self.tag = tag
        self.fail = fail
        self.calls = 0

    def synthetic_inputs(self, bucket=0):
        return [np.zeros((1, 2), np.float32)]

    def infer(self, inputs, timeout=None, **kw):
        self.calls += 1
        if self.fail:
            raise RuntimeError(f"{self.tag} exploded")
        return [np.asarray(inputs[0]) + 1.0]

    def submit(self, inputs, deadline_ms=None, trace_ctx=None, **kw):
        from concurrent.futures import Future

        fut = Future()
        try:
            fut.set_result(self.infer(inputs, **kw))
        except Exception as e:  # noqa: BLE001 — travels via the future
            fut.set_exception(e)
        return fut


class TestRemoteEngine:
    def test_round_trip(self, tmp_path):
        with EngineServer(_FakeEngine(), str(tmp_path), name="e0"):
            proxy = RemoteEngineProxy(str(tmp_path), "e0", timeout_s=10.0,
                                      hello_timeout_s=10.0)
            x = [np.full((1, 2), 3.0, np.float32)]
            out = proxy.infer(x, timeout=10.0)
            np.testing.assert_array_equal(out[0],
                                          np.full((1, 2), 4.0, np.float32))
            # synthetic inputs come from the server's hello file
            syn = proxy.synthetic_inputs()
            assert syn[0].shape == (1, 2)
            proxy.close()

    def test_server_leaves_a_request_still_being_written_alone(self, tmp_path):
        # a client's write is `<req>.tmp.<pid>` until its atomic rename;
        # a server that claimed that file made the rename fail
        # (FileNotFoundError in the client: the suite's flake under load)
        import os
        import pickle
        srv = EngineServer(_FakeEngine(), str(tmp_path), name="e0")
        half = tmp_path / f"req.e0.77-1.tmp.{os.getpid()}"
        half.write_bytes(pickle.dumps(([np.zeros((1, 2), np.float32)], {})))
        assert srv.serve_once() == 0
        assert half.exists()
        os.replace(half, tmp_path / "req.e0.77-1")
        assert srv.serve_once() == 1
        assert (tmp_path / "rsp.e0.77-1").exists()

    def test_server_exception_travels_to_client(self, tmp_path):
        with EngineServer(_FakeEngine(fail=True), str(tmp_path), name="e0"):
            proxy = RemoteEngineProxy(str(tmp_path), "e0", timeout_s=10.0,
                                      hello_timeout_s=10.0)
            with pytest.raises(RuntimeError, match="exploded"):
                proxy.infer([np.zeros((1, 2), np.float32)], timeout=10.0)
            proxy.close()

    def test_dead_server_unavailable_within_deadline(self, tmp_path):
        # server answers hello then dies: requests must fail with the
        # retryable UnavailableError inside the deadline, never hang
        srv = EngineServer(_FakeEngine(), str(tmp_path), name="e0").start()
        proxy = RemoteEngineProxy(str(tmp_path), "e0", timeout_s=1.0,
                                  hello_timeout_s=10.0)
        proxy.synthetic_inputs()
        srv.stop()
        t0 = time.monotonic()
        with pytest.raises(UnavailableError):
            # no per-request deadline: the proxy's 1s default applies
            proxy.infer([np.zeros((1, 2), np.float32)])
        assert time.monotonic() - t0 < 8
        proxy.close()

    def test_no_server_hello_times_out(self, tmp_path):
        proxy = RemoteEngineProxy(str(tmp_path), "ghost",
                                  hello_timeout_s=0.3)
        with pytest.raises(UnavailableError, match="hello"):
            proxy.synthetic_inputs()
        proxy.close()


class _FakeMonitor:
    def __init__(self, lost=()):
        self.lost = list(lost)
        self.raise_on_read = False

    def lost_workers(self):
        if self.raise_on_read:
            raise OSError("transport gone")
        return list(self.lost)


class TestRouterPeerLiveness:
    def _router(self):
        engines = [_FakeEngine("a"), _FakeEngine("b")]
        r = Router(engines, probe_interval_s=3600.0, probe_timeout_s=1.0,
                   close_engines=False)
        return r, engines

    def test_lost_process_evicts_owned_replica(self):
        r, _ = self._router()
        try:
            mon = _FakeMonitor()
            r.bind_peer_liveness(mon, {0: 1, 1: 2})  # replica -> process
            x = [np.zeros((1, 2), np.float32)]
            assert r.infer(x, timeout=10.0)
            mon.lost = [2]  # process hosting replica 1 died
            r.probe_now()
            snap = r.metrics.snapshot()
            assert snap["peer_evictions"] == 1
            # traffic keeps flowing through the surviving replica
            for _ in range(4):
                assert r.infer(x, timeout=10.0)
        finally:
            r.close()

    def test_healthy_processes_touch_nothing(self):
        r, _ = self._router()
        try:
            mon = _FakeMonitor(lost=[])
            r.bind_peer_liveness(mon, {0: 1, 1: 2})
            r.probe_now()
            assert r.metrics.snapshot()["peer_evictions"] == 0
        finally:
            r.close()

    def test_monitor_errors_are_advisory(self):
        # a broken liveness transport must not take the router down
        r, _ = self._router()
        try:
            mon = _FakeMonitor(lost=[2])
            mon.raise_on_read = True
            r.bind_peer_liveness(mon, {0: 1, 1: 2})
            r.probe_now()  # swallowed
            assert r.metrics.snapshot()["peer_evictions"] == 0
            assert r.infer([np.zeros((1, 2), np.float32)], timeout=10.0)
        finally:
            r.close()

    def test_unmapped_replicas_unaffected(self):
        r, _ = self._router()
        try:
            mon = _FakeMonitor(lost=[7])
            r.bind_peer_liveness(mon, {0: 7})  # replica 1 is local
            r.probe_now()
            assert r.metrics.snapshot()["peer_evictions"] == 1
            # replica 1 has no process mapping: still serving
            assert r.infer([np.zeros((1, 2), np.float32)], timeout=10.0)
        finally:
            r.close()
