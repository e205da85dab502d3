"""paddle_tpu.resilience: fault injection, retry, circuit breaking,
crash-safe resume, and the preemption exit contract.

Chaos engineering needs deterministic chaos: every test here drives the
failure modes through seeded FaultPlans, injectable clocks/sleeps and
byte-level corruption, and asserts exact recovery behavior — no flaky
timing, no real devices harmed.
"""
import os
import signal
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer as popt
from paddle_tpu.framework import serialization, trace_events
from paddle_tpu.framework.errors import (
    EnforceNotMet,
    InvalidArgumentError,
    TransientDeviceError,
    UnavailableError,
    is_transient,
    wrap_transient,
)
from paddle_tpu.incubate.checkpoint import AutoCheckpoint
from paddle_tpu.resilience import (
    PREEMPTION_EXIT_CODE,
    CircuitBreaker,
    FaultPlan,
    FaultRule,
    PreemptionHandler,
    RetryPolicy,
    fault_point,
)
from paddle_tpu.resilience import circuit as circuit_mod
from paddle_tpu.resilience import faults as faults_mod
from paddle_tpu.resilience import retry as retry_mod


def _model(seed=0):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 2))
    loss = nn.CrossEntropyLoss()
    model = paddle.Model(net, inputs=["x"], labels=["y"])
    model.prepare(optimizer=popt.Adam(learning_rate=1e-2), loss=loss)
    return model


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(16, 4).astype(np.float32),
             rng.randint(0, 2, size=(16,)).astype(np.int32))
            for _ in range(n)]


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    faults_mod.remove()
    retry_mod.reset_stats()
    warm = retry_mod._warm
    retry_mod._warm = False
    yield
    faults_mod.remove()
    retry_mod._warm = warm


# ---------------------------------------------------------------------------
# error classification
# ---------------------------------------------------------------------------
class TestTransientClassification:
    def test_typed_classification(self):
        assert is_transient(TransientDeviceError("x"))
        assert is_transient(UnavailableError("x"))
        assert not is_transient(InvalidArgumentError("x"))
        assert not is_transient(ValueError("x"))

    def test_runtime_message_patterns(self):
        assert is_transient(RuntimeError("RESOURCE_EXHAUSTED: too many "
                                         "concurrent requests"))
        assert is_transient(OSError("Connection reset by peer"))
        assert not is_transient(RuntimeError("INVALID_ARGUMENT: bad shape"))

    @pytest.mark.parametrize("msg", [
        # what XLA:TPU says when a program does not fit the chip's HBM
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 16.17G of 15.75G hbm. Exceeded "
        "hbm capacity by 428.41M.",
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
        "allocate 1.20G. That was not possible. There are 410.2M free.",
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "123456 bytes.",
    ])
    def test_out_of_memory_is_not_retried(self, msg):
        # the same program on the same chip fails the same way: a retry
        # only re-pays the compile before failing again
        assert not is_transient(RuntimeError(msg))
        policy = RetryPolicy(max_attempts=3, backoff_ms=0.0)
        calls = []

        def oom():
            calls.append(1)
            raise RuntimeError(msg)

        with pytest.raises(RuntimeError):
            policy.call(oom)
        assert len(calls) == 1

    def test_wrap_transient_chains_cause(self):
        src = RuntimeError("UNAVAILABLE: socket closed")
        wrapped = wrap_transient(src)
        assert isinstance(wrapped, TransientDeviceError)
        assert wrapped.__cause__ is src
        # already-typed and non-transient errors pass through untouched
        tde = TransientDeviceError("x")
        assert wrap_transient(tde) is tde
        fatal = ValueError("x")
        assert wrap_transient(fatal) is fatal


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------
class TestRetryPolicy:
    def test_retries_then_succeeds(self):
        sleeps = []
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise TransientDeviceError("hiccup")
            return "ok"

        pol = RetryPolicy(max_attempts=5, backoff_ms=10, name="t1",
                          sleep=sleeps.append)
        assert pol.call(flaky) == "ok"
        assert calls["n"] == 3
        assert len(sleeps) == 2
        s = retry_mod.stats("t1")
        assert s["attempts"] == 3 and s["retries"] == 2

    def test_fatal_error_propagates_on_attempt_one(self):
        calls = {"n": 0}

        def fatal():
            calls["n"] += 1
            raise InvalidArgumentError("config bug")

        pol = RetryPolicy(max_attempts=5, backoff_ms=1, name="t2",
                          sleep=lambda s: None)
        with pytest.raises(InvalidArgumentError):
            pol.call(fatal)
        assert calls["n"] == 1

    def test_gives_up_after_max_attempts(self):
        pol = RetryPolicy(max_attempts=3, backoff_ms=1, name="t3",
                          sleep=lambda s: None)
        with pytest.raises(TransientDeviceError):
            pol.call(lambda: (_ for _ in ()).throw(
                TransientDeviceError("always")))
        s = retry_mod.stats("t3")
        assert s["attempts"] == 3 and s["giveups"] == 1

    def test_backoff_schedule_is_seeded_deterministic(self):
        a = RetryPolicy(max_attempts=6, backoff_ms=100, seed=7)
        b = RetryPolicy(max_attempts=6, backoff_ms=100, seed=7)
        c = RetryPolicy(max_attempts=6, backoff_ms=100, seed=8)
        assert a.schedule() == b.schedule()
        assert a.schedule() != c.schedule()
        # exponential growth under the cap, jitter within +/-25%
        base = [0.1 * 2 ** i for i in range(5)]
        for got, want in zip(a.schedule(), base):
            assert want * 0.75 <= got <= want * 1.25

    def test_backoff_cap(self):
        pol = RetryPolicy(max_attempts=20, backoff_ms=100, jitter=0.0,
                          max_backoff_ms=400)
        assert max(pol.schedule()) <= 0.4 + 1e-9

    def test_deadline_abandons_retry(self):
        t = {"now": 0.0}
        pol = RetryPolicy(max_attempts=10, backoff_ms=500, jitter=0.0,
                          deadline_ms=800, name="t4",
                          sleep=lambda s: t.__setitem__("now", t["now"] + s),
                          clock=lambda: t["now"])
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            raise TransientDeviceError("x")

        with pytest.raises(TransientDeviceError):
            pol.call(flaky)
        # 0.5s + 1.0s backoffs: the second retry would cross the 0.8s
        # deadline, so exactly two attempts run
        assert calls["n"] == 2
        assert retry_mod.stats("t4")["deadline_giveups"] == 1

    def test_decorator_form(self):
        pol = RetryPolicy(max_attempts=2, backoff_ms=1, sleep=lambda s: None)
        calls = {"n": 0}

        @pol
        def once_flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise TransientDeviceError("x")
            return 42

        assert once_flaky() == 42

    def test_retry_on_tuple_of_types(self):
        pol = RetryPolicy(max_attempts=3, backoff_ms=1, retry_on=(KeyError,),
                          sleep=lambda s: None)
        calls = {"n": 0}

        def f():
            calls["n"] += 1
            if calls["n"] < 2:
                raise KeyError("x")
            return "ok"

        assert pol.call(f) == "ok"
        with pytest.raises(ValueError):
            pol.call(lambda: (_ for _ in ()).throw(ValueError("fatal")))


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------
class TestFaultInjection:
    def test_noop_without_plan(self):
        assert not faults_mod.active()
        fault_point("anything")  # must not raise, count, or allocate

    def test_nth_fires_exactly_once(self):
        plan = FaultPlan.parse("site=s,nth=3,error=TransientDeviceError")
        with plan:
            fault_point("s")
            fault_point("s")
            with pytest.raises(TransientDeviceError):
                fault_point("s")
            fault_point("s")  # past nth: silent
        assert plan.stats() == {"s": {"calls": 4, "fired": 1}}

    def test_every_with_times_cap(self):
        plan = FaultPlan.parse("site=s,every=2,times=2,error=OSError")
        fired = 0
        with plan:
            for _ in range(10):
                try:
                    fault_point("s")
                except OSError:
                    fired += 1
        assert fired == 2

    def test_probabilistic_pattern_is_seed_deterministic(self):
        def pattern(seed):
            plan = FaultPlan([FaultRule("s", p=0.5, seed=seed)])
            out = []
            with plan:
                for _ in range(20):
                    try:
                        fault_point("s")
                        out.append(0)
                    except EnforceNotMet:
                        out.append(1)
            return out

        assert pattern(3) == pattern(3)
        assert pattern(3) != pattern(4)

    def test_latency_rule_sleeps_instead_of_raising(self):
        plan = FaultPlan.parse("site=s,nth=1,latency_ms=30")
        with plan:
            t0 = time.monotonic()
            fault_point("s")  # must not raise
            assert time.monotonic() - t0 >= 0.025

    def test_parse_rejects_bad_specs(self):
        for bad in ("", "site=s", "site=s,nth=1,every=2",
                    "site=s,p=1.5", "nonsense", "site=s,nth=1,error=dict"):
            with pytest.raises(EnforceNotMet):
                FaultPlan.parse(bad)

    def test_plans_compose_multiple_sites(self):
        plan = FaultPlan.parse(
            "site=a,nth=1,error=OSError; site=b,nth=1,error=ValueError")
        with plan:
            with pytest.raises(OSError):
                fault_point("a")
            with pytest.raises(ValueError):
                fault_point("b")
            fault_point("c")  # no rule: untouched


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------
class TestCircuitBreaker:
    def _breaker(self, **kw):
        t = {"now": 0.0}
        kw.setdefault("failure_threshold", 0.5)
        kw.setdefault("window", 4)
        kw.setdefault("cooldown_ms", 1000)
        kw.setdefault("half_open_probes", 2)
        br = CircuitBreaker("test", clock=lambda: t["now"], **kw)
        return br, t

    def test_opens_only_on_full_window(self):
        br, _ = self._breaker()
        for _ in range(3):
            br.record_failure("k")  # 3 < window: never judged
        assert br.state("k") == circuit_mod.CLOSED
        br.record_failure("k")  # full window, 100% failure
        assert br.state("k") == circuit_mod.OPEN
        assert not br.allow("k")

    def test_below_threshold_stays_closed(self):
        br, _ = self._breaker()
        for ok in (True, True, True, False) * 3:
            (br.record_success if ok else br.record_failure)("k")
        assert br.state("k") == circuit_mod.CLOSED

    def test_half_open_probe_recovery(self):
        br, t = self._breaker()
        for _ in range(4):
            br.record_failure("k")
        assert not br.allow("k")
        t["now"] += 1.1  # cooldown elapsed
        assert br.allow("k")       # probe 1 admitted
        assert br.allow("k")       # probe 2 admitted
        assert not br.allow("k")   # probes exhausted: shed
        assert br.state("k") == circuit_mod.HALF_OPEN
        br.record_success("k")
        assert br.state("k") == circuit_mod.HALF_OPEN  # 1 of 2 probes
        br.record_success("k")
        assert br.state("k") == circuit_mod.CLOSED
        assert br.allow("k")

    def test_failed_probe_reopens(self):
        br, t = self._breaker(half_open_probes=1)
        for _ in range(4):
            br.record_failure("k")
        t["now"] += 1.1
        assert br.allow("k")
        br.record_failure("k")
        assert br.state("k") == circuit_mod.OPEN
        assert not br.allow("k")  # cooldown restarts from the re-open

    def test_keys_are_independent(self):
        br, _ = self._breaker()
        for _ in range(4):
            br.record_failure(0)
        assert not br.allow(0)
        assert br.allow(1)

    def test_stats_and_warm_flap_counter(self):
        br, t = self._breaker(half_open_probes=1)
        for _ in range(4):
            br.record_failure("k")
        retry_mod.mark_warm()
        t["now"] += 1.1
        br.allow("k")
        br.record_failure("k")  # re-open after warm: a flap
        s = br.stats()
        assert s["opens"] == 2 and s["opens_after_warm"] == 1
        assert s["open_keys"] == 1
        assert s["keys"]["k"]["state"] == circuit_mod.OPEN


# ---------------------------------------------------------------------------
# corruption fallback + crash-safe resume
# ---------------------------------------------------------------------------
class TestCorruptionFallback:
    def test_truncated_magic_file_raises_typed_error(self, tmp_path):
        p = str(tmp_path / "ck.pdparams")
        serialization.save({"w": np.ones(3, np.float32)}, p)
        with open(p, "rb") as f:
            blob = f.read()
        with open(p, "wb") as f:
            f.write(blob[: len(blob) // 2])
        with pytest.raises(InvalidArgumentError, match="corrupt"):
            serialization.load(p)

    def test_bitflip_detected_by_manifest(self, tmp_path):
        model = _model()
        acp = AutoCheckpoint(model, str(tmp_path), async_save=False)
        acp.save(epoch=0)
        d = acp.latest_dir()
        # flip one payload byte far from the pickle header: the file still
        # unpickles, only the digest catches it
        p = os.path.join(d, "m.pdparams")
        blob = bytearray(open(p, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(p, "wb").write(bytes(blob))
        with pytest.raises(EnforceNotMet):
            acp._load_verified(d)

    def test_resume_falls_back_and_quarantines(self, tmp_path):
        data = _batches(4)
        model = _model(seed=1)
        acp = AutoCheckpoint(model, str(tmp_path), keep_max=5,
                             async_save=False)
        for i, (x, y) in enumerate(data):
            model.train_batch([x], [y])
            acp.save(epoch=i)
        dirs = acp.committed_dirs()
        assert len(dirs) == 4
        good = acp._load_verified(dirs[1])  # second-newest, pre-corruption
        # corrupt the NEWEST checkpoint's params payload
        p = os.path.join(dirs[0], "m.pdparams")
        blob = bytearray(open(p, "rb").read())
        blob[-20] ^= 0x01
        open(p, "wb").write(bytes(blob))

        m2 = _model(seed=9)
        acp2 = AutoCheckpoint(m2, str(tmp_path))
        meta = acp2.resume()
        assert meta is not None
        # landed on the previous (healthy) checkpoint...
        assert meta["counter"] == good["meta"]["counter"]
        for k, v in good["params"].items():
            np.testing.assert_array_equal(
                np.asarray(m2.network.state_dict()[k]), v)
        # ...and the corrupt dir is quarantined, not deleted
        names = os.listdir(tmp_path)
        assert any(n.startswith("corrupt-") for n in names)
        assert os.path.basename(dirs[0]) not in names

    def test_all_corrupt_resumes_fresh(self, tmp_path):
        model = _model()
        acp = AutoCheckpoint(model, str(tmp_path), async_save=False)
        acp.save(epoch=0)
        p = os.path.join(acp.latest_dir(), "m.pdparams")
        open(p, "wb").write(b"garbage")
        m2 = _model(seed=3)
        acp2 = AutoCheckpoint(m2, str(tmp_path))
        assert acp2.resume() is None

    def test_meta_missing_file_detected(self, tmp_path):
        model = _model()
        acp = AutoCheckpoint(model, str(tmp_path), async_save=False)
        acp.save(epoch=0)
        d = acp.latest_dir()
        os.unlink(os.path.join(d, "m.pdopt"))
        with pytest.raises(EnforceNotMet):
            acp._load_verified(d)


class TestCheckpointWriterResilience:
    def test_transient_write_fault_is_retried(self, tmp_path):
        plan = FaultPlan.parse(
            "site=checkpoint.write,nth=1,error=TransientDeviceError")
        model = _model()
        acp = AutoCheckpoint(
            model, str(tmp_path), async_save=False,
            retry=RetryPolicy(max_attempts=3, backoff_ms=1,
                              name="ckpt-test", sleep=lambda s: None))
        with plan:
            acp.save(epoch=0)  # first write raises, retry lands it
        assert acp.latest_dir() is not None
        assert plan.stats()["checkpoint.write"]["fired"] == 1

    def test_worker_error_latched_and_later_saves_drain(self, tmp_path):
        # snapshot 1 fails fatally (retry can't help); snapshots 2 and 3
        # must still commit, and close() must raise the FIRST error
        plan = FaultPlan.parse(
            "site=checkpoint.write,nth=1,error=InvalidArgumentError")
        model = _model()
        acp = AutoCheckpoint(
            model, str(tmp_path),
            retry=RetryPolicy(max_attempts=2, backoff_ms=1,
                              name="ckpt-latch", sleep=lambda s: None))
        # the writer is held until all three are queued: a save() called
        # after snapshot 1 has failed raises the latched error itself
        # (the next test), and under load the worker used to win that race
        queued, write = threading.Event(), acp._write
        acp._write = lambda snap: (queued.wait(30), write(snap))[1]
        with plan:
            acp.save(epoch=0)
            acp.save(epoch=1)
            acp.save(epoch=2)
            queued.set()
            with pytest.raises(InvalidArgumentError, match="injected"):
                acp.close()
        assert len(acp.committed_dirs()) == 2
        # the latch is cleared by close(); a fresh close is clean
        acp.close()

    def test_save_raises_latched_error_without_clearing(self, tmp_path):
        plan = FaultPlan.parse(
            "site=checkpoint.write,nth=1,error=InvalidArgumentError")
        model = _model()
        acp = AutoCheckpoint(
            model, str(tmp_path),
            retry=RetryPolicy(max_attempts=2, backoff_ms=1,
                              name="ckpt-latch2", sleep=lambda s: None))
        with plan:
            acp.save(epoch=0)
            deadline = time.monotonic() + 5
            while acp._worker_err is None and time.monotonic() < deadline:
                time.sleep(0.01)
            with pytest.raises(InvalidArgumentError):
                acp.save(epoch=1)
            with pytest.raises(InvalidArgumentError):  # still latched
                acp.close()


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------
class TestPreemption:
    def test_sigterm_saves_and_exits_75(self, tmp_path):
        model = _model()
        acp = AutoCheckpoint(model, str(tmp_path), async_save=False)
        acp.step(epoch=4)  # records last_epoch without saving
        codes = []
        h = PreemptionHandler(acp, _exit=codes.append)
        h.install()
        try:
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.monotonic() + 5
            while not codes and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            h.uninstall()
        assert codes == [PREEMPTION_EXIT_CODE]
        d = acp.latest_dir()
        assert d is not None
        meta = serialization.load(os.path.join(d, "meta.pdmeta"))
        assert meta["kind"] == "preempt" and meta["epoch"] == 4

    def test_failed_final_save_still_exits(self):
        class Broken:
            last_epoch = 0

            def final_save(self, epoch):
                raise OSError("disk gone")

        codes = []
        h = PreemptionHandler(Broken(), _exit=codes.append)
        h._on_sigterm(signal.SIGTERM, None)
        assert codes == [PREEMPTION_EXIT_CODE]

    def test_watch_preemption_exit_skips_restart_budget(self, tmp_path):
        from paddle_tpu.distributed.parallel import watch

        marker = tmp_path / "second_run"
        script = tmp_path / "trainer.py"
        script.write_text(textwrap.dedent(f"""
            import os, sys
            marker = {str(marker)!r}
            if os.path.exists(marker):
                sys.exit(0)
            open(marker, "w").close()
            sys.exit({PREEMPTION_EXIT_CODE})
        """))
        # max_restarts=0: a crash exit would NOT be restarted, so rc == 0
        # proves the preemption exit bypassed the budget
        rc = watch([sys.executable, str(script)], max_restarts=0,
                   _sleep=0.05)
        assert rc == 0

    def test_watch_other_exit_codes_still_burn_budget(self, tmp_path):
        from paddle_tpu.distributed.parallel import watch

        script = tmp_path / "trainer.py"
        script.write_text("import sys; sys.exit(7)")
        rc = watch([sys.executable, str(script)], max_restarts=0)
        assert rc == 7


# ---------------------------------------------------------------------------
# serving integration: batcher deadline sweep, circuit, retry
# ---------------------------------------------------------------------------
class TestBatcherResilience:
    def test_deadline_sweep_without_traffic(self):
        from paddle_tpu.serving.batcher import MicroBatcher
        from paddle_tpu.framework.errors import ExecutionTimeoutError

        ran = []
        mb = MicroBatcher(lambda x: 0, lambda b, rs: ran.append(b) or
                          [r.inputs[0] for r in rs],
                          max_batch_size=8, max_queue_delay_ms=5000,
                          name="sweep-test")
        try:
            f = mb.submit([1], deadline_ms=50)
            t0 = time.monotonic()
            with pytest.raises(ExecutionTimeoutError):
                f.result(3)
            # with no sweep this would only fail after the 5s batch delay
            assert time.monotonic() - t0 < 1.0
            assert ran == []  # expired before wasting a device slot
        finally:
            mb.close(drain=False)

    def test_circuit_opens_sheds_and_recovers(self):
        from paddle_tpu.serving.batcher import MicroBatcher

        state = {"fail": True, "runs": 0}

        def runner(bucket, reqs):
            state["runs"] += 1
            if state["fail"]:
                raise RuntimeError("poisoned bucket")
            return [r.inputs[0] for r in reqs]

        br = CircuitBreaker("mb-test", failure_threshold=0.5, window=2,
                            cooldown_ms=80, half_open_probes=1)
        mb = MicroBatcher(lambda x: 0, runner, max_batch_size=1,
                          max_queue_delay_ms=1, breaker=br, name="cb-test")
        try:
            outcomes = []
            for i in range(5):
                try:
                    mb.submit([i]).result(2)
                    outcomes.append("ok")
                except UnavailableError:
                    outcomes.append("shed")
                except RuntimeError:
                    outcomes.append("err")
            assert outcomes[:2] == ["err", "err"]  # window fills
            assert set(outcomes[2:]) == {"shed"}   # then the circuit sheds
            runs_while_open = state["runs"]
            state["fail"] = False
            time.sleep(0.12)  # cooldown -> half-open probe next batch
            assert mb.submit([99]).result(2) == 99
            assert br.state(0) == circuit_mod.CLOSED
            assert state["runs"] == runs_while_open + 1
            assert mb._worker.is_alive()
            assert mb.metrics.snapshot()["circuit_shed"] >= 3
        finally:
            mb.close()

    def test_runner_retry_via_fault_plan(self):
        from paddle_tpu.serving.batcher import MicroBatcher

        plan = FaultPlan.parse(
            "site=serving.runner,nth=1,error=TransientDeviceError")
        mb = MicroBatcher(
            lambda x: 0, lambda b, rs: [r.inputs[0] for r in rs],
            max_batch_size=1, max_queue_delay_ms=1,
            retry=RetryPolicy(max_attempts=3, backoff_ms=1,
                              name="runner-test", sleep=lambda s: None),
            name="retry-test")
        try:
            with plan:
                assert mb.submit([7]).result(2) == 7
            assert plan.stats()["serving.runner"]["fired"] == 1
            assert retry_mod.stats("runner-test")["retries"] == 1
        finally:
            mb.close()


# ---------------------------------------------------------------------------
# executor integration
# ---------------------------------------------------------------------------
class TestExecutorRetry:
    def test_transient_dispatch_fault_is_retried(self):
        from paddle_tpu import fluid

        plan = FaultPlan.parse(
            "site=executor.dispatch,nth=1,error=TransientDeviceError")
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [-1, 4])
            out = fluid.layers.fc(x, 2)
        exe = fluid.Executor()
        exe.run(startup)
        with plan:
            res, = exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                           fetch_list=[out])
        assert res.shape == (2, 2)
        assert plan.stats()["executor.dispatch"]["fired"] == 1
        assert retry_mod.stats(f"executor#{exe._idx}")["retries"] == 1
        assert exe.dispatches == 1  # the retried dispatch counts once


# ---------------------------------------------------------------------------
# observability: F801 + profiler section
# ---------------------------------------------------------------------------
class TestF801:
    def test_retry_storm_flagged_after_warm(self):
        from paddle_tpu.analysis import RetraceMonitor, render_text

        retry_mod.mark_warm()
        pol = RetryPolicy(max_attempts=2, backoff_ms=1, name="storm",
                          sleep=lambda s: None)
        with RetraceMonitor(budget=3) as mon:
            for _ in range(6):
                with pytest.raises(TransientDeviceError):
                    pol.call(lambda: (_ for _ in ()).throw(
                        TransientDeviceError("x")))
        diags = [d for d in mon.diagnostics() if d.rule == "F801"]
        assert len(diags) == 1
        assert "storm" in diags[0].message
        assert "F801" in render_text(diags)

    def test_circuit_flapping_flagged(self):
        from paddle_tpu.analysis import RetraceMonitor

        retry_mod.mark_warm()
        t = {"now": 0.0}
        br = CircuitBreaker("flappy", failure_threshold=0.5, window=1,
                            cooldown_ms=10, half_open_probes=1,
                            clock=lambda: t["now"])
        with RetraceMonitor(budget=8) as mon:
            br.record_failure("k")  # open 1
            for _ in range(3):      # three half-open probe failures
                t["now"] += 0.02
                assert br.allow("k")
                br.record_failure("k")
        diags = [d for d in mon.diagnostics() if d.rule == "F801"]
        assert len(diags) == 1
        assert "flappy" in diags[0].message

    def test_quiet_system_raises_nothing(self):
        from paddle_tpu.analysis import RetraceMonitor

        retry_mod.mark_warm()
        pol = RetryPolicy(max_attempts=3, backoff_ms=1, name="quiet",
                          sleep=lambda s: None)
        with RetraceMonitor(budget=8) as mon:
            pol.call(lambda: "fine")
        assert [d for d in mon.diagnostics() if d.rule == "F801"] == []

    def test_resilience_stats_accessor(self):
        from paddle_tpu.analysis import RetraceMonitor

        pol = RetryPolicy(max_attempts=2, backoff_ms=1, name="acc",
                          sleep=lambda s: None)
        with RetraceMonitor() as mon:
            with pytest.raises(TransientDeviceError):
                pol.call(lambda: (_ for _ in ()).throw(
                    TransientDeviceError("x")))
        assert mon.resilience_stats("retry:acc")["retries"] == 1


class TestProfilerSection:
    def test_faults_and_retries_section_renders(self):
        from paddle_tpu import profiler

        profiler.reset_profiler()
        pol = RetryPolicy(max_attempts=2, backoff_ms=1, name="prof-sec",
                          sleep=lambda s: None)
        with pytest.raises(TransientDeviceError):
            pol.call(lambda: (_ for _ in ()).throw(
                TransientDeviceError("x")))
        text = profiler.summary()
        assert "Faults & retries" in text
        assert "prof-sec" in text
