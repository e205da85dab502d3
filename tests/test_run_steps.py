"""Fused multi-step execution: Executor.run_steps / StaticFunction.run_steps.

The contract under test (ISSUE 2): N chained optimizer steps inside one
jitted lax.scan produce params / optimizer state / buffers numerically
matching N sequential Executor.run calls — with the per-step host work
(lr schedules, RNG keys) moved into the traced loop — while issuing exactly
ONE device dispatch per chain.  Plus the compile-cache hygiene riding along:
the per-Executor LRU bound, hit/miss/eviction counters on trace_events, and
the analysis.retrace R403 cache-churn rule.
"""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.framework import trace_events
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.static.graph import reset_default_programs


@pytest.fixture(autouse=True)
def _fresh_programs():
    import paddle_tpu as paddle

    paddle.seed(0)  # builder param init draws from the global generator
    reset_default_programs()
    yield
    reset_default_programs()


def _key(name):
    # param names embed the program idx (_<idx>_<prefix>_<i>); strip it so
    # params from independently-built identical programs can be compared
    return name.split("_", 2)[2]


def _params(prog):
    return {_key(k): np.asarray(v) for k, v in prog.parameters_numpy().items()}


def _mlp(opt_factory):
    import paddle_tpu as paddle

    paddle.seed(0)  # identical init across the programs a test builds
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 13])
        y = fluid.data("y", [-1, 1])
        h = fluid.layers.fc(x, 8, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        opt = opt_factory()
        opt.minimize(loss)
    return main, startup, loss, opt


def _batches(n, bs=8, din=13):
    rng = np.random.RandomState(0)
    return (rng.rand(n, bs, din).astype(np.float32),
            rng.rand(n, bs, 1).astype(np.float32))


class TestRunStepsEquivalence:
    def _run_both(self, opt_factory, n=5):
        X, Y = _batches(n)
        main, startup, loss, opt_a = _mlp(opt_factory)
        exe = fluid.Executor()
        exe.run(startup)
        seq = [float(exe.run(main, feed={"x": X[t], "y": Y[t]},
                             fetch_list=[loss])[0]) for t in range(n)]

        main2, startup2, loss2, opt_b = _mlp(opt_factory)
        exe2 = fluid.Executor()
        exe2.run(startup2)
        fused, = exe2.run_steps(main2, feed={"x": X, "y": Y},
                                fetch_list=[loss2])
        return seq, np.asarray(fused), _params(main), _params(main2), \
            opt_a, opt_b, exe2

    def test_sgd_matches_sequential(self):
        seq, fused, pa, pb, _, _, exe2 = self._run_both(
            lambda: fluid.optimizer.SGD(learning_rate=0.1))
        np.testing.assert_allclose(fused.ravel(), seq, rtol=1e-5, atol=1e-6)
        for k, v in pb.items():
            np.testing.assert_allclose(v, pa[k], rtol=1e-5, atol=1e-6)

    def test_adam_matches_sequential(self):
        seq, fused, pa, pb, _, _, _ = self._run_both(
            lambda: fluid.optimizer.AdamOptimizer(learning_rate=0.01))
        np.testing.assert_allclose(fused.ravel(), seq, rtol=1e-5, atol=1e-6)
        for k, v in pb.items():
            np.testing.assert_allclose(v, pa[k], rtol=1e-5, atol=1e-6)

    def test_one_dispatch_per_chain(self):
        X, Y = _batches(6)
        main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe = fluid.Executor()
        exe.run(startup)  # empty startup: no device dispatch
        assert exe.dispatches == 0
        exe.run_steps(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        assert exe.dispatches == 1
        exe.run_steps(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        assert exe.dispatches == 2
        stats = exe.cache_stats()
        assert stats["hits"] == 1 and stats["misses"] == 1

    def test_graph_mode_scheduler_matches_sequential(self):
        # StepDecay has a closed-form value_at -> the lr is computed
        # in-graph as value_at(base_epoch + t)
        import paddle_tpu.optimizer as popt

        assert popt.lr.StepDecay(0.1, step_size=2).supports_in_graph()
        seq, fused, pa, pb, opt_a, opt_b, _ = self._run_both(
            lambda: fluid.optimizer.SGD(
                popt.lr.StepDecay(0.1, step_size=2, gamma=0.5)), n=6)
        np.testing.assert_allclose(fused.ravel(), seq, rtol=1e-5, atol=1e-6)
        for k, v in pb.items():
            np.testing.assert_allclose(v, pa[k], rtol=1e-5, atol=1e-6)
        # host scheduler advanced N steps, same as the sequential lane
        assert opt_b.lr_scheduler.last_epoch == opt_a.lr_scheduler.last_epoch

    def test_host_fallback_scheduler_matches_sequential(self):
        # LambdaDecay runs arbitrary Python -> no in-graph form; the lr
        # sequence is precomputed on host and scanned
        import paddle_tpu.optimizer as popt

        assert not popt.lr.LambdaDecay(
            0.1, lr_lambda=lambda e: 0.9 ** e).supports_in_graph()
        seq, fused, pa, pb, opt_a, opt_b, _ = self._run_both(
            lambda: fluid.optimizer.SGD(
                popt.lr.LambdaDecay(0.1, lr_lambda=lambda e: 0.9 ** e)), n=6)
        np.testing.assert_allclose(fused.ravel(), seq, rtol=1e-5, atol=1e-6)
        for k, v in pb.items():
            np.testing.assert_allclose(v, pa[k], rtol=1e-5, atol=1e-6)
        assert opt_b.lr_scheduler.last_epoch == opt_a.lr_scheduler.last_epoch

    def test_bn_buffers_match_sequential(self):
        import paddle_tpu as paddle

        def build():
            paddle.seed(0)
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                x = fluid.data("x", [-1, 6])
                y = fluid.data("y", [-1, 1])
                b = fluid.layers.batch_norm(fluid.layers.fc(x, 8))
                pred = fluid.layers.fc(b, 1)
                loss = fluid.layers.mean(
                    fluid.layers.square_error_cost(pred, y))
                fluid.optimizer.SGD(0.1).minimize(loss)
            return main, startup, loss

        n = 4
        rng = np.random.RandomState(0)
        X = rng.rand(n, 16, 6).astype(np.float32)
        Y = rng.rand(n, 16, 1).astype(np.float32)

        main, startup, loss = build()
        exe = fluid.Executor()
        exe.run(startup)
        bufs0 = {k: np.asarray(v) for k, v in main.buffers.items()}
        for t in range(n):
            exe.run(main, feed={"x": X[t], "y": Y[t]}, fetch_list=[loss])
        seq_bufs = {_key(k): np.asarray(v) for k, v in main.buffers.items()}
        assert any(not np.array_equal(bufs0[k], np.asarray(v))
                   for k, v in main.buffers.items())  # stats really moved

        main2, startup2, loss2 = build()
        exe2 = fluid.Executor()
        exe2.run(startup2)
        exe2.run_steps(main2, feed={"x": X, "y": Y}, fetch_list=[loss2])
        for k, v in main2.buffers.items():
            np.testing.assert_allclose(np.asarray(v), seq_bufs[_key(k)],
                                       rtol=1e-5, atol=1e-6)
        for k, v in _params(main2).items():
            np.testing.assert_allclose(v, _params(main)[k],
                                       rtol=1e-5, atol=1e-6)


class TestRunStepsAPI:
    def test_fetch_every_subsamples(self):
        X, Y = _batches(6)
        main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe = fluid.Executor()
        exe.run(startup)
        all_losses, = exe.run_steps(main, feed={"x": X, "y": Y},
                                    fetch_list=[loss])

        main2, startup2, loss2, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe2 = fluid.Executor()
        exe2.run(startup2)
        sub, = exe2.run_steps(main2, feed={"x": X, "y": Y},
                              fetch_list=[loss2], fetch_every=2)
        assert np.asarray(sub).shape[0] == 3
        np.testing.assert_allclose(np.asarray(sub),
                                   np.asarray(all_losses)[1::2],
                                   rtol=1e-5, atol=1e-6)

    def test_iterator_of_feed_dicts(self):
        X, Y = _batches(4)
        main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe = fluid.Executor()
        exe.run(startup)
        stacked, = exe.run_steps(main, feed={"x": X, "y": Y},
                                 fetch_list=[loss])

        main2, startup2, loss2, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe2 = fluid.Executor()
        exe2.run(startup2)
        it = ({"x": X[t], "y": Y[t]} for t in range(4))
        from_iter, = exe2.run_steps(main2, feed=it, fetch_list=[loss2])
        np.testing.assert_allclose(np.asarray(from_iter),
                                   np.asarray(stacked), rtol=1e-6)

    def test_constant_feeds_not_stacked(self):
        n = 3
        X, Y = _batches(n)
        main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe = fluid.Executor()
        exe.run(startup)
        # y constant across the chain: pass it UNstacked
        out, = exe.run_steps(main, feed={"x": X, "y": Y[0]},
                             fetch_list=[loss], constant_feeds=("y",))
        assert np.asarray(out).shape == (n,)

    def test_strategy_default_chain_length(self):
        from paddle_tpu.static import ExecutionStrategy

        n = 4
        X, Y = _batches(n)
        strat = ExecutionStrategy()
        strat.num_iteration_per_run = n
        main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe = fluid.Executor(strategy=strat)
        exe.run(startup)
        # all-constant feeds + no iterations=: length comes from strategy
        out, = exe.run_steps(main, feed={"x": X[0], "y": Y[0]},
                             fetch_list=[loss],
                             constant_feeds=("x", "y"))
        assert np.asarray(out).shape == (n,)

    def test_requires_optimizer(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [-1, 4])
            fluid.layers.fc(x, 2)
        with pytest.raises(InvalidArgumentError, match="minimize"):
            fluid.Executor().run_steps(
                main, feed={"x": np.zeros((3, 8, 4), np.float32)})

    def test_mismatched_leading_dim_rejected(self):
        X, Y = _batches(4)
        main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe = fluid.Executor()
        exe.run(startup)
        with pytest.raises(InvalidArgumentError, match="leading dim"):
            exe.run_steps(main, feed={"x": X, "y": Y[:2]},
                          fetch_list=[loss])


class TestCompileCache:
    def test_lru_eviction_at_cap(self):
        main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe = fluid.Executor(cache_capacity=2)
        exe.run(startup)
        rng = np.random.RandomState(0)

        def run(bs):
            exe.run(main, feed={"x": rng.rand(bs, 13).astype(np.float32),
                                "y": rng.rand(bs, 1).astype(np.float32)},
                    fetch_list=[loss])

        for bs in (4, 8, 16):  # 3 geometries through a capacity-2 cache
            run(bs)
        s = exe.cache_stats()
        assert s == {**s, "misses": 3, "evictions": 1, "size": 2}
        run(4)  # evicted (LRU) -> miss again
        assert exe.cache_stats()["misses"] == 4
        run(4)  # now resident -> hit
        assert exe.cache_stats()["hits"] == 1

    def test_counters_published_on_trace_events(self):
        events = []
        obs = lambda site, info: events.append((site, info))  # noqa: E731
        trace_events.register(obs)
        try:
            X, Y = _batches(2)
            main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
            exe = fluid.Executor()
            exe.run(startup)
            exe.run_steps(main, feed={"x": X, "y": Y}, fetch_list=[loss])
        finally:
            trace_events.unregister(obs)
        cache_ev = [(s, i) for s, i in events if s[0] == "executor_cache"]
        assert cache_ev, [s for s, _ in events]
        site, info = cache_ev[-1]
        assert site[1].startswith("executor#")
        assert info["misses"] == 1 and info["dispatches"] == 1
        # the run_steps compile also published a signature event
        assert any(s[0] == "executor" and i.get("mode", "").startswith(
            "run_steps") for s, i in events)

    def test_retrace_monitor_reports_r403_on_churn(self):
        from paddle_tpu.analysis import RetraceMonitor

        main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe = fluid.Executor(cache_capacity=1)
        exe.run(startup)
        rng = np.random.RandomState(0)
        with RetraceMonitor(budget=2) as mon:
            for bs in (4, 8, 16, 4, 8, 16):  # churn through capacity 1
                exe.run(main,
                        feed={"x": rng.rand(bs, 13).astype(np.float32),
                              "y": rng.rand(bs, 1).astype(np.float32)},
                        fetch_list=[loss])
        diags = mon.diagnostics()
        r403 = [d for d in diags if d.rule == "R403"]
        assert len(r403) == 1
        assert "evicted" in r403[0].message
        assert "executor_cache_capacity" in r403[0].hint
        assert mon.cache_stats()  # accessor exposes the snapshots

    def test_no_r403_below_budget(self):
        from paddle_tpu.analysis import RetraceMonitor

        X, Y = _batches(3)
        main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe = fluid.Executor()
        exe.run(startup)
        with RetraceMonitor(budget=8) as mon:
            for _ in range(5):  # steady-state: one signature, zero evictions
                exe.run(main, feed={"x": X[0], "y": Y[0]},
                        fetch_list=[loss])
        assert not [d for d in mon.diagnostics() if d.rule == "R403"]
        # and the counter events did NOT inflate R402 either
        assert not [d for d in mon.diagnostics() if d.rule == "R402"]


class TestOneChipPlacement:
    def test_second_window_does_not_recompile_on_a_one_device_mesh(
            self, use_mesh):
        """On one chip a meta_parallel model's ``constrain`` calls say
        nothing — but used to hand the chained step's outputs back under a
        mesh sharding its single-device inputs did not have, so the second
        window paid a second, placement-specialised XLA compile (minutes at
        BERT-base width).  jax.monitoring sees what the trace counter
        cannot."""
        import jax

        import paddle_tpu as paddle
        from paddle_tpu import optimizer as popt
        from paddle_tpu.models import BertForPretraining, bert_tiny
        from paddle_tpu.static.builders import layer_op
        from paddle_tpu.static.graph import record_call

        compiled = []

        def on_compile(name, secs, **kw):
            if name.endswith("backend_compile_duration"):
                compiled.append(kw.get("fun_name"))

        use_mesh(jax.devices()[:1])
        jax.monitoring.register_event_duration_secs_listener(on_compile)
        try:
            paddle.seed(0)
            cfg = bert_tiny()
            net = BertForPretraining(cfg)
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                ids_v = fluid.data("input_ids", [4, 8], "int32")
                mlm_y = fluid.data("mlm_labels", [4, 8], "int32")
                nsp_y = fluid.data("nsp_labels", [4, 1], "int32")
                mlm, nsp = layer_op(net, ids_v, prefix="bert")
                loss = record_call(net.loss, mlm, nsp, mlm_y, nsp_y,
                                   prefix="loss")
                popt.SGD(learning_rate=0.1).minimize(loss)
            rng = np.random.RandomState(0)
            ids = rng.randint(0, cfg.vocab_size, (4, 8)).astype(np.int32)
            feeds = {"input_ids": ids, "mlm_labels": ids,
                     "nsp_labels": rng.randint(0, 2, (4, 1)).astype(np.int32)}
            exe = fluid.Executor()
            exe.run(startup)

            def window():
                return exe.run_steps(main, feed=feeds, fetch_list=[loss],
                                     iterations=2,
                                     constant_feeds=tuple(feeds))

            window()
            assert "jit(chain)" in compiled
            del compiled[:]
            window()
            window()
            assert "jit(chain)" not in compiled, compiled
        finally:
            jax.monitoring.unregister_event_duration_listener(on_compile)


class TestDataLoaderSuperbatch:
    def test_superbatch_stacks_k_batches(self):
        from paddle_tpu.io import DataLoader
        from paddle_tpu.io.dataset import Dataset

        class DS(Dataset):
            def __len__(self):
                return 10

            def __getitem__(self, i):
                return np.full((3,), i, np.float32), np.int32(i)

        dl = DataLoader(DS(), batch_size=2, superbatch=2, return_numpy=True)
        items = list(dl)
        # 5 batches of 2 -> superbatches of 2, 2, and a trailing 1
        shapes = [[np.asarray(f).shape for f in it] for it in items]
        assert shapes == [[(2, 2, 3), (2, 2)], [(2, 2, 3), (2, 2)],
                          [(1, 2, 3), (1, 2)]]
        np.testing.assert_array_equal(np.asarray(items[0][1]),
                                      [[0, 1], [2, 3]])

    def test_superbatch_feeds_run_steps(self):
        from paddle_tpu.io import DataLoader
        from paddle_tpu.io.dataset import Dataset

        rng = np.random.RandomState(0)
        Xd = rng.rand(32, 13).astype(np.float32)
        Yd = rng.rand(32, 1).astype(np.float32)

        class DS(Dataset):
            def __len__(self):
                return 32

            def __getitem__(self, i):
                return Xd[i], Yd[i]

        main, startup, loss, _ = _mlp(lambda: fluid.optimizer.SGD(0.1))
        exe = fluid.Executor()
        exe.run(startup)
        dl = DataLoader(DS(), batch_size=8, superbatch=4, return_numpy=True)
        for xb, yb in dl:  # one fused dispatch per superbatch
            out, = exe.run_steps(main, feed={"x": xb, "y": yb},
                                 fetch_list=[loss])
            assert np.asarray(out).shape == (4,)
        assert exe.dispatches == 1  # 32 samples / (8*4) = one superbatch


class TestStaticFunctionRunSteps:
    def _net(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(8, 4)
                self.bn = nn.BatchNorm1D(4)

            def forward(self, x):
                return self.bn(self.fc(x))

        paddle.seed(0)
        net = Net()
        net.train()
        return net

    def test_matches_eager_sequential_with_bn(self):
        import paddle_tpu as paddle
        from paddle_tpu import jit

        rng = np.random.RandomState(0)
        X = rng.rand(5, 16, 8).astype(np.float32)

        net_a = self._net()
        seq = [np.asarray(net_a(paddle.to_tensor(X[t]))) for t in range(5)]
        bufs_a = {k: np.asarray(v.value)
                  for k, v in dict(net_a.named_buffers()).items()}

        net_b = self._net()
        out = jit.to_static(net_b).run_steps(X)
        assert np.asarray(out).shape == (5, 16, 4)
        for t in range(5):
            np.testing.assert_allclose(np.asarray(out)[t], seq[t],
                                       rtol=1e-5, atol=1e-6)
        for k, v in dict(net_b.named_buffers()).items():
            np.testing.assert_allclose(np.asarray(v.value), bufs_a[k],
                                       rtol=1e-5, atol=1e-6)

    def test_iterations_kwarg_on_call(self):
        from paddle_tpu import jit

        rng = np.random.RandomState(0)
        X = rng.rand(3, 16, 8).astype(np.float32)
        net = self._net()
        sf = jit.to_static(net)
        out = sf(X, iterations=3)
        assert np.asarray(out).shape == (3, 16, 4)

    def test_fetch_every(self):
        from paddle_tpu import jit

        rng = np.random.RandomState(0)
        X = rng.rand(6, 16, 8).astype(np.float32)
        out = jit.to_static(self._net()).run_steps(X, fetch_every=3)
        assert np.asarray(out).shape == (2, 16, 4)

    def test_eager_fallback_when_to_static_disabled(self):
        from paddle_tpu import jit

        rng = np.random.RandomState(0)
        X = rng.rand(3, 16, 8).astype(np.float32)
        net = self._net()
        sf = jit.to_static(net)
        fused = np.asarray(sf.run_steps(X))
        net2 = self._net()
        sf2 = jit.to_static(net2)
        jit.ProgramTranslator().enable(False)
        try:
            eager = np.asarray(sf2.run_steps(X))
        finally:
            jit.ProgramTranslator().enable(True)
        np.testing.assert_allclose(eager, fused, rtol=1e-5, atol=1e-6)
