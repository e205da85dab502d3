"""Two-process jax.distributed data parallelism on localhost.

Reference test strategy: python/paddle/fluid/tests/unittests/
test_dist_base.py:578,689-703 — spawn localhost trainer subprocesses,
run the distributed train loop, compare losses against the single-process
run.  Here the transport is jax.distributed's coordination service (the
NCCL-bootstrap replacement, SURVEY §7) with one CPU device per process:
a 2-process, 2-device global mesh.

Also exercises the cross-process liveness side-channel: each trainer
writes FileHeartbeat beats during the run (VERDICT r3 #7).
"""
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import json, os, sys
sys.path.insert(0, {repo!r})

import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn, optimizer as popt
from paddle_tpu.distributed import env as penv
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.heartbeat import FileHeartbeat

rank = int(os.environ["PADDLE_TRAINER_ID"])
penv.init_parallel_env()  # wires jax.distributed from the env vars
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2, jax.device_count()

hb = FileHeartbeat(os.environ["PT_TEST_HB"] + str(rank))

fleet._initialized = False
strategy = fleet.DistributedStrategy(dp_degree=2)
fleet.init(is_collective=True, strategy=strategy)

paddle.seed(0)
net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
opt = fleet.distributed_optimizer(popt.SGD(learning_rate=0.05))
model = paddle.Model(net, inputs=["x"], labels=["y"])
model.prepare(optimizer=opt, loss=nn.MSELoss())

rng = np.random.RandomState(1)
x = rng.randn(8, 8).astype(np.float32)
y = rng.randn(8, 1).astype(np.float32)
losses = []
for _ in range(4):
    loss, _ = model.train_batch([x], [y])
    losses.append(float(np.asarray(loss)))
    hb.beat()

if rank == 0:
    with open(os.environ["PT_TEST_OUT"], "w") as f:
        json.dump(losses, f)
print("worker", rank, "done", losses)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _single_process_losses():
    """Same model/batch, plain single-process run, for parity."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer as popt

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    model = paddle.Model(net, inputs=["x"], labels=["y"])
    model.prepare(optimizer=popt.SGD(learning_rate=0.05), loss=nn.MSELoss())
    rng = np.random.RandomState(1)
    x = rng.randn(8, 8).astype(np.float32)
    y = rng.randn(8, 1).astype(np.float32)
    return [float(np.asarray(model.train_batch([x], [y])[0]))
            for _ in range(4)]


@pytest.mark.slow
def test_two_process_dp_matches_single_process(tmp_path):
    port = _free_port()
    out = str(tmp_path / "losses.json")
    hb_base = str(tmp_path / "beat")
    worker = str(tmp_path / "worker.py")
    with open(worker, "w") as f:
        f.write(WORKER.format(repo=REPO))

    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PADDLE_TRAINER_ENDPOINTS": f"127.0.0.1:{port}",
            "PADDLE_TRAINERS_NUM": "2",
            "PADDLE_TRAINER_ID": str(rank),
            "PT_TEST_OUT": out,
            "PT_TEST_HB": hb_base,
        })
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))

    deadline = time.time() + 240
    for p in procs:
        timeout = max(1.0, deadline - time.time())
        try:
            stdout, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("2-process DP run hung")
        assert p.returncode == 0, stdout.decode()[-3000:]

    with open(out) as f:
        dist_losses = json.load(f)
    single = _single_process_losses()
    # identical model, identical global batch, SPMD grad averaging ==
    # single-process gradient: loss-for-loss parity
    np.testing.assert_allclose(dist_losses, single, rtol=1e-5, atol=1e-6)

    # heartbeat side-channel: both trainers beat during the run
    for rank in range(2):
        assert os.path.exists(hb_base + str(rank))
        assert os.path.getsize(hb_base + str(rank)) > 0
