"""Multi-host launch/env wiring (mock form).

Reference capability: fleet launch env plumbing (fleet/launch_utils.py
PADDLE_TRAINER_ID/PADDLE_TRAINER_ENDPOINTS → trainer bootstrap; tested by
the reference's test_launch.sh).  TPU-native: those env vars must reach
``jax.distributed.initialize``.  Real multi-host needs multiple machines,
so initialize is captured by a stub — exactly how the reference fakes
multi-rank in test_collective_base.py.
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu.distributed.env as penv
from paddle_tpu.distributed.parallel import launch, spawn


@pytest.fixture
def clean_env(monkeypatch):
    """Reset the module singleton + scrub trainer vars around each test.

    Also hermeticizes SPAWNED CHILDREN (launch/watch run real python
    subprocesses that inherit os.environ): these are CPU tests, so the
    children must see a plain CPU environment regardless of the host's
    accelerator config.
    """
    penv._initialized = False
    for k in ("COORDINATOR_ADDRESS", "PADDLE_TRAINER_ENDPOINTS",
              "PADDLE_TRAINERS_NUM", "PADDLE_TRAINER_ID",
              "TPU_SKIP_MDS_QUERY", "TPU_WORKER_HOSTNAMES",
              "TPU_WORKER_ID"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    yield monkeypatch
    penv._initialized = False


@pytest.fixture
def capture_init(clean_env):
    calls = []

    def fake_initialize(coordinator_address=None, num_processes=None,
                        process_id=None, **kw):
        calls.append({"addr": coordinator_address, "nproc": num_processes,
                      "pid": process_id})

    clean_env.setattr(penv.jax.distributed, "initialize", fake_initialize)
    return calls


class TestInitParallelEnv:
    def test_single_host_is_noop(self, capture_init):
        env = penv.init_parallel_env()
        assert capture_init == []  # no rendezvous for one host
        assert env.rank == 0
        assert penv.is_initialized()

    def test_paddle_trainer_env_wires_rendezvous(self, clean_env, capture_init):
        clean_env.setenv("PADDLE_TRAINER_ENDPOINTS",
                         "10.0.0.1:6170,10.0.0.2:6170")
        clean_env.setenv("PADDLE_TRAINERS_NUM", "2")
        clean_env.setenv("PADDLE_TRAINER_ID", "1")
        penv.init_parallel_env()
        assert capture_init == [
            {"addr": "10.0.0.1:6170", "nproc": 2, "pid": 1}]

    def test_coordinator_address_beats_endpoints(self, clean_env, capture_init):
        clean_env.setenv("COORDINATOR_ADDRESS", "coord:1234")
        clean_env.setenv("PADDLE_TRAINER_ENDPOINTS", "other:1,other:2")
        clean_env.setenv("PADDLE_TRAINERS_NUM", "4")
        clean_env.setenv("PADDLE_TRAINER_ID", "3")
        penv.init_parallel_env()
        assert capture_init == [{"addr": "coord:1234", "nproc": 4, "pid": 3}]

    def test_explicit_args_beat_env(self, clean_env, capture_init):
        clean_env.setenv("PADDLE_TRAINERS_NUM", "8")
        penv.init_parallel_env(coordinator_address="a:1", num_processes=2,
                               process_id=1)
        assert capture_init == [{"addr": "a:1", "nproc": 2, "pid": 1}]

    def test_second_init_is_idempotent(self, clean_env, capture_init):
        clean_env.setenv("COORDINATOR_ADDRESS", "coord:1")
        clean_env.setenv("PADDLE_TRAINERS_NUM", "2")
        penv.init_parallel_env()
        penv.init_parallel_env()
        assert len(capture_init) == 1

    def test_endpoints_env_surfaced(self, clean_env):
        clean_env.setenv("PADDLE_TRAINER_ENDPOINTS", "h1:1,h2:2")
        env = penv.ParallelEnv()
        assert env.trainer_endpoints == ["h1:1", "h2:2"]
        assert env.current_endpoint == "h1:1"


class TestLaunch:
    def test_launch_runs_script_with_env(self, clean_env, capture_init, tmp_path):
        script = os.path.join(tmp_path, "train.py")
        marker = os.path.join(tmp_path, "ran.txt")
        with open(script, "w") as f:
            f.write(
                "import sys, os\n"
                f"open({marker!r}, 'w').write(' '.join(sys.argv[1:]))\n")
        clean_env.setenv("COORDINATOR_ADDRESS", "c:9")
        clean_env.setenv("PADDLE_TRAINERS_NUM", "2")
        clean_env.setenv("PADDLE_TRAINER_ID", "0")
        old_argv = list(sys.argv)
        try:
            rc = launch([script, "--lr", "0.1"])
        finally:
            sys.argv = old_argv
        assert rc == 0
        with open(marker) as f:
            assert f.read() == "--lr 0.1"
        assert capture_init == [{"addr": "c:9", "nproc": 2, "pid": 0}]

    def test_launch_no_script_usage(self, clean_env):
        assert launch([]) == 1

    def test_spawn_single_runs_func(self, capture_init):
        out = []
        spawn(lambda a: out.append(a), args=(7,))
        assert out == [7]

    def test_spawn_multi_on_one_host_errors(self, clean_env):
        with pytest.raises(Exception, match="multi-host"):
            spawn(lambda: None, nprocs=4)


class TestWatchdog:
    """Elastic-lite (reference: launch_utils.py trainer watch loop)."""

    def test_restart_then_success(self, clean_env, tmp_path):
        from paddle_tpu.distributed.parallel import watch
        from paddle_tpu.framework import monitor

        marker = os.path.join(tmp_path, "crashed-once")
        script = os.path.join(tmp_path, "flaky.py")
        with open(script, "w") as f:
            f.write(
                "import os, sys\n"
                f"m = {marker!r}\n"
                "if not os.path.exists(m):\n"
                "    open(m, 'w').close()\n"
                "    sys.exit(3)\n"  # first run: simulated preemption
                "sys.exit(0)\n")
        monitor.reset_stat("trainer_restarts")
        rc = watch([sys.executable, script], max_restarts=2, _sleep=0.01)
        assert rc == 0
        assert monitor.get_stat("trainer_restarts") == 1

    def test_budget_exhausted_propagates_rc(self, clean_env, tmp_path):
        from paddle_tpu.distributed.parallel import watch

        script = os.path.join(tmp_path, "dead.py")
        with open(script, "w") as f:
            f.write("import sys; sys.exit(7)\n")
        rc = watch([sys.executable, script], max_restarts=1, _sleep=0.01)
        assert rc == 7

    def test_launch_flag_parses(self, clean_env, capture_init, tmp_path):
        from paddle_tpu.distributed.parallel import launch

        script = os.path.join(tmp_path, "ok.py")
        with open(script, "w") as f:
            f.write("print('fine')\n")
        old_argv = list(sys.argv)
        try:
            assert launch(["--max-restarts=0", script]) == 0
            assert launch(["--bogus", script]) == 2
        finally:
            sys.argv = old_argv

    def test_watchdog_resume_end_to_end(self, clean_env, tmp_path):
        """Preempted trainer + auto-checkpoint: the restarted run resumes
        from the snapshot and finishes all epochs exactly once."""
        from paddle_tpu.distributed.parallel import watch

        log = os.path.join(tmp_path, "epochs.log")
        script = os.path.join(tmp_path, "train.py")
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(script, "w") as f:
            f.write(f'''
import os, sys
sys.path.insert(0, {repo_root!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn, optimizer as popt
from paddle_tpu.incubate.checkpoint import train_epoch_range

paddle.seed(0)
net = nn.Sequential(nn.Linear(4, 2))
m = paddle.Model(net, inputs=["x"], labels=["y"])
m.prepare(optimizer=popt.SGD(learning_rate=0.1), loss=nn.CrossEntropyLoss())
x = np.zeros((4, 4), np.float32); y = np.zeros((4,), np.int32)
for epoch, acp in train_epoch_range(4, m, {os.path.join(tmp_path, "ck")!r}):
    m.train_batch([x], [y])
    with open({log!r}, "a") as fh:
        fh.write(f"{{epoch}}\\n")
    if epoch == 1 and os.environ.get("CRASH_ONCE") and not os.path.exists(
            {os.path.join(tmp_path, "crashed")!r}):
        # checkpoint writes are async: wait for epoch 0's commit (its meta
        # file) so the kill lands AFTER that commit, BEFORE epoch 1's —
        # the scenario under test, made deterministic
        import glob, time
        deadline = time.time() + 30
        while (not glob.glob({os.path.join(tmp_path, "ck")!r}
                             + "/ckpt-*/meta.pdmeta")
               and time.time() < deadline):
            time.sleep(0.01)
        open({os.path.join(tmp_path, "crashed")!r}, "w").close()
        os._exit(9)  # hard kill AFTER epoch-1 work, BEFORE its commit
''')
        env_backup = os.environ.get("CRASH_ONCE")
        os.environ["CRASH_ONCE"] = "1"
        try:
            rc = watch([sys.executable, script], max_restarts=1, _sleep=0.01)
        finally:
            if env_backup is None:
                os.environ.pop("CRASH_ONCE", None)
        assert rc == 0
        with open(log) as fh:
            epochs = [int(l) for l in fh.read().split()]
        # first run: 0,1 (epoch 1 uncommitted); resumed run: 1,2,3
        assert epochs == [0, 1, 1, 2, 3]

    def test_bad_flag_values_usage_not_traceback(self, clean_env):
        from paddle_tpu.distributed.parallel import launch

        assert launch(["--max-restarts"]) == 2        # missing value
        assert launch(["--max-restarts=abc", "s.py"]) == 2
        assert launch(["--max-restartsfoo=3", "s.py"]) == 2

    def test_no_restart_counts_zero(self, clean_env, tmp_path):
        from paddle_tpu.distributed.parallel import watch
        from paddle_tpu.framework import monitor

        script = os.path.join(tmp_path, "fail.py")
        with open(script, "w") as f:
            f.write("import sys; sys.exit(5)\n")
        monitor.reset_stat("trainer_restarts")
        assert watch([sys.executable, script], max_restarts=0,
                     _sleep=0.01) == 5
        assert monitor.get_stat("trainer_restarts") == 0


class TestValidateEnv:
    """Typed launch-env validation: every inconsistency raises
    InvalidArgumentError NAMING the offending variable, before it can
    surface as an opaque coordination-service failure."""

    @pytest.fixture(autouse=True)
    def _scrub(self, clean_env):
        for k in ("PADDLE_TPU_GANG_TRANSPORT", "PADDLE_TPU_GANG_DIR"):
            clean_env.delenv(k, raising=False)
        self.env = clean_env

    def _raises(self, match):
        from paddle_tpu.framework.errors import InvalidArgumentError
        return pytest.raises(InvalidArgumentError, match=match)

    def test_single_process_defaults(self):
        assert penv.validate_env() == (None, 1, 0)

    def test_non_integer_trainers_num_named(self):
        self.env.setenv("PADDLE_TRAINERS_NUM", "two")
        with self._raises("PADDLE_TRAINERS_NUM='two' is not an integer"):
            penv.validate_env()

    def test_non_integer_trainer_id_named(self):
        self.env.setenv("PADDLE_TRAINER_ID", "1.5")
        with self._raises("PADDLE_TRAINER_ID='1.5' is not an integer"):
            penv.validate_env()

    def test_zero_trainers_num_rejected(self):
        self.env.setenv("PADDLE_TRAINERS_NUM", "0")
        with self._raises("PADDLE_TRAINERS_NUM"):
            penv.validate_env()

    def test_rank_out_of_range(self):
        self.env.setenv("PADDLE_TRAINERS_NUM", "2")
        self.env.setenv("PADDLE_TRAINER_ID", "2")
        self.env.setenv("COORDINATOR_ADDRESS", "h:1234")
        with self._raises(r"PADDLE_TRAINER_ID=2 out of range \[0, 2\)"):
            penv.validate_env()

    def test_endpoint_count_mismatch_without_coordinator(self):
        self.env.setenv("PADDLE_TRAINERS_NUM", "3")
        self.env.setenv("PADDLE_TRAINER_ENDPOINTS", "a:1,b:2")
        with self._raises("every rank needs exactly one endpoint"):
            penv.validate_env()

    def test_endpoint_count_informational_with_coordinator(self):
        # with an explicit rendezvous address the endpoint list is
        # informational — a short list must NOT fail the launch
        self.env.setenv("PADDLE_TRAINERS_NUM", "3")
        self.env.setenv("PADDLE_TRAINER_ENDPOINTS", "a:1,b:2")
        self.env.setenv("COORDINATOR_ADDRESS", "a:1")
        addr, world, pid = penv.validate_env()
        assert (addr, world, pid) == ("a:1", 3, 0)

    def test_duplicate_endpoints_rejected(self):
        self.env.setenv("PADDLE_TRAINER_ENDPOINTS", "a:1,b:2,a:1")
        with self._raises("duplicate endpoint"):
            penv.validate_env()

    def test_malformed_address_names_source_var(self):
        self.env.setenv("COORDINATOR_ADDRESS", "no-port")
        with self._raises("COORDINATOR_ADDRESS='no-port' is not host:port"):
            penv.validate_env()
        self.env.delenv("COORDINATOR_ADDRESS")
        self.env.setenv("PADDLE_TRAINER_ENDPOINTS", "host:notaport")
        with self._raises("PADDLE_TRAINER_ENDPOINTS.*not host:port"):
            penv.validate_env()

    def test_bad_gang_transport_rejected(self):
        self.env.setenv("PADDLE_TPU_GANG_TRANSPORT", "tcp")
        with self._raises("PADDLE_TPU_GANG_TRANSPORT.*auto\\|jax\\|file"):
            penv.validate_env()

    def test_multi_host_needs_rendezvous(self):
        self.env.setenv("PADDLE_TRAINERS_NUM", "4")
        with self._raises("needs a rendezvous point"):
            penv.validate_env()

    def test_file_transport_needs_gang_dir(self):
        self.env.setenv("PADDLE_TRAINERS_NUM", "2")
        self.env.setenv("PADDLE_TPU_GANG_TRANSPORT", "file")
        with self._raises("PADDLE_TPU_GANG_DIR"):
            penv.validate_env()

    def test_file_transport_with_gang_dir_ok(self, tmp_path):
        self.env.setenv("PADDLE_TRAINERS_NUM", "2")
        self.env.setenv("PADDLE_TRAINER_ID", "1")
        self.env.setenv("PADDLE_TPU_GANG_TRANSPORT", "file")
        self.env.setenv("PADDLE_TPU_GANG_DIR", str(tmp_path))
        addr, world, pid = penv.validate_env()
        assert (world, pid) == (2, 1)
