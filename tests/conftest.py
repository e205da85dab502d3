"""Test config: run the suite on a simulated 8-device CPU mesh.

Mirrors the reference's strategy of testing distributed paths without a real
cluster (reference: python/paddle/fluid/tests/unittests/test_dist_base.py
spawns localhost subprocesses; test_collective_base.py fakes 2 ranks on one
GPU).  The TPU-native equivalent is XLA's host-platform device partitioning:
8 virtual CPU devices let every pjit/shard_map path compile and execute.
"""
import os

# Must be set before jax import (8 virtual host devices for the mesh tests).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# Numeric tests compare against the numpy oracle: force exact f32 matmuls.
# The framework default (XLA "default" precision ≈ bf16 passes on TPU) is the
# perf-correct choice in production — it matches the reference's cuBLAS TF32
# default on A100.
jax.config.update("jax_default_matmul_precision", "highest")


# -- fast / slow lanes -------------------------------------------------------
# `pytest -m fast` is the <5-minute inner-loop lane; the full (~20 min,
# 1-core) suite stays the merge gate.  Files land in SLOW_FILES by measured
# wall time (per-file totals from --durations, 2026-07-31); everything else
# is auto-marked fast.  A file-level split keeps the list maintainable —
# re-run `pytest --durations=120` and update when a file's cost changes class.
SLOW_FILES = {
    "test_vision.py", "test_models.py", "test_attention.py",
    "test_sequence_parallel_model.py", "test_detection_targets.py",
    "test_detection.py", "test_io.py", "test_launch_env.py",
    "test_roi_extra.py", "test_pipeline.py", "test_strategies.py",
    "test_extension_ops.py", "test_distributed.py", "test_heartbeat.py",
    "test_nn_functional.py", "test_nn_layers.py", "test_fluid_compat.py",
    "test_crf.py", "test_slim.py", "test_sparse_embedding.py",
    "test_multiprocess_dp.py", "test_multiprocess_hybrid.py",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "fast: quick lane (pytest -m fast, <5 min total)")
    config.addinivalue_line(
        "markers", "slow: heavy tests excluded from the fast lane")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (item.get_closest_marker("fast")
                or item.get_closest_marker("slow")):
            continue  # an explicit per-test lane beats the file default
        fname = os.path.basename(item.nodeid.split("::")[0])
        item.add_marker(
            pytest.mark.slow if fname in SLOW_FILES else pytest.mark.fast)


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture
def use_mesh():
    """``use_mesh(devices)`` installs a global mesh over just those devices
    (the suite's default spans all 8); the previous one returns after the
    test.  One chip is ``use_mesh(jax.devices()[:1])``."""
    from paddle_tpu.distributed import mesh as pmesh

    prev = pmesh._global_mesh
    yield lambda devices: pmesh.set_mesh(
        pmesh.build_mesh(devices=list(devices)))
    pmesh._global_mesh = prev
