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


# -- the latent decoders' page walk against their gather path -----------------
#: what a decode step's slots can look like; ``latent_walk_check`` builds each
LATENT_WALK_CASES = ("full_window", "wrapped_ring", "last_page_part_filled",
                     "free_slots", "very_different_lengths")


@pytest.fixture
def latent_walk_check(monkeypatch):
    """``check(mixer, case, tol)``: one decode step of a
    ``LatentAttention`` layer (``models/latent_moe.py``; rotary or not, any
    heads) over 4 slots x 40 pages of 16 (640 positions: two key blocks of
    the ``latent_decode`` kernel, the second cut by the window's end), the
    pool full of random rows, with the kernel steered on (interpret mode)
    against the gather path: ``absorbed`` over the slots' gathered views.
    Live rows agree to ``tol`` of the layer's largest output, the pools
    are bit-equal."""
    import jax.numpy as jnp

    from paddle_tpu.models import latent_moe as lm

    B, G, page = 4, 40, 16
    C, P = G * page, B * G

    def layout(case):
        at = {"full_window": [C - 1] * 4,
              "wrapped_ring": [C + 5, 2 * C + 77, C + 300, 3 * C - 1],
              "last_page_part_filled": [20, 100, 332, 628],
              "free_slots": [C // 2, -1, 37, -1],
              "very_different_lengths": [0, 16, C - 1, 199]}[case]
        rng = np.random.default_rng(LATENT_WALK_CASES.index(case))
        free = list(rng.permutation(P))
        table = np.full((B, G), -1, np.int32)
        pos_map = np.full((B, C), -1, np.int32)
        for b, p in enumerate(at):
            if p < 0:
                continue
            for g in range(min(G, p // page + 1)):
                table[b, g] = free.pop()
            held = np.arange(max(0, p - C + 1), p + 1)  # the last C positions
            pos_map[b, held % C] = held
        return table, pos_map, np.asarray(at, np.int32)[:, None]

    def check(mixer, case, tol):
        cfg = mixer.cfg
        dt = jnp.dtype(cfg.dtype)
        table, pos_map, pos = layout(case)
        rng = np.random.default_rng(7)
        pool = jnp.asarray(rng.normal(size=(P + 1, page, cfg.page_width)), dt)
        x = jnp.asarray(rng.normal(size=(B, 1, cfg.hidden_size)), dt)
        ring = np.where(pos >= 0, pos % C, -1)
        phys = np.take_along_axis(table, np.clip(ring // page, 0, G - 1), 1)
        phys = np.where((ring >= 0) & (phys >= 0), phys, P)  # P: drop page
        paged = (phys.reshape(-1), np.clip(ring % page, 0, page - 1)
                 .reshape(-1), np.maximum(table, 0), pos, pos_map)
        paged = tuple(jnp.asarray(a, jnp.int32) for a in paged)
        out = {}
        for walk in (False, True):
            monkeypatch.setattr(lm, "_walks_pages",
                                lambda pool, T, walk=walk: walk and T == 1)
            y, kv = mixer.forward_paged(x, {"latent": pool}, *paged)
            out[walk] = np.asarray(y, np.float32), np.asarray(kv["latent"])
        live = pos[:, 0] >= 0
        want = out[False][0][live]
        gap = np.abs(out[True][0][live] - want).max() / np.abs(want).max()
        assert gap < tol, gap
        assert np.array_equal(out[True][1], out[False][1])

    return check


@pytest.fixture
def latent_walk_serves_the_same(monkeypatch):
    """``check(model, prompts, new_tokens)``: greedy tokens served by
    ``GenerationEngine`` with the decode step's ``latent_decode`` kernel
    steered on (interpret mode) equal the gather path's, and only the step
    program holds the kernel (an op of a program is named by the code it
    came from)."""
    import re

    from paddle_tpu.models import latent_moe as lm
    from paddle_tpu.serving import GenerationEngine

    def check(model, prompts, new_tokens):
        served = {}
        for walk in (False, True):
            monkeypatch.setattr(lm, "_walks_pages",
                                lambda pool, T, walk=walk: walk and T == 1)
            eng = GenerationEngine(model, batch_size=4,
                                   prompt_buckets=[16, 32], kv_page_size=8,
                                   speculative_k=0, eos_token_id=None,
                                   name="walk")
            try:
                eng.warmup()
                served[walk] = [
                    np.asarray(f.result(timeout=300)).tolist()
                    for f in [eng.submit(p, new_tokens) for p in prompts]]
                texts = eng.compiled_programs()
            finally:
                eng.close()
            walked = {n: "latent_decode" in " ".join(
                re.findall(r'op_name="([^"]*)"', t))
                for n, t in texts.items()}
            assert walked == {n: walk and n == "step" for n in texts}
        assert served[True] == served[False]

    return check
