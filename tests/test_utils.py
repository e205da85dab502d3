"""paddle_tpu.utils — run_check, deprecated, try_import.

Reference capability: python/paddle/utils/ (install_check.py:134,
deprecated.py:31, lazy_import.py:19).
"""
import warnings

import pytest

import paddle_tpu as paddle


class TestUtils:
    def test_run_check_passes_and_restores_state(self, capsys):
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.mesh import _global_mesh  # noqa: F401
        from paddle_tpu.framework import random as prandom

        paddle.seed(1234)
        key_before = prandom.get_rng_state()
        strategy_before = fleet._strategy
        paddle.utils.run_check()
        out = capsys.readouterr().out
        assert "installed successfully" in out
        assert "8" in out  # the 8-device CPU mesh exercises the DP leg
        # the sanity check must not perturb the session
        import numpy as np

        assert fleet._strategy is strategy_before
        np.testing.assert_array_equal(
            np.asarray(prandom.get_rng_state()),
            np.asarray(key_before))

    def test_deprecated_warns_and_documents(self):
        @paddle.utils.deprecated(since="0.1", update_to="paddle.new_api",
                                 reason="renamed")
        def old_api(x):
            """Old docstring."""
            return x + 1

        assert "deprecated since 0.1" in old_api.__doc__
        assert "paddle.new_api" in old_api.__doc__
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert old_api(1) == 2
        assert any(issubclass(x.category, DeprecationWarning) for x in w)

    def test_try_import(self):
        mod = paddle.utils.try_import("math")
        assert mod.sqrt(4) == 2
        with pytest.raises(ImportError, match="pip install"):
            paddle.utils.try_import("definitely_not_a_module_xyz")


class TestCompatSysconfig:
    """paddle.compat (compat.py:36,120,193) + paddle.sysconfig."""

    def test_to_text_to_bytes(self):
        assert paddle.compat.to_text(b"abc") == "abc"
        assert paddle.compat.to_bytes("abc") == b"abc"
        assert paddle.compat.to_text([b"a", b"b"]) == ["a", "b"]
        assert paddle.compat.to_bytes({"a"}) == {b"a"}
        # dicts convert keys AND values (reference compat.py:74)
        assert paddle.compat.to_text({b"k": b"v"}) == {"k": "v"}
        lst = [b"x"]
        out = paddle.compat.to_text(lst, inplace=True)
        assert out is lst and lst == ["x"]

    def test_round_half_away_from_zero(self):
        assert paddle.compat.round(0.5) == 1.0
        assert paddle.compat.round(-0.5) == -1.0
        assert paddle.compat.round(2.675, 2) == 2.68
        assert paddle.compat.round(0.0) == 0.0

    def test_misc(self):
        assert paddle.compat.floor_division(7, 2) == 3
        assert paddle.compat.get_exception_message(ValueError("x")) == "x"

    def test_sysconfig_paths(self):
        import os

        inc = paddle.sysconfig.get_include()
        assert os.path.isdir(inc)
        assert any(f.endswith(".cc") for f in os.listdir(inc))
        lib = paddle.sysconfig.get_lib()
        assert os.path.isdir(lib)  # must exist even before any native build
        # everything the program generates lives under the one fixed root
        assert lib == os.path.join(paddle.sysconfig.cache_root(), "native")


class TestCompilationCachePlacement:
    """Where the persistent XLA cache lives (sysconfig): the deployment's
    ``JAX_COMPILATION_CACHE_DIR`` when set — and then NO directory is set
    from code — else one fixed path inside the checkout."""

    @pytest.fixture
    def restore_cache_config(self):
        import jax

        from paddle_tpu import sysconfig

        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")
        prev = {k: getattr(jax.config, k) for k in keys}
        prev_enabled = sysconfig._pcc_enabled
        yield
        for k, v in prev.items():
            jax.config.update(k, v)
        sysconfig._pcc_enabled = prev_enabled
        from jax.experimental.compilation_cache import (
            compilation_cache as cc)

        cc.reset_cache()  # do not leave the suite writing a disk cache

    def test_env_var_set_means_no_directory_from_code(
            self, monkeypatch, tmp_path, restore_cache_config):
        import jax

        from paddle_tpu import sysconfig

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert sysconfig.enable_persistent_compilation_cache() == str(
            tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_unset_is_the_fixed_in_checkout_path(
            self, monkeypatch, restore_cache_config):
        import os
        import subprocess
        import sys

        import jax

        from paddle_tpu import sysconfig

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".cache", "xla")
        assert sysconfig.enable_persistent_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # ... and identical in another process (the directory is part of
        # JAX's cache key: a path that moved would never hit)
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-c",
             "import sys; sys.path.insert(0, sys.argv[1]); "
             "from paddle_tpu import sysconfig; "
             "print(sysconfig.enable_persistent_compilation_cache())",
             repo],
            env=env, capture_output=True, text=True, timeout=120,
            cwd=os.path.dirname(repo))
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == want

    def test_cache_dirs_are_git_ignored(self):
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, ".gitignore")) as f:
            ignored = {line.strip() for line in f}
        assert ".cache/" in ignored
