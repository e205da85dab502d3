"""paddle.sysconfig — include/lib paths for building native extensions,
and the one place that says where the program keeps what it generates.

Parity: python/paddle/sysconfig.py:20,37.  The reference points at its
bundled C++ headers and libpaddle; here native components are plain-C
ABI over ctypes (paddle_tpu.native), so the include dir is the package's
native source tree and the lib dir is the build cache where the shared
objects land after their first-use compile.
"""
from __future__ import annotations

import os

__all__ = ["get_include", "get_lib", "cache_root",
           "enable_persistent_compilation_cache",
           "maybe_enable_persistent_compilation_cache",
           "kernel_tuning_cache_path"]


def cache_root() -> str:
    """``<checkout>/.cache`` — the fixed, git-ignored directory beside the
    package that holds everything the program generates for itself: the
    XLA compilation cache (``xla/``), the kernel-tuning winners
    (``kernel_tuning.json``) and the native build outputs (``native/``).
    Fixed on purpose: the directory is part of JAX's cache key, so a path
    that moves between runs (a temp dir, a pid, a timestamp, another
    user's home) never hits, and a machine that only receives the
    checkout still finds and fills it."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".cache")


def get_include() -> str:
    """Directory holding the native C/C++ sources and headers."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "native")


def get_lib() -> str:
    """Directory holding the compiled native shared objects (created here
    if no native component has built yet — a -L flag must point at an
    existing directory)."""
    from .native import _CACHE_DIR

    os.makedirs(_CACHE_DIR, exist_ok=True)
    return _CACHE_DIR


# -- persistent XLA compilation cache ----------------------------------------
_pcc_enabled = False


def enable_persistent_compilation_cache() -> str:
    """Turn JAX's persistent compilation cache on so compiled XLA
    executables survive process restarts (the in-process Executor LRU
    only helps within one run).  Returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the deployment has placed
    the cache: JAX reads the variable itself and this function sets NO
    directory in code.  Otherwise the directory is the fixed
    ``<checkout>/.cache/xla`` (:func:`cache_root`).

    Idempotent; safe to call before or after the first compile — only
    computations compiled afterwards are cached.
    """
    global _pcc_enabled
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(cache_root(), "xla")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    # cache even fast compiles / small entries
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _pcc_enabled = True
    return cache_dir


def maybe_enable_persistent_compilation_cache() -> None:
    """Flag-gated hook (FLAGS_persistent_compilation_cache): called from
    ``Executor.__init__`` so setting the flag/env var is all a user needs.
    WHERE the cache lives is not the flag's business — see
    :func:`enable_persistent_compilation_cache`."""
    if _pcc_enabled:
        return
    from .framework.flags import flag

    if flag("persistent_compilation_cache"):
        enable_persistent_compilation_cache()


def kernel_tuning_cache_path() -> str | None:
    """Where the measured searches (sharding plans, serving configs)
    persist their winners (``FLAGS_kernel_tuning_cache``; the XLA
    executable cache above is a separate store).  ``None`` when disk
    persistence is disabled."""
    from .tuning.engine import cache_path

    return cache_path()
