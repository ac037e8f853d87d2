"""One persistent XLA compile cache, shared by every process that compiles.

A cold TPU compile of a training step or a decode engine costs tens of
seconds; the driver, every serve replica, every ``DecodeEngine`` and every
SPMD rank call :func:`enable_compile_cache` before their first compile so
that cost is paid once per program, not once per process start.

Where ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads it at import), or the
program configured a directory itself, nothing is changed here: the cache is
placed from outside. Otherwise it lives at ``<checkout>/.jax_cache`` — a
fixed path beside the package, never ``~``, a tempdir, a pid or a timestamp,
because the directory is part of the cache key and one that moves never
hits.
"""

from __future__ import annotations

import os


def default_cache_dir() -> str:
    package = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def enable_compile_cache() -> str:
    """Make sure this process compiles against the persistent cache; returns
    the directory in use. Idempotent. Every compiling process passes here
    before its first compile, so this is also where the compile account's
    listeners are registered (``obs.profiler``: once a process)."""
    import jax

    from raydp_tpu.obs import profiler

    profiler.install_compile_listeners()
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    cache_dir = default_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
