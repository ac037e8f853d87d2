"""The persistent compile cache is placed from outside (raydp_tpu/compile_cache.py).

``JAX_COMPILATION_CACHE_DIR`` set: the program leaves the directory alone,
sets no other in code, and every artifact lands there. Unset: the cache is
``<checkout>/.jax_cache`` — a fixed path, because the path is part of the key.
Each case runs in a fresh interpreter: ``jax.config`` reads the variable once,
at import.
"""

import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROGRAM = """
import os, sys
import jax
from raydp_tpu.compile_cache import default_cache_dir, enable_compile_cache

before = jax.config.jax_compilation_cache_dir
used = enable_compile_cache()
assert enable_compile_cache() == used  # idempotent
# cache every program, however quick its compile, so the listing is a proof
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(7.0)))
print("BEFORE", before)
print("USED", used)
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("DEFAULT", default_cache_dir())
"""


def _run(tmp_path, cache_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(cache_env, HOME=str(tmp_path / "home"), JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO_ROOT)
    os.makedirs(env["HOME"])
    done = subprocess.run(
        [sys.executable, "-c", _PROGRAM], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stdout + done.stderr
    return dict(
        line.split(" ", 1) for line in done.stdout.splitlines()
        if line.split(" ", 1)[0] in ("BEFORE", "USED", "CONFIG", "DEFAULT")
    )


def _files(root):
    return sorted(
        os.path.join(d, f) for d, _, names in os.walk(root) for f in names
    )


def test_env_dir_is_left_alone_and_takes_every_artifact(tmp_path):
    outside = str(tmp_path / "outside")
    checkout_cache = os.path.join(REPO_ROOT, ".jax_cache")
    before = _files(checkout_cache)
    out = _run(tmp_path, {"JAX_COMPILATION_CACHE_DIR": outside})
    # config untouched: what jax read from the environment is what is in use
    assert out["BEFORE"] == out["USED"] == out["CONFIG"] == outside
    assert _files(outside), "no compile artifact under the configured dir"
    # and nothing anywhere else: not ~/.cache, not the checkout
    assert not os.path.exists(tmp_path / "home" / ".cache")
    assert _files(checkout_cache) == before


def test_unset_means_checkout_jax_cache(tmp_path):
    out = _run(tmp_path, {})
    expected = os.path.join(REPO_ROOT, ".jax_cache")
    assert out["BEFORE"] == "None"
    assert out["USED"] == out["CONFIG"] == out["DEFAULT"] == expected
    assert _files(expected)
    assert not os.path.exists(tmp_path / "home" / ".cache")
