"""Where the persistent XLA compile cache lives.

(The reference has no compile step to cache; PyTorch eager, ref
train.py:86-162.)

One rule for every entry point (main.py, bench.py, scaling.py, scripts/*,
chip_smoke.py, the test suite): if `JAX_COMPILATION_CACHE_DIR` is set the
environment has placed the cache — JAX reads that variable itself and the
code sets nothing; otherwise the cache is `<checkout>/build/jax_cache`
(git-ignored). The path is part of every cache key, so it is never a temp
dir, a pid or a timestamp: a directory that moves never hits.

The cache is machine-specific (XLA:CPU entries bake in host CPU
features); it is never committed.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, "build", "jax_cache")


def use_compile_cache() -> str:
    """Enable the persistent compile cache for this process (call before
    the first compile) and return its directory."""
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
