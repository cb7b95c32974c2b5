"""Placement of JAX's persistent compilation cache for the entry
scripts of this checkout (``chip_smoke.py``, ``chipbench/run.py``,
``benchmarks/*.py``)."""

import os

_REPO_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_DIR, ".jax_cache")


def place_compile_cache():
    """Call before first backend use; returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own reading of
    it stands and nothing is set here.  Elsewhere the cache is the
    fixed, git-ignored ``<repo>/.jax_cache``: the directory is part of
    the cache's key, so it never carries a temp name, a pid or a time.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
