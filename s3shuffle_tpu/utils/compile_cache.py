"""Persistent XLA compile cache for the processes that drive the chip.

A cold process compiles every codec kernel again: the TLZ encode graph alone
takes ~19 s to compile for a v5e. JAX keys its persistent cache by path, so
the directory must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads that itself and nothing is set here),
otherwise ``<repo>/.jax_cache``, a fixed path inside the checkout.

Two callers, two strengths: the repo's entry points (``chip_smoke.py``,
``bench.py``) own their process and arm the cache outright
(:func:`enable_compile_cache`); the library, at its first device verdict,
only fills in a directory nobody chose (:func:`default_compile_cache`).
"""

from __future__ import annotations

import os
from typing import Optional

#: the checkout root: two levels above this package's ``utils`` dir
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
REPO_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """For entry points that own their process: point JAX's persistent
    compile cache at its fixed directory and keep even the small codec
    kernels (sub-second compiles) in it. Call before the process's first
    compile; a later call re-arms the cache for the compiles that follow.
    Returns the directory in use."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = os.environ.get(_ENV) or REPO_CACHE_DIR
    if not os.environ.get(_ENV) and jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # JAX decides once, at the first compile, whether the cache is used
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def default_compile_cache() -> Optional[str]:
    """For the library, inside a user's process: a directory the process
    already chose (``JAX_COMPILATION_CACHE_DIR`` or ``jax.config``) is left
    as it is, as are the cache's other settings. With none chosen, a
    checkout keeps its cache in ``<repo>/.jax_cache``; an installed package
    sets nothing. Never resets the cache, so a process that compiled before
    this call keeps its own decision. Returns the directory, or None."""
    import jax

    current = jax.config.jax_compilation_cache_dir
    if current:
        return current
    if not os.path.isfile(os.path.join(REPO_ROOT, "pyproject.toml")):
        return None
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
