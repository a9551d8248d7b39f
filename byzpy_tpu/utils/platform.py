"""Where compiled programs are kept between runs.

Plain JAX honours ``JAX_PLATFORMS`` by itself, so platform selection
needs no helper. What every entry point that compiles shares is the
persistent compilation cache: the directory is part of the cache key, so
entry points that each pick their own never hit each other's entries.
:func:`enable_compile_cache` is the one place the directory is decided.
"""

from __future__ import annotations

import os

_ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
#: The one in-checkout cache location (git-ignored), used when the
#: environment names none. Fixed: never a temporary name, a pid or a time.
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """The persistent compile cache directory this checkout uses:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    (the same absolute path from any working directory). Parents pass it
    to JAX children through the environment so they share one cache."""
    return os.environ.get(_ENV_CACHE_DIR) or _REPO_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`compile_cache_dir`. With ``JAX_COMPILATION_CACHE_DIR`` set
    this touches nothing — JAX reads the variable into
    ``jax_compilation_cache_dir`` by itself, and no other directory is
    ever set in code. Call before the first compile. Returns the
    directory in use."""
    if not os.environ.get(_ENV_CACHE_DIR):
        import jax

        jax.config.update("jax_compilation_cache_dir", _REPO_CACHE_DIR)
        # small programs dominate the CPU test mesh's compile bill
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return compile_cache_dir()


def child_env(platform: str) -> dict:
    """The JAX environment a parent hands a spawned child: an EXPLICIT
    platform (a chip belongs to one process — the child never gets the
    parent's by inheritance; host-side children pass ``"cpu"``) and the
    parent's compile cache."""
    return {"JAX_PLATFORMS": platform, _ENV_CACHE_DIR: compile_cache_dir()}


__all__ = ["child_env", "compile_cache_dir", "enable_compile_cache"]
