"""Where compiled XLA programs are cached between runs.

``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, and nothing
here overrides it.  Without it the cache lives in one fixed directory
of the checkout, ``<repo>/.jax_cache`` (git-ignored), so a second run
from the same checkout skips recompiling every kernel and step.
"""
from __future__ import annotations

import os

import jax

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Places the persistent compile cache; returns its directory."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
