"""Persistent JAX compilation cache for the command-line entry points.

:func:`enable` is called by ``serve_cnn.main``, ``serve_lm.main`` and
``chip_smoke.py`` before they compile anything — never at import and
never from tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
that directory itself and nothing else is set here.  Otherwise the cache
lives at the fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``):
a fixed path, because a cache directory that moves never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["ENV_VAR", "DEFAULT_DIR", "enable"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
