"""Where XLA's persistent compilation cache lives.

A first TPU compile of a training step takes tens of seconds, and every
fresh process pays it again unless JAX's persistent cache is on. The
cache key includes the directory's path, so the directory must not move
between runs: it is either where the operator put it
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads by itself) or one fixed
place next to the package, ``<checkout>/.jax_cache`` (git-ignored).
Nothing else in the tree sets a cache directory.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> Optional[str]:
    """Point JAX's persistent compile cache at ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already places it. Returns the
    directory set here, or None when JAX was left alone. Called once at
    ``import paddle_tpu``; touches only ``jax.config``, never a
    backend."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
