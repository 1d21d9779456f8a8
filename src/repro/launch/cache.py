"""Where entry points keep JAX's persistent compilation cache.

A chip run compiles every program from scratch unless an earlier run left
its executables in a cache the new run can find; the cache is keyed by its
path, so the path must not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> str | None:
    """The directory this program should set, or None to leave JAX's own.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and nothing
    is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``, a
    fixed path (git-ignored) that never comes from a temporary name, a
    process id or the time.
    """
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> None:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`."""
    path = compile_cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
