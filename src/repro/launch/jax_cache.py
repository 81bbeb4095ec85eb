"""Where the launch drivers keep JAX's persistent compilation cache.

A published-width step program takes tens of seconds to compile, and the
cache only hits when its directory stays put, so the location is fixed:
``JAX_COMPILATION_CACHE_DIR`` where the environment sets it (JAX reads the
variable itself and nothing here overrides it), else ``.jax_cache`` at the
root of this checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["DEFAULT_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` — this file lives at src/repro/launch/.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
