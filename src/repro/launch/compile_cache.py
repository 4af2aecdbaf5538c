"""Where JAX's persistent compilation cache lives.

The place comes from outside the program: ``JAX_COMPILATION_CACHE_DIR``
where it is set (JAX reads it itself, and nothing here overrides it),
else a fixed directory inside the checkout, ``<checkout>/.jax_cache``
(git ignores it).  A fixed path matters: it is part of the cache key, so
a directory that moved between runs would never hit.

Entry points call :func:`enable_compile_cache` once, before they compile
anything; importing a module never does.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's cache directory (``src/repro/launch`` -> checkout root)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    The default directory is also exported as ``JAX_COMPILATION_CACHE_DIR``
    so spawned worker processes share it.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(DEFAULT_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    return path
