"""Where JAX's persistent compilation cache lives for the entry points.

The entry points (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``) call :func:`use_compile_cache` from their ``main``;
no module calls it on import.  A cache directory placed from outside
(``JAX_COMPILATION_CACHE_DIR``) is JAX's to use and is left alone.
Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed path
(the path is part of the cache key, so a directory that moved would
never hit), listed in ``.gitignore``.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
