"""Where JAX keeps its persistent compilation cache.

The entry points (``launch.train``, ``launch.serve``, ``chip_smoke.py``)
call :func:`enable_compile_cache` before they compile anything; importing
this module changes nothing. A cache is found again only under the same
path, so the path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when it is set
(JAX reads that variable itself), otherwise ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import os
import pathlib

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
