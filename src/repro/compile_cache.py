"""Where JAX keeps its persistent compilation cache.

Entry points that compile on purpose (``chip_smoke.py``, the examples, the
serving launcher, the live bench sections and container-mode children) call
``enable_compile_cache`` before they compile anything. Nothing calls it on
import, so the test suite never fills the directory.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path: a cache directory that moves between runs never hits
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set, and no other
    directory is used. Otherwise the cache is ``.jax_cache`` at the root of
    the checkout (listed in ``.gitignore``)."""
    import jax

    path = os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
