"""JAX's persistent compilation cache for the runnable entry points.

Called by ``chip_smoke.py``, ``benchmarks/serve_bench.py`` and
``examples/serve_decode.py`` before they compile anything; importing a
library module never touches the cache.  The cache directory is part of
each entry's key, so it is a fixed path: ``JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads it itself), otherwise ``.jax_cache`` at the
checkout root.
"""

from __future__ import annotations

import os

import jax

CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str) -> str:
    """Turn the cache on and return its directory (see module docstring);
    ``root`` is the checkout the entry point runs from."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
