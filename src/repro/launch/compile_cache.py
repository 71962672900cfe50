"""JAX's persistent compilation cache at a fixed place.

Called by the entry points, never on import: ``repro`` itself stays
jax-free.  The cache's path is part of its key, so it must not move
between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the default cache directory, ``<checkout>/.jax_cache``
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on the persistent cache on a TPU, before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; otherwise the cache is ``DEFAULT_DIR``.  Other
    backends get no cache: a CPU executable read back from it can abort
    the process (jax 0.9), and CPU runs are tests and demos.
    """
    if (not os.environ.get("JAX_COMPILATION_CACHE_DIR")
            and jax.default_backend() == "tpu"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
