"""JAX's persistent compilation cache, turned on by the entry points.

Each process that reaches a chip otherwise compiles every launch shape from
scratch.  The entry points (``chip_smoke.py``, ``python -m
repro.launch.stream``, ``benchmarks/run.py``) call :func:`enable` once at
start-up; importing this module changes nothing.
"""

from __future__ import annotations

import os
import pathlib

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path inside the checkout (git-ignored).  It must not vary between runs —
#: the path is part of what a later process looks its entries up under.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    used as it is; otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.
    Every compile is persisted however quick it was: the Pallas kernels
    compile in under a second, below JAX's default one-second floor.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
