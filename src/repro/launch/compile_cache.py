"""Where JAX keeps its persistent compilation cache.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile, so a second run of
the same program on the same chip reads its executables back instead of
compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and
    nothing is set here.  Otherwise the cache goes to the fixed path
    ``<checkout>/.jax_cache``: the directory is part of what makes a
    later run find an entry, so it is never built from a temporary name,
    a process id or the time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
