"""JAX's persistent compilation cache for the repository's entry points.

Each entry point (``chip_smoke.py``, ``examples/*.py``, ``python -m
repro.launch.serve``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` first thing in its ``main``; importing this
module changes nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and this sets no other directory.  Otherwise the cache goes to
``.jax_cache`` at the root of the checkout: a fixed path, because the
path is part of what a later run must match to find an entry.
"""

from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    ),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
