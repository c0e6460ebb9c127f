"""Where JAX keeps its persistent compilation cache for this checkout.

Called by the entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/*.py``), never on import: a library that picks a cache directory
for whoever imports it would override the caller's choice.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_compilation_cache (listed in .gitignore); a fixed path,
# because the directory is part of the cache's key
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_compilation_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
