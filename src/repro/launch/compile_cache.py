"""Where compiled programs persist between processes.

The entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) call ``enable_compile_cache``
once, before their first compile; library code and tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory (listed in ``.gitignore``).  The
#: path is part of each cache key, so it is fixed: a run in the same
#: checkout finds the entries an earlier run wrote.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to
    ``CHECKOUT_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
