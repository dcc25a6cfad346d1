"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``python -m repro.launch.discovery``,
``python -m benchmarks.run``) call ``enable_compile_cache`` once at start-up;
nothing calls it at import, and tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout: the cache is keyed on it, so a path made
# from a temporary name, a pid or the time would never be hit again
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
    directory is left alone; otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout.  Either way every compile is kept, however
    short: the Pallas filter kernels compile in about a second, under JAX's
    default threshold.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
