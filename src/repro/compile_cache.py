"""JAX's persistent compilation cache, placed for the program's entry points.

``enable_compile_cache()`` is called by the entry points only
(``chip_smoke.py``, ``python -m repro.fleet``, ``python -m benchmarks.run``),
never as a side effect of importing ``repro``: library users and the test
suite keep JAX's own defaults.
"""

from __future__ import annotations

import os

import jax

# A fixed path inside the checkout: a path built from a temporary name, a
# pid or the time would never be found again by the next process.
REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and it
    is the only directory in use; otherwise the cache lives at
    ``<repo>/.jax_cache``. Call before the first compilation.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the planning kernels compile in well under a second: keep those too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
