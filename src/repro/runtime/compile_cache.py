"""JAX's persistent compilation cache for the repo's entry scripts.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable` before
their first compile; importing ``repro`` sets nothing, so library users
and the CPU test suite keep JAX's own defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module leaves it alone.  Otherwise the cache goes to ``<root>/.jax_cache``
(listed in ``.gitignore``): a fixed path, because the directory is part
of what a later process looks up, so a path that moved between runs
would never be hit.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DIRNAME = ".jax_cache"


def enable(root: str) -> str:
    """Turn on the persistent cache; return the directory in use.

    ``root`` is the checkout the calling script runs from."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = os.path.join(os.path.abspath(root), DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
