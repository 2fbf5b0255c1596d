"""Where JAX keeps its persistent compilation cache for this checkout.

Every entry point (``chip_smoke.py``, ``launch.serve``, ``launch.train``,
``benchmarks.run``) calls :func:`enable_compile_cache` first; nothing
calls it at import.  When the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX reads that itself and nothing is set
here.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path, because a cache whose directory moves between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[2]   # <checkout>/src/repro/..


def cache_dir(environ: Mapping[str, str] = os.environ) -> Optional[str]:
    """The cache directory to set, or None when the environment names
    one already (JAX then uses it as given)."""
    if environ.get(ENV_VAR):
        return None
    return str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at :func:`cache_dir`;
    returns the directory set, or None when left to the environment."""
    path = cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["ENV_VAR", "CHECKOUT", "cache_dir", "enable_compile_cache"]
