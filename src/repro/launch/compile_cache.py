"""JAX's persistent compilation cache for the launchers and the chip smoke.

One rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing here overrides it; otherwise, when running from a source
checkout, the cache lives at ``<checkout>/.jax_cache``.  The path is part
of a cache entry's key, so it is fixed: never a temporary, per-process or
per-run directory.  An installed package (no checkout around it) leaves
the cache off rather than write into the Python installation.
"""

from __future__ import annotations

import os
from typing import Optional

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns its directory,
    or None when there is neither the variable nor a checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if not os.path.exists(os.path.join(CHECKOUT, "pyproject.toml")):
        return None
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
