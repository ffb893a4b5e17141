"""JAX's persistent compilation cache, placed once for every launcher.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
stands: whoever runs the program decides where compiled programs live.
Otherwise they go to ``<checkout>/.jax_cache``. The path is fixed, never
temporary or per-process: it is part of the cache's key, so a moving
directory would never hit.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
