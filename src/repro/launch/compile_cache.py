"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Call :func:`enable_compile_cache` before the first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and no
other is set.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because the path is part of what a later process must find
again (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # Cache every executable: the Pallas kernels compile in under the
    # default one-second floor, once per shape bucket.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
