"""On-disk caches, kept inside the checkout.

JAX's persistent compilation cache keys entries partly by their path, so
it lives at one fixed directory (``<repo>/.jax_cache``) found from this
package's own location — never from a temp name, a pid or the clock — and
a second run of the same program finds what the first one compiled.  The
kernel tuning cache (``repro.kernels.autotune``) sits next to it.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout root: ``<repo>/src/repro/runtime/caches.py`` -> ``<repo>``
REPO_ROOT = Path(__file__).resolve().parents[3]
COMPILE_CACHE_DIR = REPO_ROOT / ".jax_cache"
TUNE_CACHE_FILE = REPO_ROOT / ".pvq_tune_cache.json"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    the path in effect.  Call from entry points, never at import.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself, so
    nothing is set here.  Otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
