"""Where the entry points keep JAX's persistent compilation cache.

A cold process compiles every bucket kernel it launches, and a dim-8
bucket takes tens of seconds to compile.  The entry points
(``chip_smoke.py``, ``serve_integrals``, ``integrate``) call
:func:`enable_compile_cache` first, never at import, so a later process
in the same checkout finds those kernels compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (git-ignored).  A fixed path: the cache is keyed
# by it, so a name that changed per run would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a directory; return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and no other directory is set here.  Otherwise the cache goes to
    :data:`DEFAULT_DIR` inside the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
