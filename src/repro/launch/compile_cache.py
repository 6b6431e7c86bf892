"""Where the repository's entry points keep JAX's persistent compile cache.

Called by ``chip_smoke.py`` and the examples, never at library import: a
library user chooses their own cache. The directory is part of each
entry's key, so it is one fixed path, never a temporary name, a pid or a
timestamp.
"""

from __future__ import annotations

import os
import pathlib

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set (and no other), else
    ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
