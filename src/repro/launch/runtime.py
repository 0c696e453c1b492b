"""Process start-up settings shared by the repository's entry points.

Call these at start-up, never at import: importing a module must not
change how JAX compiles.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository checkout (``src/repro/launch`` -> root)
REPO_ROOT = Path(__file__).resolve().parents[3]

#: persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is
#: not set: a fixed path, since the directory is part of what makes a
#: later process find an entry again
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing here overrides it. Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
