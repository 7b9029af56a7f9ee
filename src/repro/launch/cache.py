"""Where JAX keeps its persistent compilation cache."""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's own cache directory (`.gitignore` lists `.cache/`).  The
#: path is fixed so that a later run finds what an earlier one stored.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".cache" / "jax"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is changed; otherwise the cache goes to `CACHE_DIR`.  Call it
    from a ``main()``, never on import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
