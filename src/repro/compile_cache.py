"""Where JAX keeps its persistent compilation cache.

A cache only hits when its directory stays put from one process to the
next, so the fallback is a fixed path inside the checkout — never one
built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["ENV_VAR", "DEFAULT_DIR", "cache_dir", "enable"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory the cache lives in: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else :data:`DEFAULT_DIR`."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable() -> str:
    """Turn the persistent cache on and return its directory.  When the
    environment names one, JAX already reads it and nothing is set here."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
