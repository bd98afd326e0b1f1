"""Where JAX's persistent compilation cache lives.

``$JAX_COMPILATION_CACHE_DIR`` wins when it is set: jax reads it itself, and
no other directory is set here. Otherwise the cache goes to the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``). The directory must not
move between runs — a temp-, pid- or time-derived path never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
