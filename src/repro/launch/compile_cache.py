"""Where JAX keeps its persistent compilation cache for this repo's programs.

Entry points (``repro.launch.serve``, ``chip_smoke.py``) call
:func:`use_compile_cache` once, before their first compile.  An exported
``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself and nothing here
overrides it.  Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout — a fixed path, because the path is part of what makes a later run
find an entry again.  Importing ``repro`` sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    exported = os.environ.get(ENV_VAR)
    if exported:
        return exported
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
