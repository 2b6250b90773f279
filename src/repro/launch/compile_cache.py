"""Where JAX keeps its persistent compilation cache.

The entry points (``launch/serve.py``, ``launch/train.py`` and the repo's
``chip_smoke.py``) call ``use_compile_cache()`` once, before their first
compile; importing a module or running the tests never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache (gitignored): a fixed path, so a later run in the same
# checkout finds what an earlier one compiled
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the directory JAX's persistent compilation cache uses.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory
    itself and nothing is changed here; otherwise the cache goes to
    ``REPO_CACHE_DIR``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
