"""Where JAX keeps its persistent compilation cache, and what keys it.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache lives in one fixed directory inside
the checkout, ``<repo>/.jax_cache`` (git-ignored). The directory is part of
what makes an entry found again, so it is never derived from a temporary
name, a pid or the time.

Either way the checkout's own path is kept out of the cache keys. JAX strips
source locations from a program before hashing it, but a Pallas TPU kernel
carries its Mosaic module, locations included, inside the program as an
opaque payload; so without this every program with a commit kernel would
miss the cache when the same code runs from another checkout.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
REPO_CACHE_DIR = REPO_ROOT / ".jax_cache"
# source paths in program metadata lose this prefix (a regex JAX removes):
# the checkout as Python imported it, which is what the paths spell
SOURCE_PREFIX = "^" + re.escape(
    os.path.join(str(Path(os.path.abspath(__file__)).parents[3]), ""))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and keep
    the checkout path out of its keys (call before the first compile);
    returns the directory in use."""
    import jax

    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      SOURCE_PREFIX)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
