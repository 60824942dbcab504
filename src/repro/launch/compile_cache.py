"""JAX's persistent compile cache, placed from outside the program.

Every entry point (``chip_smoke.py``, the ``launch/canny_*.py`` CLIs,
the benchmark's ``bench/harness.py``) calls ``use_compile_cache()``
once at start-up; importing the library never does, so tests and
embedding programs keep whatever cache setting they chose.
"""

from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py → the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    the variable itself and no other directory is set here. Otherwise
    the cache lives at the fixed ``<checkout>/.jax_cache`` (listed in
    ``.gitignore``), so a later run from the same checkout finds what an
    earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
