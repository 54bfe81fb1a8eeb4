"""Placement of JAX's persistent compilation cache for the repo's entry
points (``chip_smoke.py``, benchmarks).

A run finds what earlier runs compiled only if the directory stays put:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself, so no directory is set in code), otherwise a fixed
``.jax_cache`` directory inside the checkout — never a temp, pid or
time-based name.

Three JAX defaults would still keep a second run from hitting the cache:

* only compiles slower than ``jax_persistent_cache_min_compile_time_secs``
  (1 s) are written, and the datapath's kernels and the small XLA programs
  around them mostly compile faster than that;
* ``jax_persistent_cache_enable_xla_caches`` points XLA's GPU autotune
  cache at a subdirectory of the cache and puts that path in the compile
  options every cache key hashes, so a cache at another path misses every
  entry;
* a compiled Pallas kernel's body (part of the key) records the absolute
  path of its source file, so a checkout at another path misses every
  kernel.

:func:`use_compile_cache` writes every compile, turns the GPU-only extra
cache off, and records source files relative to the checkout, so the key
depends neither on where the cache lives nor on where the checkout does.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIRNAME = ".jax_cache"


def use_compile_cache(root: os.PathLike) -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<root>/.jax_cache``. Call before the first compile."""
    import jax

    checkout = Path(root).resolve()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(checkout) + os.sep))
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(checkout / DEFAULT_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
