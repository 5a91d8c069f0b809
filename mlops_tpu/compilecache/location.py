"""Where compiled code is kept between processes — decided in one place.

Two stores share one root:

- JAX's own persistent compilation cache (every jit in the process), at
  the root itself;
- this repo's AOT executable store (`cache.py`, ``cache.dir``), which is
  off unless a caller switches it on, at ``<root>/aot-executables`` when
  chip_smoke.py does.

The root is ``$JAX_COMPILATION_CACHE_DIR`` where that is set — JAX reads
the variable itself, and this module then sets no directory at all — and
otherwise ONE fixed, git-ignored path inside the checkout. Never a
temporary name, a pid or a time: the path is part of JAX's cache key, so
a directory that moves never hits.

`enable_persistent_cache` is called before the first compile by the CLI
entry (`commands.py`), chip_smoke.py's children and
tests/conftest.py. This module imports jax only inside that function, so
jax-free parents (chip_smoke.py, the serve supervisor) can ask for the
paths.
"""

from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT = Path(__file__).resolve().parents[2]


def cache_root() -> Path:
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return Path(env) if env else _CHECKOUT / ".jax_cache"


def aot_store_dir() -> Path:
    return cache_root() / "aot-executables"


def enable_persistent_cache(aot_store_on: bool = False) -> Path:
    """Point JAX's persistent compilation cache at `cache_root` and cache
    every compile that takes longer than a blink. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already placed its cache
    there, and this sets no directory.

    ``aot_store_on``: a process that writes the AOT store compiles for
    real, so it is not given JAX's cache here. On the CPU backend of
    jaxlib 0.9.0 (checked in PR 22) an executable that was SERVED from
    JAX's persistent cache serializes into an artifact that does not load
    ("Function ... not found"); the store's validation logs and refuses
    it, so the two caches on together cost the store its hits whenever
    another process compiled the same program first. (On the TPU v5e such
    an executable does round-trip — chip run, PR 22 — and there both
    caches are on together whenever the variable is set.)"""
    import jax

    root = cache_root()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or aot_store_on:
        return root
    jax.config.update("jax_compilation_cache_dir", str(root))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    return root
