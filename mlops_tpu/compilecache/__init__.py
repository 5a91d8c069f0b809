"""Persistent AOT compile cache: kill cold-start by making compiled
executables first-class artifacts (probe -> deserialize hits, compile
misses in parallel -> persist). See `cache.py` for mechanics, `warmup.py`
for the per-entry-point job builders, `keys.py` for the versioned key, and
`registry.py` for the JAX-free surface tpulint's TPU203 rule reads."""

from mlops_tpu.compilecache.cache import (
    CacheJob,
    CompileCache,
    from_config,
)

__all__ = [
    "CacheJob",
    "CompileCache",
    "from_config",
]
