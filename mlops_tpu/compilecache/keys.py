"""Versioned cache keys for AOT-compiled executables.

A serialized executable is only reusable in a process whose compiler stack,
backend, and program are EXACTLY the ones that produced it. The key bakes in
every axis that can change the binary:

- format version (this module's serialization layout),
- jax + jaxlib versions (XLA codegen changes between releases),
- backend platform, device kind, device count, and the x64 flag,
- mesh shape (sharded programs embed a device assignment),
- donation flags (donated and undonated lowerings differ),
- the entry-point id and the full abstract call signature
  (pytree structure + per-leaf shape/dtype),
- a config hash covering everything the program closes over that the
  signature cannot see (model family/architecture knobs, optimizer
  schedule constants, ...).

Any mismatch is a MISS, never a wrong artifact — stale executables cannot
be served because a changed component changes the key.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any

CACHE_FORMAT_VERSION = 2


def environment_fingerprint() -> dict[str, Any]:
    """The compiler-stack/backend components of every cache key, read at
    call time (tests monkeypatch this module attribute to simulate version
    bumps)."""
    import jax
    import jaxlib

    device = jax.devices()[0]
    return {
        "format": CACHE_FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(device, "device_kind", "unknown"),
        "device_count": jax.device_count(),
        "x64": bool(jax.config.jax_enable_x64),
    }


def tree_avals(tree: Any) -> Any:
    """Concrete pytree -> matching ShapeDtypeStruct pytree (identity for
    leaves that already are abstract)."""
    import jax

    def aval(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)

    return jax.tree_util.tree_map(aval, tree)


def abstract_signature(args: Any) -> str:
    """Canonical string for a call signature: the flattened pytree
    structure plus every leaf's dtype and shape."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree_avals(args))
    shapes = ",".join(f"{leaf.dtype}{list(leaf.shape)}" for leaf in leaves)
    return f"{treedef}|{shapes}"


def fingerprint(*parts: Any) -> str:
    """Short stable hash of arbitrary JSON-serializable parts (dataclasses
    are converted; everything else falls back to ``str``)."""

    def norm(part: Any) -> Any:
        if dataclasses.is_dataclass(part) and not isinstance(part, type):
            return dataclasses.asdict(part)
        if isinstance(part, (dict, list, tuple, str, int, float, bool)) or part is None:
            return part
        return str(part)

    blob = json.dumps([norm(p) for p in parts], sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def model_fingerprint(model_config: Any) -> str:
    """Hash of everything a predict program closes over that its abstract
    signature cannot see: the model architecture. Params, monitor state,
    and the calibration temperature are ARGUMENTS of the cached programs,
    so their shapes live in the signature and their values never touch the
    executable."""
    return fingerprint("model", model_config)


def train_fingerprint(model: Any, train_config: Any, tag: Any) -> str:
    """Hash for train-step programs: the built model's structure (its flax
    repr names every submodule and hyperparameter), the TrainConfig (the
    optimizer schedule constants are baked into the step), and a tag
    distinguishing program variants (window length, 'tp', ...)."""
    return fingerprint("train", str(model), train_config, tag)


def cache_key(
    entry_id: str,
    abstract_args: Any,
    config_hash: str = "",
    mesh_shape: tuple[int, ...] | None = None,
    donated: bool = False,
    env: dict[str, Any] | None = None,
) -> tuple[dict[str, Any], str]:
    """Assemble the key components and their sha256 digest (the cache file
    name). ``env`` overrides the live environment fingerprint (tests)."""
    signature = abstract_signature(abstract_args)
    components = {
        **(environment_fingerprint() if env is None else env),
        "entry": entry_id,
        "mesh": list(mesh_shape) if mesh_shape is not None else None,
        "donated": bool(donated),
        "config": config_hash,
        "signature_sha": hashlib.sha256(signature.encode()).hexdigest(),
    }
    digest = hashlib.sha256(
        json.dumps(components, sort_keys=True).encode()
    ).hexdigest()
    # The full signature is kept alongside (truncated) for debuggability,
    # but hashed above so arbitrarily large param trees stay keyable.
    components["signature"] = signature[:2000]
    return components, digest
