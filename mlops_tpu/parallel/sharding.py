"""Sharding specs: batch layouts + regex partition rules for param trees.

Megatron-style tensor parallelism for the dense trunks: the first matmul of
each block is column-split (output features over 'model'), the second is
row-split (input features over 'model'); XLA inserts the psum on the row-cut
output. Embeddings, norms and the attention module replicate. The same
rules serve MLP and FT-Transformer because both name their projections
accordingly.
"""

from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path regex, spec) — first match wins; default replicate.
PARAM_RULES: tuple[tuple[str, P], ...] = (
    # MLP residual blocks: a = column-parallel, b = row-parallel.
    (r"dense_\d+a/kernel", P(None, "model")),
    (r"dense_\d+b/kernel", P("model", None)),
    (r"stem/kernel", P(None, None)),
    # Transformer attention (MultiHeadSelfAttention) replicates: its dense
    # path multiplies by the qkv kernel flattened to [embed, 3*heads*
    # head_dim] and reads each head as a lane slice, so there is no heads
    # axis for GSPMD to partition. A kernel sharded on heads is gathered
    # at every use and the slices are re-split by all-to-alls: on 4 v5e
    # chips a block's forward then takes 31.1 ms against 12.4 ms with the
    # module replicated and 12.5 ms on one chip (PERF.md section 6, PR 26).
    # FT-Transformer MLP: Dense_0 widens (column), Dense_1 narrows (row).
    (r"block_\d+/Dense_0/kernel", P(None, "model")),
    (r"block_\d+/Dense_1/kernel", P("model", None)),
    # MoE: stacked expert weights [E, ...] — EXPERT parallelism: each
    # device holds E/ep experts (spec right-truncates for the 2-d biases).
    (r"experts_", P("model", None, None)),
)


def serve_mesh(shards: int, offset: int = 0) -> Mesh:
    """A ('model',)-only mesh over ``shards`` devices starting at
    ``offset`` — the serving-side tensor/expert-parallel layout
    (ISSUE 13 ``serve.model_shards``): params shard by `PARAM_RULES`,
    activations and the monitor accumulator replicate, and XLA inserts
    the psums the Megatron column/row cuts imply. No 'data' axis:
    request fan-out is the ENGINE REPLICA SET's job (process-level DP)
    — ``offset`` is how replica r takes ITS device slice
    (``devices[r*S : (r+1)*S]``) when one process's visibility spans
    the whole fleet's devices."""
    import numpy as np

    devices = jax.devices()
    if offset + shards > len(devices):
        raise ValueError(
            f"serve.model_shards={shards} at device offset {offset} "
            f"exceeds the {len(devices)} visible devices in this engine "
            "process"
        )
    return Mesh(np.asarray(devices[offset : offset + shards]), ("model",))


def sharded_avals(tree: Any) -> Any:
    """Concrete COMMITTED pytree -> ShapeDtypeStruct pytree carrying each
    leaf's live sharding: AOT warmup lowers against these so the cached
    executable bakes the same layout the engine's resident state has —
    a sharded engine deserializing an unsharded artifact (or vice versa)
    is excluded by the cache key's mesh_shape axis before it could even
    mismatch here."""

    def aval(leaf):
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=leaf.sharding
        )

    return jax.tree_util.tree_map(aval, tree)


def replicated_avals(tree: Any, mesh: Mesh) -> Any:
    """Abstract pytree -> the same avals pinned to full replication over
    ``mesh`` (batch inputs, the temperature scalar, the accumulator)."""
    sharding = replicated(mesh)

    def aval(leaf):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding)

    return jax.tree_util.tree_map(aval, tree)


def batch_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Shard the leading (batch) axis over 'data'; trailing axes replicated."""
    return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _path_str(path) -> str:
    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in path
    )


def param_shardings(
    mesh: Mesh,
    params: Any,
    rules: tuple[tuple[str, P], ...] = PARAM_RULES,
) -> Any:
    """Map a param pytree to NamedShardings via regex rules (default:
    replicate). Specs with more axes than the leaf are right-truncated."""

    def assign(path, leaf):
        path_s = _path_str(path)
        for pattern, spec in rules:
            if re.search(pattern, path_s):
                if leaf.ndim > len(spec):
                    # Extra LEADING axes (deep-ensemble member axis, vmapped
                    # HPO trial axis) replicate; the rule's axes stay aligned
                    # to the kernel's own trailing dims.
                    spec = P(*([None] * (leaf.ndim - len(spec)) + list(spec)))
                trimmed = P(*spec[: leaf.ndim])
                # Drop 'model' axes that don't divide the dim (tiny leaves).
                sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
                cleaned = []
                for dim, axis in zip(leaf.shape, trimmed):
                    if axis is not None and dim % sizes.get(axis, 1):
                        cleaned.append(None)
                    else:
                        cleaned.append(axis)
                return NamedSharding(mesh, P(*cleaned))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(assign, params)
