"""Causal softmax attention in plain XLA, one block of queries at a time:
the one form both token-level decoders call (`ops/mla.py mla_attend_xla`,
`models/lfm2_moe.py`), under whichever scope the caller opens.

Query heads may outnumber key/value heads (grouped-query attention):
head ``i`` of ``H`` reads key/value head ``i // (H // G)`` of ``G``. The
``H // G`` query heads of a group go side by side on the QUERY axis of
the group's one key/value head (a block of ``Q`` queries is ``H // G * Q``
rows against ``[B, K, G, E]``), so keys and values are read where they lie
and never repeated in HBM; with ``H == G`` the stacking is the identity
and the form is plain multi-head attention.

``softmax(q k^T * scale + causal mask) v`` with the scores, their
maximum, exponentials and sum in float32, the two products on the
inputs' dtype with float32 accumulation, the weights rounded to the
inputs' dtype once before the second product. Each block of
``query_block`` queries goes against the keys up to the block's end: the
blocks above the diagonal are never computed, and one block's scores
(heads x block x keys so far) are what is live in HBM. With ``read``
(positions) the caller hands over those positions' queries alone, and
each goes against every key up to it.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from mlops_tpu.ops.attention import NEG_INF

QUERY_BLOCK = 512


def _attend_block(q, k, v, scale: float, query_at: np.ndarray):
    """One block: q ``[B, Q, G, R, E]`` (``R`` query heads a key/value
    head) at positions ``query_at`` ``[Q]`` against k ``[B, K, G, E]``, v
    ``[B, K, G, D]`` at positions 0..K-1 -> ``[B, Q, G, R, D]``."""
    b, asked, groups, share, width = q.shape
    q = q.transpose(0, 3, 1, 2, 4).reshape(b, share * asked, groups, width)
    query_at = np.tile(query_at, share)
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    visible = np.arange(k.shape[1])[None, :] <= query_at[:, None]
    scores = jnp.where(jnp.asarray(visible)[None, None], scores, NEG_INF)
    top = scores.max(axis=-1, keepdims=True)
    weights = jnp.exp(scores - top)
    total = weights.sum(axis=-1)  # [B, G, R * Q]
    mixed = jnp.einsum(
        "bhqk,bkhd->bqhd", weights.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    mixed = (mixed / total.transpose(0, 2, 1)[..., None]).astype(v.dtype)
    return mixed.reshape(b, share, asked, groups, -1).transpose(0, 2, 3, 1, 4)


def causal_attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: float,
    read: np.ndarray | None = None,
    query_block: int = QUERY_BLOCK,
) -> jnp.ndarray:
    """``q`` ``[B, S, H, E]``, ``k`` ``[B, S, G, E]``, ``v`` ``[B, S, G,
    D]`` with ``H`` a multiple of ``G`` -> ``[B, S, H, D]`` in ``v``'s
    dtype. With ``read``, ``q`` holds those positions' queries alone,
    ``[B, len(read), H, E]``, and so does the result."""
    b, asked, heads, width = q.shape
    groups = k.shape[2]
    if heads % groups:
        raise ValueError(f"{heads} query heads over {groups} key/value heads")
    q = q.reshape(b, asked, groups, heads // groups, width)
    if read is not None:
        read = np.asarray(read)
        stop = int(read.max()) + 1
        out = _attend_block(q, k[:, :stop], v[:, :stop], scale, read)
    else:
        blocks = []
        for start in range(0, asked, query_block):
            stop = min(start + query_block, asked)
            blocks.append(
                _attend_block(
                    q[:, start:stop], k[:, :stop], v[:, :stop], scale, np.arange(start, stop)
                )
            )
        out = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)
    return out.reshape(b, asked, heads, v.shape[-1])
