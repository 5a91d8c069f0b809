"""Causal softmax attention in plain XLA, one block of queries at a time:
the definition of what the token-level decoders compute (`ops/mla.py
mla_attend_xla`; `ops/gqa_attention.py gqa_attend` under
`models/grouped_attention.py` for `models/lfm2_moe.py` and
`models/exaone_moe.py`), under whichever scope the caller opens.

Which form runs where: on a TPU the layers that answer every position of
a whole history run a Pallas kernel in this one's place wherever their
predicate admits the shape (`ops/mla.py mla_attend_fwd`;
`ops/gqa_attention.py gqa_attend_fwd`, full and windowed, at the published
shapes of both grouped-query families). This form is what runs on every
other platform, at every shape the predicates refuse, with ``read`` (a
model's last layer) and in the backward everywhere: the kernels' scaffold
(`ops/kernel_gate.py tpu_kernel_forward`) differentiates it, recomputed.

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

With ``window`` (a sliding window: the query at position ``p`` sees keys
``max(0, p - window + 1) .. p``, itself and the ``window - 1`` before it,
never across the history's start) nothing left of the band is computed
beyond a block's slack. The history is cut into blocks of ``min(window,
query_block)`` queries (padded behind its end to whole blocks), every
block goes against its own keys and those of the blocks before it that
its window reaches (ONE block before it where the block is the window: a
tile of ``2 * window`` keys), and all blocks of a history are ONE batched
pair of products, not a slice a block: the work grows with the history's
length, not with its square. With ``read`` each read position goes
against the ``window`` keys that end at it. A window no shorter than the
history is no window: the form above, operation for operation.
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


def _attend_tiles(q, k, v, scale: float, visible: np.ndarray):
    """``N`` blocks at once, each against a tile of keys of its own: q
    ``[B, N, Q, G, R, E]``, k ``[B, N, K, G, E]``, v ``[B, N, K, G, D]``,
    ``visible`` bool ``[N, Q, K]`` (every query sees a key at least) ->
    ``[B, N, Q, G, R, D]``. `_attend_block`'s arithmetic with one more
    batch axis."""
    b, blocks, asked, groups, share, width = q.shape
    q = q.transpose(0, 1, 4, 2, 3, 5).reshape(b, blocks, share * asked, groups, width)
    scores = jnp.einsum("bnqhe,bnkhe->bnhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    visible = np.tile(visible, (1, share, 1))
    scores = jnp.where(jnp.asarray(visible)[None, :, None], scores, NEG_INF)
    top = scores.max(axis=-1, keepdims=True)
    weights = jnp.exp(scores - top)
    total = weights.sum(axis=-1)  # [B, N, G, R * Q]
    mixed = jnp.einsum(
        "bnhqk,bnkhd->bnqhd", weights.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    mixed = (mixed / total.transpose(0, 1, 3, 2)[..., None]).astype(v.dtype)
    return mixed.reshape(b, blocks, share, asked, groups, -1).transpose(0, 1, 3, 4, 2, 5)


def _attend_band(q, k, v, scale: float, window: int, block: int):
    """Every position's query, q ``[B, S, G, R, E]``, against the band of
    ``window`` keys that ends at it -> ``[B, S, G, R, D]``: blocks of
    ``block`` queries, each against its own keys and those of the ``back``
    blocks before it that the window reaches."""
    b, seq = q.shape[:2]
    blocks = -(-seq // block)
    back = -(-(window - 1) // block)
    behind = blocks * block - seq  # causal: what is padded behind the end reaches no answer

    def cut(x, ahead: int):
        x = jnp.pad(x, ((0, 0), (ahead * block, behind)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape(b, ahead + blocks, block, *x.shape[2:])

    def tiles(x):  # [B, N, (back + 1) * block, G, .]: block n's tile starts at block n - back
        x = cut(x, back)
        return jnp.concatenate([x[:, j : j + blocks] for j in range(back + 1)], axis=2)

    first = (np.arange(blocks) - back)[:, None, None] * block  # a tile's first key
    query_at = (np.arange(blocks)[:, None] * block + np.arange(block))[:, :, None]
    key_at = first + np.arange((back + 1) * block)[None, None, :]
    visible = (0 <= key_at) & (query_at - window < key_at) & (key_at <= query_at)
    out = _attend_tiles(cut(q, 0), tiles(k), tiles(v), scale, visible)
    return out.reshape(b, blocks * block, *out.shape[3:])[:, :seq]


def _attend_band_at(q, k, v, scale: float, window: int, read: np.ndarray):
    """The read positions' queries alone, q ``[B, len(read), G, R, E]``,
    each against the ``window`` keys that end at its position."""
    key_at = read[:, None] - (window - 1) + np.arange(window)[None, :]  # [N, window]
    visible = (0 <= key_at)[:, None, :]
    taken = np.maximum(key_at, 0)
    return _attend_tiles(q[:, :, None], k[:, taken], v[:, taken], scale, visible)[:, :, 0]


def causal_attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: float,
    read: np.ndarray | None = None,
    query_block: int = QUERY_BLOCK,
    window: int | None = None,
) -> jnp.ndarray:
    """``q`` ``[B, S, H, E]``, ``k`` ``[B, S, G, E]``, ``v`` ``[B, S, G,
    D]`` with ``H`` a multiple of ``G`` -> ``[B, S, H, D]`` in ``v``'s
    dtype. With ``read``, ``q`` holds those positions' queries alone,
    ``[B, len(read), H, E]``, and so does the result. With ``window``, a
    query sees itself and the ``window - 1`` keys before it."""
    b, asked, heads, width = q.shape
    groups = k.shape[2]
    if heads % groups:
        raise ValueError(f"{heads} query heads over {groups} key/value heads")
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} keys")
    q = q.reshape(b, asked, groups, heads // groups, width)
    if window is not None and window < k.shape[1]:
        if read is not None:
            out = _attend_band_at(q, k, v, scale, window, np.asarray(read))
        else:
            out = _attend_band(q, k, v, scale, window, min(window, query_block))
    elif read is not None:
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
