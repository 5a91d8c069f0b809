"""Causal grouped-query attention as the token-level decoders build it
(`models/lfm2_moe.py`, `models/exaone_moe.py`, `models/falcon_h1.py`,
`models/nemotron_h.py`): the four projections and, where the family norms
its heads, the two head norms under the CALLING block's own names (``q``,
``k``, ``v``, ``o``, ``q_norm``, ``k_norm``), the optional turn
(`ops/eva_attention.py rope`), `ops/gqa_attention.py gqa_attend` and the
output projection, under the three scopes the caller names.

Which form of the attention runs where (`ops/gqa_attention.py` has it in
full): a layer that answers every position of a whole history, full or
windowed, is ONE call of the Pallas kernel ``gqa_attend_fwd`` under the
caller's second scope wherever the program is lowered for a TPU and
`wants_gqa_kernel` admits the shape (the published ones: `exaone_moe`'s
heads of 128, full and window 128, `falcon_h1`'s, a group of five,
`nemotron_h`'s, a group of sixteen, and `lfm2_moe`'s heads of 64); every
other platform and shape, the ``read`` form (a model's last layer) and
the backward are `ops/causal_attention.py causal_attend` in plain XLA,
which ``query_block`` steers and nothing else.

A family differs in what it passes: a head's width (its own, or ``hidden
// heads``), the window (``None``: every key up to the query), whether the
layer turns its queries and keys, whether a head's query and key are
normed (``normed``: `falcon_h1` and `nemotron_h` norm none and have no
such parameters), a scale on the keys (``key_scale``: `falcon_h1`'s muP
``key_multiplier``), and the scopes' names. The head counts, the rotary
base and the dtypes are the block's own fields (``heads``, ``kv_heads``,
``dtype``; ``rope_theta`` where it turns), the layers its ``_dense`` and
(where it norms) ``_norm``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mlops_tpu.ops.causal_attention import QUERY_BLOCK
from mlops_tpu.ops.eva_attention import rope
from mlops_tpu.ops.gqa_attention import gqa_attend

# the scopes of a layer that sees every key so far, whichever family runs it
# (`benchmark/layer_metrics/bulk_gqa_device_pct.py` reads them)
GQA_SCOPES = ("gqa_qkv", "gqa_attend", "gqa_o")


def grouped_query_attention(
    block: nn.Module,
    h: jnp.ndarray,
    read: np.ndarray | None,
    *,
    head_dim: int,
    scopes: tuple[str, str, str],
    window: int | None = None,
    turn: bool = True,
    query_block: int = QUERY_BLOCK,
    normed: bool = True,
    key_scale: float = 1.0,
) -> jnp.ndarray:
    """``h`` ``[B, S, dim]`` (normed, in the products' dtype) -> ``[B, S,
    dim]``, or ``[B, len(read), dim]`` with ``read``: keys and values at
    every position, everything else at the read positions. Called inside
    ``block``'s compact ``__call__``: the parameters become the block's.
    ``scopes`` names the projections with the head norms and the turn, the
    attention, and the output projection. Without ``normed`` the block
    gets no ``q_norm`` and ``k_norm``; ``key_scale`` multiplies the keys
    (float32) before they turn."""
    b, seq, dim = h.shape
    heads, kv_heads, width = block.heads, block.kv_heads, head_dim
    project, attend, output = scopes
    with jax.named_scope(project):
        asked = h if read is None else h[:, read]
        q = block._dense(heads * width, "q")(asked).reshape(b, -1, heads, width)
        k = block._dense(kv_heads * width, "k")(h).reshape(b, seq, kv_heads, width)
        v = block._dense(kv_heads * width, "v")(h).reshape(b, seq, kv_heads, width)
        if normed:  # a head's query and key, in float32, before they turn
            q, k = block._norm("q_norm")(q), block._norm("k_norm")(k)
        else:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        if key_scale != 1.0:
            k = k * key_scale
        if turn:
            q, k = rope(q, block.rope_theta, positions=read), rope(k, block.rope_theta)
    with jax.named_scope(attend):
        mixed = gqa_attend(
            q.astype(block.dtype), k.astype(block.dtype), v, width**-0.5, read=read,
            query_block=query_block, window=window,
        )
    with jax.named_scope(output):
        return block._dense(dim, "o")(mixed.reshape(b, -1, heads * width))
