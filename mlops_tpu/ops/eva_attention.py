"""EVA chunked linear attention (Zheng et al., arXiv:2302.04542) as
EvaByte's decoder uses it, in plain XLA.

A sequence is cut two ways: into WINDOWS of ``window`` positions and into
CHUNKS of ``chunk`` positions (``window`` a multiple of ``chunk``). A
query attends the keys of its own window exactly and causally; everything
in the windows before it, it sees only through one learned summary a
chunk. One softmax runs over both key sets.

- ``rope``: rotary positions, rotate-half convention, applied to q and k
  before anything else;
- ``eva_prep_kv``: per head and chunk, ``alpha = softmax_m(s * k_m . phi)``
  over the chunk's positions, ``k~ = sum_m alpha_m k_m + mu``, ``v~ =
  sum_m alpha_m v_m`` (``phi``, ``mu`` learned, one pair a head; ``s =
  head_dim ** -0.5``);
- ``eva_attend``: query ``i`` in window ``W(i)`` over the local keys ``m``
  with ``W(m) = W(i)``, ``m <= i``, and the summaries of the chunks that
  lie wholly in a window before ``W(i)``.

Why blockwise: at S = 16,384 and 32 heads the scores of ONE sequence are
32 x 8 windows x 2048 x up to 2944 float32 = 6.2 GB. ``eva_attend`` runs
one head at a time (``lax.map`` over a heads-major copy; a head of 128 is
one lane tile, so that copy is a plain transposition), every window of it
as one batched product, so what is live is one head's scores. The scopes
``rope``, ``eva_prep_kv`` and ``eva_attend`` are what a device trace
carries (`benchmark/layer_metrics/`); a Pallas kernel that skips the
masked half of each window is a later change and keeps the names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rope_tables(seq: int, head_dim: int, theta: float):
    """cos, sin ``[seq, head_dim // 2]`` float32. The inverse frequencies
    are worked out on the host in float64 and rounded once, so that the
    angle of a late position does not depend on a device's ``pow``."""
    half = head_dim // 2
    inv_freq = (float(theta) ** (-np.arange(half, dtype=np.float64) / half)).astype(
        np.float32
    )
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    return jnp.cos(angle), jnp.sin(angle)


@jax.named_scope("rope")
def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary positions 0..S-1 on ``[B, S, H, D]``, rotate-half: with ``x =
    (x1, x2)`` the two halves of a head, ``(x1 cos - x2 sin, x2 cos + x1
    sin)``, computed in float32 and returned in ``x``'s dtype."""
    _, seq, _, head_dim = x.shape
    if head_dim % 2:
        raise ValueError(f"rope needs an even head size, got {head_dim}")
    cos, sin = _rope_tables(seq, head_dim, theta)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@jax.named_scope("eva_prep_kv")
def eva_prep_kv(
    k: jnp.ndarray, v: jnp.ndarray, phi: jnp.ndarray, mu: jnp.ndarray, chunk: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunk summaries ``(k~, v~)``, each ``[B, S // chunk, H, D]`` in the
    inputs' dtype, of ``k``, ``v`` ``[B, S, H, D]`` (``k`` after ``rope``)
    with ``phi``, ``mu`` ``[H, D]``. Weights and sums in float32."""
    b, seq, heads, head_dim = k.shape
    if seq % chunk:
        raise ValueError(f"sequence {seq} is not whole chunks of {chunk}")
    kf = k.astype(jnp.float32).reshape(b, seq // chunk, chunk, heads, head_dim)
    vf = v.astype(jnp.float32).reshape(b, seq // chunk, chunk, heads, head_dim)
    logits = (kf * phi.astype(jnp.float32)).sum(-1) * head_dim**-0.5
    alpha = jax.nn.softmax(logits, axis=2)[..., None]  # over a chunk's positions
    k_sum = (alpha * kf).sum(2) + mu.astype(jnp.float32)
    v_sum = (alpha * vf).sum(2)
    return k_sum.astype(k.dtype), v_sum.astype(v.dtype)


@jax.named_scope("eva_attend")
def eva_attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    k_sum: jnp.ndarray,
    v_sum: jnp.ndarray,
    window: int,
    chunk: int,
) -> jnp.ndarray:
    """``[B, S, H, D]`` out of q, k, v ``[B, S, H, D]`` and the summaries
    of ``eva_prep_kv``. Scores, the softmax and its sum are float32; both
    products take the inputs' dtype as operands. A last window that is not
    full is padded (causality keeps the padding from every real query)."""
    b, seq, heads, head_dim = q.shape
    if window % chunk:
        raise ValueError(f"window {window} is not whole chunks of {chunk}")
    per_window = window // chunk
    windows = -(-seq // window)
    pad = windows * window - seq
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v))
    # the summaries any query may see: those of every window but the last
    remote = (windows - 1) * per_window

    def by_head(t, *shape):  # [B, ..., H, D] -> [H, B, *shape, D]
        return jnp.moveaxis(t, 2, 0).reshape(heads, b, *shape, head_dim)

    # one mask for every head and sequence, from index arithmetic alone (a
    # constant of this size would be 48 MB of the executable): key c of the
    # joint key axis is local key c, causal, or the summary of chunk
    # c - window, visible once its whole window is past
    c = jnp.arange(window + remote)
    mask = jnp.where(
        c < window,
        c <= jnp.arange(window)[None, :, None],
        (c - window) // per_window < jnp.arange(windows)[:, None, None],
    )  # [W, window, window + remote]
    scale = head_dim**-0.5

    def one_head(operands):
        qw, kw, vw, ks, vs = operands  # [B, W, window, D] x 3, [B, remote, D] x 2
        keys = jnp.concatenate(
            [kw, jnp.broadcast_to(ks[:, None], (b, windows, remote, head_dim))], axis=2
        )
        values = jnp.concatenate(
            [vw, jnp.broadcast_to(vs[:, None], (b, windows, remote, head_dim))], axis=2
        )
        scores = jnp.einsum(
            "bwqd,bwkd->bwqk", qw, keys, preferred_element_type=jnp.float32
        )
        scores = jnp.where(mask, scores * scale, jnp.finfo(jnp.float32).min)
        weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        mixed = jnp.einsum(
            "bwqk,bwkd->bwqd",
            weights.astype(values.dtype),
            values,
            preferred_element_type=jnp.float32,
        )
        return (mixed / weights.sum(axis=-1, keepdims=True)).astype(qw.dtype)

    out = jax.lax.map(
        one_head,
        (
            by_head(q, windows, window),
            by_head(k, windows, window),
            by_head(v, windows, window),
            by_head(k_sum[:, :remote], remote),
            by_head(v_sum[:, :remote], remote),
        ),
    )  # [H, B, W, window, D]
    out = jnp.moveaxis(out.reshape(heads, b, windows * window, head_dim), 0, 2)
    return out[:, :seq]
