"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) in its
expanded (prefill) form, with YaRN-scaled rotary positions.

Queries and keys are ``nope + rope`` wide (128 + 64 at the published
sizes), values ``v`` wide (128): one head width for q and k, another for
v. The rotary part of a key is ONE vector a position, shared by all
heads; the caller expands keys and values from the latent and hands this
module whole ``q``, ``k``, ``v``. A scorer has no decode step and keeps no
cache, so the absorbed form (products against the latent itself) is not
here.

- ``yarn_inv_freq``: the rotary inverse frequencies under YaRN (Peng et
  al., arXiv:2309.00071) as the DeepSeek-V3 reference code computes them
  (``yarn_find_correction_range``, ``yarn_linear_ramp_mask``): frequency
  ``f_i = theta ** (-2 i / dim)`` becomes ``f_i / factor * (1 - m_i) + f_i
  * m_i``, ``m`` being 1 below the lower correction dimension, 0 above the
  upper one and linear between. They go to `ops/eva_attention.py rope` as
  its ``freqs``: one rotary, two callers. The scaling applies at every
  length, also under ``original_positions``.
- ``softmax_scale``: ``qk_head_dim ** -0.5 * (0.1 * mscale_all_dim * ln
  factor + 1) ** 2``.
- ``causal_attend``: ``softmax(q k^T * scale + causal mask) v`` with the
  scores, their maximum, exponentials and sum in float32, the two
  products on the inputs' dtype with float32 accumulation. Plain XLA, one
  block of queries at a time against the keys up to the block's end: the
  blocks above the diagonal are never computed, and no more than one
  block's scores (heads x block x keys so far) are live. With ``read``
  (positions) the caller hands over those positions' queries alone, and
  each goes against every key up to it.

The scope ``mla_attend`` is what a device trace carries
(`benchmark/layer_metrics/mla_attend_roofline_pct.py`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from mlops_tpu.ops.attention import NEG_INF

QUERY_BLOCK = 512


def _yarn_correction_dim(rotations: float, dim: int, theta: float, positions: int) -> float:
    """The rotary dimension whose wavelength makes ``rotations`` turns over
    ``positions`` positions."""
    return dim * math.log(positions / (rotations * 2 * math.pi)) / (2 * math.log(theta))


def yarn_inv_freq(
    dim: int,
    theta: float,
    factor: float,
    original_positions: int,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
) -> np.ndarray:
    """float32 ``[dim // 2]`` inverse frequencies of a ``dim``-wide rotary
    under YaRN; worked out on the host in float64 and rounded once."""
    half = dim // 2
    plain = float(theta) ** (-np.arange(half, dtype=np.float64) / half)
    low = max(math.floor(_yarn_correction_dim(beta_fast, dim, theta, original_positions)), 0)
    high = min(math.ceil(_yarn_correction_dim(beta_slow, dim, theta, original_positions)), dim - 1)
    if low == high:
        high += 0.001  # the reference's guard against a zero-wide ramp
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: a fast dimension, left as it is; 0: slowed by `factor`
    return (plain / factor * (1.0 - keep) + plain * keep).astype(np.float32)


def softmax_scale(qk_head_dim: int, factor: float, mscale_all_dim: float = 1.0) -> float:
    mscale = 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return qk_head_dim**-0.5 * mscale * mscale


def _attend_block(q, k, v, scale: float, query_at: np.ndarray):
    """One block: q ``[B, Q, H, E]`` at positions ``query_at`` ``[Q]``
    against k ``[B, K, H, E]``, v ``[B, K, H, D]`` at positions 0..K-1 ->
    ``[B, Q, H, D]``."""
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    visible = np.arange(k.shape[1])[None, :] <= query_at[:, None]
    scores = jnp.where(jnp.asarray(visible)[None, None], scores, NEG_INF)
    top = scores.max(axis=-1, keepdims=True)
    weights = jnp.exp(scores - top)
    total = weights.sum(axis=-1)  # [B, H, Q]
    mixed = jnp.einsum(
        "bhqk,bkhd->bqhd", weights.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return (mixed / total.transpose(0, 2, 1)[..., None]).astype(v.dtype)


@jax.named_scope("mla_attend")
def causal_attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: float,
    read: np.ndarray | None = None,
    query_block: int = QUERY_BLOCK,
) -> jnp.ndarray:
    """``q``, ``k`` ``[B, S, H, E]``, ``v`` ``[B, S, H, D]`` -> ``[B, S, H,
    D]`` in ``v``'s dtype. With ``read``, ``q`` holds those positions'
    queries alone, ``[B, len(read), H, E]``, and so does the result."""
    seq = q.shape[1]
    if read is not None:
        read = np.asarray(read)
        stop = int(read.max()) + 1
        return _attend_block(q, k[:, :stop], v[:, :stop], scale, read)
    out = []
    for start in range(0, seq, query_block):
        stop = min(start + query_block, seq)
        out.append(
            _attend_block(
                q[:, start:stop], k[:, :stop], v[:, :stop], scale, np.arange(start, stop)
            )
        )
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)
