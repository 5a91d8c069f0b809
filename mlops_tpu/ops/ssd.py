"""The selective state-space scan of Mamba-2 (SSD: Dao and Gu,
arXiv:2405.21060) over whole histories, in its chunked form: the token
mixer of `models/mamba2.py`, which `models/falcon_h1.py` runs beside its
attention in every layer and `models/nemotron_h.py` as a layer of its own.

Per head ``h`` (``P`` channels) of group ``g = h // (H // G)``, with a
state ``H`` ``[P, N]`` that is zero at a history's start:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T
    y_t = H_t C_t + D x_t

``A`` (negative) and ``D`` are one number a head, ``dt_t`` (positive) one
a head and position, ``B_t`` and ``C_t`` ``[N]`` are shared by the heads of
a group. A batch row is ONE history: nothing is carried from one row to
the next, and the recurrence is causal, so rows padded behind a short
history change no answer.

The chunked form, per chunk of ``chunk`` positions with ``a_t = dt_t A``
(the recurrence above regrouped, no approximation):

- within the chunk, ``y_i += sum_{j <= i} exp(sum_{j < k <= i} a_k) (C_i .
  B_j) dt_j x_j``: two products a chunk (``C B^T`` a group, the decayed
  scores against ``dt x`` a head), nothing a history wide: no ``[S, S]``
  array is formed;
- from the state that entered the chunk, ``y_i += exp(sum_{k <= i} a_k)
  H_in C_i``;
- the state that leaves it, ``H_out = exp(sum a) H_in + sum_j exp(sum_{k >
  j} a_k) dt_j x_j B_j^T``: each chunk's own sum is one product, and the
  states are handed from chunk to chunk by a `lax.scan` over the chunks
  (24 steps at 3,072 positions), ONE ``[chunks, B, H, P, N]`` float32
  array.

Decays, cumulative sums and the carried state are float32; the four
products take ``dtype`` operands (each rounded once from float32) and
accumulate in float32, as everywhere in the zoo.

With ``read`` (positions; a model's last layer): the states need ``x``,
``B`` and ``dt`` at every position, the answers are computed at the read
positions alone, ``C`` handed over at those. Plain XLA; the caller's scope
(``ssm_scan``) is what a device trace carries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 128  # Mamba-2's, and the published ``mamba_chunk_size``


def _read_slots(read: np.ndarray, chunk: int, chunks: int):
    """The read positions chunk by chunk: (``within`` ``[chunks, n]``, the
    place in its chunk of each slot's position; ``source`` ``[chunks, n]``,
    which of ``read`` the slot holds; ``flat`` ``[len(read)]``, each read
    position's slot in the flattened table). A chunk with fewer than ``n``
    read positions fills its spare slots with one it has, or with position
    0: computed and never picked."""
    chunk_of, place = np.divmod(np.asarray(read, np.int64), chunk)
    n = max(int(np.bincount(chunk_of, minlength=chunks).max()), 1)
    within = np.zeros((chunks, n), np.int64)
    source = np.zeros((chunks, n), np.int64)
    flat = np.empty(len(chunk_of), np.int64)
    filled = np.zeros(chunks, np.int64)
    for i, (c, p) in enumerate(zip(chunk_of, place)):
        within[c, filled[c] :], source[c, filled[c] :] = p, i
        flat[i] = c * n + filled[c]
        filled[c] += 1
    return within, source, flat


def ssd_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    skip: jnp.ndarray,
    *,
    chunk: int = CHUNK,
    read: np.ndarray | None = None,
    dtype: jnp.dtype = jnp.bfloat16,
) -> jnp.ndarray:
    """``x`` ``[B, S, H, P]``, ``dt`` ``[B, S, H]`` (positive: after its
    softplus), ``a`` ``[H]`` (negative), ``b`` ``[B, S, G, N]``, ``c`` ``[B,
    S, G, N]`` (with ``read``: ``[B, len(read), G, N]``, the read positions'
    own), ``skip`` ``[H]`` -> float32 ``y`` ``[B, S, H, P]`` (with ``read``:
    ``[B, len(read), H, P]``). ``G`` divides ``H``."""
    batch, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    if heads % groups or chunk < 1:
        raise ValueError(f"{heads} heads over {groups} groups, chunks of {chunk}")
    share = heads // groups  # heads a group
    chunks = -(-seq // chunk)
    pad = chunks * chunk - seq

    def chunked(t, *tail):
        """``[B, S, ...]`` -> ``[B, chunks, chunk, *tail]``, zeros behind the
        history's end: a padded position neither decays the state (``dt`` 0)
        nor adds to it."""
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape(batch, chunks, chunk, *tail)

    x, dt, a, skip = (t.astype(jnp.float32) for t in (x, dt, a, skip))
    x = x.reshape(batch, seq, groups, share, width)
    x_c = chunked(x, groups, share, width)
    dt_c = chunked(dt, groups, share)
    b_c = chunked(b, groups, state).astype(dtype)
    # cumulative decay exponents within each chunk, the chunk's positions last
    cum = jnp.cumsum(dt_c * a.reshape(groups, share), axis=2).transpose(0, 1, 3, 4, 2)
    total = cum[..., -1]  # [B, chunks, G, R]
    fed = x_c * dt_c[..., None]  # dt_j x_j, float32

    # each chunk's own sum of what it adds to the state, then the hand-over
    to_end = jnp.exp(total[..., None] - cum).transpose(0, 1, 4, 2, 3)
    added = jnp.einsum(
        "bckgrp,bckgn->cbgrpn", (fed * to_end[..., None]).astype(dtype), b_c,
        preferred_element_type=jnp.float32,
    )

    def hand_over(entering, chunk_of):
        decay, own = chunk_of
        return decay[..., None, None] * entering + own, entering

    _, entered = jax.lax.scan(
        hand_over,
        jnp.zeros((batch, groups, share, width, state), jnp.float32),
        (jnp.exp(total).transpose(1, 0, 2, 3), added),
    )  # [chunks, B, G, R, P, N]: the state each chunk starts from

    if read is None:
        asked = chunk
        c_q = chunked(c, groups, state).astype(dtype)
        cum_q = cum
        visible = np.tril(np.ones((chunk, chunk), bool))[None]  # [1, i, j]: j <= i
    else:
        read = np.asarray(read)
        within, source, flat = _read_slots(read, chunk, chunks)
        asked = within.shape[1]
        c_q = c[:, source].astype(dtype)  # [B, chunks, n, G, N]
        cum_q = jnp.take_along_axis(
            cum, jnp.asarray(within)[None, :, None, None, :], axis=-1
        )
        visible = np.arange(chunk)[None, None, :] <= within[:, :, None]  # [chunks, n, j]

    # within the chunk: decayed scores against dt x
    gap = cum_q[..., :, None] - cum[..., None, :]  # [B, chunks, G, R, i, j]
    decay = jnp.exp(jnp.where(visible[None, :, None, None], gap, -jnp.inf))
    scores = jnp.einsum(
        "bcign,bckgn->bcgik", c_q, b_c, preferred_element_type=jnp.float32
    )
    y = jnp.einsum(
        "bcgrik,bckgrp->bcigrp", (scores[:, :, :, None] * decay).astype(dtype),
        fed.astype(dtype), preferred_element_type=jnp.float32,
    )
    # from the state that entered the chunk
    carried = jnp.einsum(
        "bcign,cbgrpn->bcigrp", c_q, entered.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    y = y + carried * jnp.exp(cum_q).transpose(0, 1, 4, 2, 3)[..., None]
    y = y.reshape(batch, chunks * asked, groups, share, width)
    y = y[:, :seq] if read is None else y[:, flat]
    y = y + skip.reshape(groups, share, 1) * (x if read is None else x[:, read])
    return y.reshape(batch, -1, heads, width)
