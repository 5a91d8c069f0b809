"""EVA chunked linear attention (Zheng et al., arXiv:2302.04542) as
EvaByte's decoder uses it.

A sequence is cut two ways: into WINDOWS of ``window`` positions and into
CHUNKS of ``chunk`` positions (``window`` a multiple of ``chunk``). A
query attends the keys of its own window exactly and causally; everything
in the windows before it, it sees only through one learned summary a
chunk. One softmax runs over both key sets.

- ``rope``: rotary positions, rotate-half convention, applied to q and k
  before anything else; the caller says which frequencies (a base, or the
  inverse frequencies themselves);
- ``eva_prep_kv``: per head and chunk, ``alpha = softmax_m(s * k_m . phi)``
  over the chunk's positions, ``k~ = sum_m alpha_m k_m + mu``, ``v~ =
  sum_m alpha_m v_m`` (``phi``, ``mu`` learned, one pair a head; ``s =
  head_dim ** -0.5``);
- ``eva_attend``: query ``i`` in window ``W(i)`` over the local keys ``m``
  with ``W(m) = W(i)``, ``m <= i``, and the summaries of the chunks that
  lie wholly in a window before ``W(i)``.

Which form of ``eva_attend`` runs where. At S = 16,384 and 32 heads the
scores of ONE sequence are 32 x 8 windows x 2048 x up to 2944 float32 =
6.2 GB, so no form holds them all:

- ``eva_attend_blockwise``, one Pallas kernel (Mosaic): where the
  computation is lowered for a TPU (`kernel_gate.tpu_kernel_or`) and
  `wants_eva_kernel` admits the shape: a head of whole 128-lane tiles, a
  window of whole blocks, 128 summaries (or a multiple) a window, at most
  2,048 keys in a window and summaries in a sequence. EvaByte's shape is
  one. A grid step is one block of 512 queries of one head; it reads q, k,
  v as column blocks of the view ``[B, S, H * D]`` (no heads-major copy;
  XLA still relayouts each once for that view), holds its window's keys
  and the head's summaries in VMEM, and makes two visits: the local keys
  up to its own block (only that block under the causal mask; the blocks
  after it are never read), then the summaries of the windows that are
  past (a window's 128 are visible or invisible as one: no mask). Scores,
  their maximum, the exponentials and their sum are float32 and never
  leave VMEM; both products take the inputs' dtype as operands and
  accumulate in float32; the weights are rounded to the inputs' dtype
  once, as the second product's operand. Only ``o`` is written.
- ``eva_attend_xla``, plain XLA, one head at a time with that head's
  scores in HBM: every other platform, every other shape, and the backward
  everywhere (`kernel_gate.tpu_kernel_forward`: the kernel is the forward,
  the XLA form is recomputed and differentiated).

The kernel's two visits are `ops/lane_softmax.py joint_softmax_state`.

The scopes ``rope``, ``eva_prep_kv`` and ``eva_attend`` are what a device
trace carries (`benchmark/layer_metrics/`); the kernel's operation is
``.../eva_attend/.../eva_attend_fwd``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlops_tpu.ops.attention import NEG_INF
from mlops_tpu.ops.kernel_gate import tpu_kernel_forward
from mlops_tpu.ops.lane_softmax import LANES, joint_softmax_state


def rope_inv_freq(head_dim: int, theta: float) -> np.ndarray:
    """The plain rotary inverse frequencies ``theta ** (-i / half)``, float32
    ``[head_dim // 2]``: worked out on the host in float64 and rounded once,
    so that the angle of a late position does not depend on a device's
    ``pow``."""
    half = head_dim // 2
    return (float(theta) ** (-np.arange(half, dtype=np.float64) / half)).astype(
        np.float32
    )


@jax.named_scope("rope")
def rope(x: jnp.ndarray, freqs, positions: np.ndarray | None = None) -> jnp.ndarray:
    """Rotary positions 0..S-1 (or the S ``positions`` given, where ``x``
    holds some positions of a longer sequence) on ``[B, S, H, D]``,
    rotate-half: with ``x = (x1, x2)`` the two halves of a head, ``(x1 cos
    - x2 sin, x2 cos + x1 sin)``, computed in float32 and returned in
    ``x``'s dtype.

    ``freqs`` is the caller's: a bare base ``theta`` (the plain frequencies,
    ``rope_inv_freq``) or the ``[D // 2]`` inverse frequencies themselves,
    however scaled (`ops/mla.py yarn_inv_freq`)."""
    _, seq, _, head_dim = x.shape
    if head_dim % 2:
        raise ValueError(f"rope needs an even head size, got {head_dim}")
    inv_freq = (
        rope_inv_freq(head_dim, freqs)
        if np.ndim(freqs) == 0
        else np.asarray(freqs, np.float32)
    )
    if inv_freq.shape != (head_dim // 2,):
        raise ValueError(f"{inv_freq.shape} frequencies for a head of {head_dim}")
    at = (
        jnp.arange(seq, dtype=jnp.float32)
        if positions is None
        else jnp.asarray(np.asarray(positions, np.float32))
    )
    angle = at[:, None] * jnp.asarray(inv_freq)[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
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


def _whole_windows(window: int, *tensors: jnp.ndarray):
    """(windows, the ``[B, S, H, D]`` tensors padded to that many whole
    windows): causality keeps the padding from every real query."""
    seq = tensors[0].shape[1]
    windows = -(-seq // window)
    pad = windows * window - seq
    if pad:
        tensors = tuple(jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in tensors)
    return windows, tensors


def eva_attend_xla(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    k_sum: jnp.ndarray,
    v_sum: jnp.ndarray,
    window: int,
    chunk: int,
) -> jnp.ndarray:
    """``eva_attend`` in plain XLA: the form of every platform that is not
    a TPU, of every shape `wants_eva_kernel` refuses, and the definition
    of the backward. One head at a time (``lax.map`` over a heads-major
    copy), every window of it as one batched product over the joint key
    axis (local keys, then every summary a query of any window may see),
    masked: one head's scores are what is live, in HBM."""
    b, seq, heads, head_dim = q.shape
    per_window = window // chunk
    windows, (q, k, v) = _whole_windows(window, q, k, v)
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


# --------------------------------------------------------------------------
# Pallas kernel
# --------------------------------------------------------------------------

LOCAL_BLOCK = 512  # queries a grid step
MAX_VISIT_KEYS = 2048  # a visit's scores are LOCAL_BLOCK x keys float32 in VMEM: 4 MB


def wants_eva_kernel(
    seq: int, head_dim: int, window: int, chunk: int, block: int = LOCAL_BLOCK
) -> bool:
    """The kernel's tiling rule, from shapes alone. A head is whole lane
    tiles (``head_dim % 128``: a head's rows are then a column block of the
    ``[B, S, H * D]`` view); a window is whole blocks of whole lane tiles;
    a window's summaries are whole 128-lane score tiles, so a tile of them
    is visible or invisible as one and needs no mask; and the widest visit
    (a whole window of keys, or every summary of a sequence) fits VMEM as
    float32 scores beside the window and the summaries themselves. Every
    other shape (each tiny configuration of the tests) takes the XLA form."""
    block = min(block, window)
    return (
        head_dim % 128 == 0
        and block % 128 == 0
        and window % block == 0
        and (window // chunk) % 128 == 0
        and max(window, seq // chunk) <= MAX_VISIT_KEYS
    )


def _eva_kernel(
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, block, per_window, windows,
):
    """One (history, head, window, query block) step: ``q_ref`` holds the
    block's queries, ``k_ref``/``v_ref`` the whole window's keys and
    values, ``ks_ref``/``vs_ref`` every summary of the head. Two visits a
    row, each of a static width chosen by the step's place: every local
    key the block sees at once (the blocks before its own unmasked, its
    own under the causal mask), then every summary of the windows that
    are past at once. Keys no query of the block sees are never read.

    The softmax state between the two visits is float32 in VMEM scratch:
    the row maximum ``m``, the unnormalised accumulator ``acc``, and the
    normaliser ``l`` as 128 partial sums a row. A visit is
    `ops/lane_softmax.py joint_softmax_state` (one cross-lane reduction
    for its maximum); the sum takes one in all, at the end."""
    w, qi = pl.program_id(2), pl.program_id(3)
    q = q_ref[0]

    def visit(keys_ref, values_ref, parts, first):
        """``parts``: (first row, rows, causal), the key sets of one joint
        softmax, each read from ``keys_ref`` and ``values_ref``."""
        scores = []
        for start, size, causal in parts:
            s = jax.lax.dot_general(
                q, keys_ref[0, start : start + size, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [block, size]
            if causal:
                row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(col <= row, s, NEG_INF)
            scores.append((start, s))
        m_prev, m_new, l_new, acc_new = joint_softmax_state(
            scores,
            lambda start, size: values_ref[0, start : start + size, :],
            carried=None if first else lambda: m_ref[:, :1],
        )
        if first:
            l_ref[:], acc_ref[:] = l_new, acc_new
        else:
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:] = l_ref[:] * alpha + l_new
            acc_ref[:] = acc_ref[:] * alpha + acc_new
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    # one branch a place, each of static widths; a flat chain of `pl.when`s
    # (a `lax.switch` nests its branches, and Mosaic's layout inference
    # recurses through the nest: sixteen windows overflowed its stack)
    for before in range(k_ref.shape[1] // block):  # query blocks before the step's

        @pl.when(qi == before)
        def _local(before=before):
            own = (before * block, block, True)
            earlier = [(0, before * block, False)] if before else []
            visit(k_ref, v_ref, [*earlier, own], first=True)

    for past in range(1, windows):  # windows before the step's

        @pl.when(w == past)
        def _remote(past=past):
            visit(ks_ref, vs_ref, [(0, past * per_window, False)], first=False)

    normaliser = jnp.sum(l_ref[:], axis=-1, keepdims=True)
    o_ref[0] = (acc_ref[:] / normaliser).astype(o_ref.dtype)


def eva_attend_blockwise(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    k_sum: jnp.ndarray,
    v_sum: jnp.ndarray,
    window: int,
    chunk: int,
    block: int = LOCAL_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """``eva_attend`` as one Pallas kernel, for shapes `wants_eva_kernel`
    admits. Compiled by Mosaic (``interpret=False``): it lowers for a TPU
    and raises anywhere else; ``interpret=True`` is for CPU tests, which
    pass it themselves. Scores and weights never leave VMEM, and no tile
    the mask would blank whole is visited: of a window's local tiles the
    ``n (n + 1) / 2`` on or under the diagonal (``n = window // block``),
    of the summaries those of the windows that are past."""
    b, seq, heads, head_dim = q.shape
    block = min(block, window)
    if not wants_eva_kernel(seq, head_dim, window, chunk, block):
        raise ValueError(
            f"no tiling for head {head_dim}, window {window}, chunk {chunk}, "
            f"block {block}, {seq // chunk} summaries"
        )
    per_window = window // chunk
    windows, (q, k, v) = _whole_windows(window, q, k, v)
    blocks = window // block
    # heads side by side on the minor axis: a head's rows are the column
    # block h of this view. Not free on a TPU: `rope` leaves q and k tiled
    # over (H, D) and the view is tiled over (S, H * D), so XLA moves q, k
    # and v once each (PERF.md section 5); ``o`` is written as `out` reads it
    flat = lambda t: t.reshape(b, t.shape[1], heads * head_dim)
    summaries = k_sum.shape[1]

    out = pl.pallas_call(
        functools.partial(
            _eva_kernel, scale=head_dim**-0.5, block=block, per_window=per_window,
            windows=windows,
        ),
        grid=(b, heads, windows, blocks),
        in_specs=[
            pl.BlockSpec(
                (1, block, head_dim), lambda bi, h, w, qi: (bi, w * blocks + qi, h)
            ),
            # the window's keys and values: the same block for every query
            # block of the window, so fetched once a window
            pl.BlockSpec((1, window, head_dim), lambda bi, h, w, qi: (bi, w, h)),
            pl.BlockSpec((1, window, head_dim), lambda bi, h, w, qi: (bi, w, h)),
            # the head's summaries: fetched once a head
            pl.BlockSpec((1, summaries, head_dim), lambda bi, h, w, qi: (bi, 0, h)),
            pl.BlockSpec((1, summaries, head_dim), lambda bi, h, w, qi: (bi, 0, h)),
        ],
        out_specs=pl.BlockSpec(
            (1, block, head_dim), lambda bi, h, w, qi: (bi, w * blocks + qi, h)
        ),
        out_shape=jax.ShapeDtypeStruct((b, windows * window, heads * head_dim), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),  # row maximum m
            pltpu.VMEM((block, LANES), jnp.float32),  # normaliser l, 128 partial sums a row
            pltpu.VMEM((block, head_dim), jnp.float32),  # unnormalised accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY, pltpu.ARBITRARY,
            ),
        ),
        interpret=interpret,
        name="eva_attend_fwd",
    )(flat(q), flat(k), flat(v), flat(k_sum), flat(v_sum))
    return out.reshape(b, windows * window, heads, head_dim)[:, :seq]


_eva_attend = tpu_kernel_forward(
    eva_attend_blockwise, eva_attend_xla, static_argnames=("window", "chunk")
)


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
    full is padded (causality keeps the padding from every real query).
    Where `wants_eva_kernel` admits the shape and the computation is
    lowered for a TPU (`kernel_gate`), the forward is the blockwise kernel
    and the backward the XLA form's; everywhere else the XLA form is both."""
    _, seq, _, head_dim = q.shape
    if window % chunk:
        raise ValueError(f"window {window} is not whole chunks of {chunk}")
    if not wants_eva_kernel(seq, head_dim, window, chunk):
        return eva_attend_xla(q, k, v, k_sum, v_sum, window, chunk)
    return _eva_attend(q, k, v, k_sum, v_sum, window=window, chunk=chunk)
