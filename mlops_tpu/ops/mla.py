"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) in its
expanded (prefill) form, with YaRN-scaled rotary positions.

Queries and keys are ``nope + rope`` wide (128 + 64 at the published
sizes), values ``v`` wide (128): one head width for q and k, another for
v. The rotary part of a key is ONE vector a position, shared by all
heads; the caller expands keys and values from the latent and hands this
module the projections as they are written (`mla_attend`). A scorer has
no decode step and keeps no cache, so the absorbed form (products against
the latent itself) is not here.

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
- ``mla_attend``: ``softmax(q k^T * scale + causal mask) v`` with the
  scores, their maximum, exponentials and sum in float32, the two
  products on the inputs' dtype with float32 accumulation, the weights
  rounded to the inputs' dtype once before the second product. With
  ``read`` (positions) the caller hands over those positions' queries
  alone, and each goes against every key up to it.

Which form of ``mla_attend`` runs where. At S = 3,072 and 64 heads the
scores of one block of 512 queries of two histories are up to 0.8 GB of
float32:

- ``mla_attend_blockwise``, one Pallas kernel (Mosaic, ``mla_attend_fwd``):
  where the computation is lowered for a TPU (`kernel_gate.tpu_kernel_or`)
  and `wants_mla_kernel` admits the shape: un-rotated keys and values of
  one 128-lane tile each, a rotary part of at most one tile, a sequence
  of whole query blocks and at most 4,096 positions. The published shape
  is one. A grid step is one block of 512 queries of one head. It reads
  the head's keys and values as column blocks ``2h`` and ``2h + 1`` of
  ``kv_b``'s output ``[B, S, H * 256]`` where that product wrote them (no
  slice, no rotary key copied to every head, no joined ``k`` in HBM),
  holds them and the history's rotary keys in VMEM, and makes one visit:
  every key before its own block unmasked, its own block under the
  causal mask; the blocks after it are never read. A score is ``q_nope .
  k_nope + q_rot . k_rot``, the rotary parts zero-padded from 64 to 128
  lanes (192 is no whole number of lane tiles, and a v5e's MXU contracts
  over 128 at a time either way), as ONE product over 256 lanes against
  the head's two key parts put side by side in VMEM. Scores, their
  maximum, the exponentials and their sum are float32 and never leave
  VMEM. Only ``o`` is written, ``[B, S, H * 128]``, as the output
  projection reads it.
- ``mla_attend_xla`` over `ops/causal_attention.py causal_attend` (the
  one causal XLA form of both token-level decoders), the definition:
  whole ``q``, ``k``, ``v`` put together, then one block of queries at a
  time against the keys up to the block's end (the blocks above the
  diagonal are never computed, and one block's scores, heads x block x
  keys so far, are live in HBM). Every other platform, every other shape,
  ``read`` (the last layer: 64 queries a history against whole keys, a
  small memory-bound block), and the backward everywhere
  (`kernel_gate.tpu_kernel_forward`: the kernel is the forward, the XLA
  form is recomputed and differentiated).

The kernel's visit is `ops/lane_softmax.py joint_softmax`.

The scope ``mla_attend`` is what a device trace carries
(`benchmark/layer_metrics/mla_attend_roofline_pct.py`); the kernel's
operation is ``.../mla_attend/.../mla_attend_fwd``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlops_tpu.ops.attention import NEG_INF
from mlops_tpu.ops.causal_attention import causal_attend
from mlops_tpu.ops.kernel_gate import tpu_kernel_forward
from mlops_tpu.ops.lane_softmax import VMEM_LIMIT_BYTES, joint_softmax


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


# --------------------------------------------------------------------------
# Pallas kernel
# --------------------------------------------------------------------------

KERNEL_BLOCK = 512  # queries a grid step
MAX_VISIT_KEYS = 4096  # a step's scores are KERNEL_BLOCK x keys float32 in VMEM: 8 MB


def wants_mla_kernel(
    seq: int, nope: int, rot: int, wide: int, block: int = KERNEL_BLOCK
) -> bool:
    """The kernel's tiling rule, from shapes alone. The un-rotated key part
    and the value are ONE lane tile each (a head's are then column blocks
    ``2h`` and ``2h + 1`` of ``kv_b``'s output as written); the rotary part
    fits the lane tile it is zero-padded to; the sequence is whole query
    blocks of whole lane tiles; and a step's one visit (every key of the
    sequence at the last block) fits VMEM as float32 scores beside the
    head's keys and values. Every other shape (each tiny configuration of
    the tests, a ragged or a longer history) takes the XLA form."""
    return (
        nope == wide == 128
        and 0 < rot <= 128
        and block % 128 == 0
        and seq % block == 0
        and seq <= MAX_VISIT_KEYS
    )


def _mla_kernel(qn_ref, qr_ref, kn_ref, v_ref, kr_ref, o_ref, keys_ref, *, scale, block):
    """One (history, head, query block) step. ``qn_ref``/``qr_ref`` hold the
    block's queries (un-rotated part; rotary part padded to a lane tile),
    ``kn_ref``/``v_ref`` the head's keys and values at every position,
    ``kr_ref`` the history's rotary keys (one a position, every head's,
    padded alike). At a head's first block the two key parts are put side
    by side in the VMEM scratch ``keys_ref`` ``[S, 256]``, so a score is
    ONE product contracted over 256 lanes (the MXU adds the rotary part's
    pass to the other's; as two products and an add the kernel was 4%
    slower, PERF.md section 6, PR 32). Then ONE visit of a static width
    chosen by the step's place: every key before the block's own unmasked
    and the block's own under the causal mask, in one softmax. Keys after
    the block are never read. The visit's softmax is
    `ops/lane_softmax.py joint_softmax`."""
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _join_the_heads_keys():
        keys_ref[:, :128] = kn_ref[0]
        keys_ref[:, 128:] = kr_ref[0]

    q = jnp.concatenate([qn_ref[0], qr_ref[0]], axis=-1)  # [block, 256]

    def scores(start, size):
        return jax.lax.dot_general(
            q, keys_ref[start : start + size, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    def visit(before):
        own = scores(before * block, block)
        row = jax.lax.broadcasted_iota(jnp.int32, own.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, own.shape, 1)
        parts = [(before * block, jnp.where(col <= row, own, NEG_INF))]
        if before:
            parts.insert(0, (0, scores(0, before * block)))
        mixed = joint_softmax(parts, lambda start, size: v_ref[0, start : start + size, :])
        o_ref[0] = mixed.astype(o_ref.dtype)

    # one branch a place, each of static widths: a flat chain of `pl.when`s
    # (a `lax.switch` nests its branches and overflowed Mosaic's layout
    # inference at sixteen, PERF.md section 6, PR 30)
    for before in range(kn_ref.shape[1] // block):  # query blocks before the step's

        @pl.when(qi == before)
        def _place(before=before):
            visit(before)


def mla_attend_blockwise(
    q_nope: jnp.ndarray,
    q_rot: jnp.ndarray,
    kv: jnp.ndarray,
    k_rot: jnp.ndarray,
    scale: float,
    block: int = KERNEL_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """``mla_attend`` as one Pallas kernel, for shapes `wants_mla_kernel`
    admits. Compiled by Mosaic (``interpret=False``): it lowers for a TPU
    and raises anywhere else; ``interpret=True`` is for CPU tests, which
    pass it themselves. ``kv`` is read where ``kv_b`` wrote it: head ``h``'s
    un-rotated keys are column block ``2h`` of ``[B, S, H * 2 * 128]`` and
    its values column block ``2h + 1``; the rotary keys are fetched once a
    history. The rotary parts are zero-padded to a lane tile here (192
    lanes are no whole number of tiles, and the MXU contracts over 128 at
    a time either way)."""
    b, seq, heads, nope = q_nope.shape
    rot, wide = q_rot.shape[-1], kv.shape[-1] // heads - nope
    if not wants_mla_kernel(seq, nope, rot, wide, block):
        raise ValueError(
            f"no tiling for widths {nope} + {rot} against {wide}, {seq} positions, "
            f"block {block}"
        )
    pad = 128 - rot
    q_rot = jnp.pad(q_rot, ((0, 0), (0, 0), (0, 0), (0, pad))).reshape(b, seq, heads * 128)
    k_rot = jnp.pad(k_rot, ((0, 0), (0, 0), (0, pad)))
    q_block = pl.BlockSpec((1, block, 128), lambda bi, h, qi: (bi, qi, h))
    return pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, block=block),
        grid=(b, heads, seq // block),
        in_specs=[
            q_block,
            q_block,
            # the head's keys and values: the same blocks for every query
            # block of the head, so fetched once a head
            pl.BlockSpec((1, seq, 128), lambda bi, h, qi: (bi, 0, 2 * h)),
            pl.BlockSpec((1, seq, 128), lambda bi, h, qi: (bi, 0, 2 * h + 1)),
            # the rotary keys: fetched once a history
            pl.BlockSpec((1, seq, 128), lambda bi, h, qi: (bi, 0, 0)),
        ],
        out_specs=q_block,
        out_shape=jax.ShapeDtypeStruct((b, seq, heads * wide), kv.dtype),
        scratch_shapes=[pltpu.VMEM((seq, 256), kv.dtype)],  # a head's keys, both parts
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="mla_attend_fwd",
    )(q_nope.reshape(b, seq, heads * nope), q_rot, kv, kv, k_rot)


def mla_attend_xla(
    q_nope: jnp.ndarray,
    q_rot: jnp.ndarray,
    kv: jnp.ndarray,
    k_rot: jnp.ndarray,
    scale: float,
    read: np.ndarray | None = None,
) -> jnp.ndarray:
    """``mla_attend`` in plain XLA, the definition: whole ``q``, ``k``,
    ``v`` put together (the rotary key of a position handed to every
    head) and `causal_attend`. The form of every platform that is not a
    TPU, of every shape `wants_mla_kernel` refuses, of ``read`` and of the
    backward."""
    b, seq, heads, nope = q_nope.shape
    kv = kv.reshape(b, kv.shape[1], heads, -1)
    k_rot = jnp.broadcast_to(k_rot[:, :, None], (*kv.shape[:3], k_rot.shape[-1]))
    mixed = causal_attend(
        jnp.concatenate([q_nope, q_rot], axis=-1),
        jnp.concatenate([kv[..., :nope], k_rot], axis=-1),
        kv[..., nope:],
        scale,
        read=read,
    )
    return mixed.reshape(b, seq, -1)


_mla_attend = tpu_kernel_forward(
    mla_attend_blockwise, mla_attend_xla, static_argnames=("scale",)
)


@jax.named_scope("mla_attend")
def mla_attend(
    q_nope: jnp.ndarray,
    q_rot: jnp.ndarray,
    kv: jnp.ndarray,
    k_rot: jnp.ndarray,
    scale: float,
    read: np.ndarray | None = None,
) -> jnp.ndarray:
    """Causal latent attention from the projections as they are written:
    ``q_nope`` ``[B, Q, H, nope]`` and ``q_rot`` ``[B, Q, H, rot]`` (after
    `rope`), ``kv`` ``[B, S, H * (nope + v)]`` (``kv_b``'s output: a head's
    un-rotated keys, then its values) and ``k_rot`` ``[B, S, rot]`` (after
    `rope`; one a position) -> ``[B, Q, H * v]`` in ``kv``'s dtype, as the
    output projection reads it. ``Q`` is ``S``, or with ``read`` those
    positions alone.

    Where `wants_mla_kernel` admits the shape and the computation is
    lowered for a TPU (`kernel_gate`), the forward is the blockwise kernel
    and the backward the XLA form's; everywhere else, and with ``read`` (a
    few queries a history against whole keys: memory-bound, one small
    block), the XLA form is both."""
    _, seq, heads, nope = q_nope.shape
    rot, wide = q_rot.shape[-1], kv.shape[-1] // heads - nope
    if read is not None or not wants_mla_kernel(seq, nope, rot, wide):
        return mla_attend_xla(q_nope, q_rot, kv, k_rot, scale, read=read)
    return _mla_attend(q_nope, q_rot, kv, k_rot, scale=scale)
