"""Causal grouped-query attention over whole histories, full or over a
sliding window, as `models/grouped_attention.py` calls it for
`models/lfm2_moe.py`, `models/exaone_moe.py`, `models/falcon_h1.py` and
`models/nemotron_h.py`: `ops/causal_attention.py
causal_attend`'s arithmetic (float32 scores, maximum, exponentials and
sum; the two products on the inputs' dtype with float32 accumulation; the
weights rounded to the inputs' dtype once before the second product) in
the form the platform and the shape allow.

Which form of ``gqa_attend`` runs where:

- ``gqa_attend_blockwise``, one Pallas kernel (Mosaic, ``gqa_attend_fwd``):
  where the computation is lowered for a TPU (`kernel_gate.tpu_kernel_or`)
  and `wants_gqa_kernel` admits the shape: every position's query
  (``read is None``), heads of one 128-lane tile or half of one, a history
  of whole query blocks, a visit whose float32 scores fit VMEM. Every
  published shape is (`exaone_moe`: 64 heads over 8 of 128, full and
  window 128; `lfm2_moe`: 32 over 8 of 64; `falcon_h1`: a group of five
  of 128; `nemotron_h`: 32 over 2 of 128, a group of SIXTEEN, unturned). The operands are read where the
  projections wrote them: a step's queries and outputs are a column block
  of ``[B, S, H * E]``, a key/value head's keys and values column block
  ``g`` of ``[B, S, G * E]`` (at a width of 64 a PAIR of key/value heads is
  the tile, and a query head goes against it beside zeros in the other
  head's lanes: a 64-lane block is no legal block and Mosaic pads such an
  operand to 128 lanes in HBM), indexed by (history, tile) alone: fetched
  once a group and held in VMEM over the group's steps. A step makes ONE
  visit of a static width by its place, for as many of the group's query
  heads, stacked on the query axis as `causal_attend` stacks them, as its
  scores' room in VMEM and the kernel's code size allow (`_tiling`). A
  full layer: 512 rows against every key before its own block unmasked
  and its own block under the causal mask (at 3,072 keys one lane tile of
  heads a step). A window layer: 128 queries (at a window of 128 of ALL
  the group's heads) against one tile of keys from the first block the
  window reaches to the own, under the band's mask. Keys after the block
  are never read,
  scores never leave VMEM, and only ``o`` is written, ``[B, S, H * E]``, as
  the output projection reads it.
- `causal_attend` in plain XLA, the definition: every other platform,
  every shape the predicate refuses (another head width, a ragged or a
  longer history, heads of 64 past 3,072 keys; a window no shorter than
  the history is the full form),
  ``read`` (the last layer: a few queries a history against whole keys, one
  small memory-bound block) and the backward everywhere
  (`kernel_gate.tpu_kernel_forward`: the kernel is the forward, the XLA
  form is recomputed and differentiated).

The kernel's visit is `ops/lane_softmax.py joint_softmax`.

The caller's scope (``gqa_attend``, ``swa_attend``) is what a device trace
carries; the kernel's operation is ``.../<scope>/.../gqa_attend_fwd``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlops_tpu.ops.attention import NEG_INF
from mlops_tpu.ops.causal_attention import QUERY_BLOCK, causal_attend
from mlops_tpu.ops.kernel_gate import tpu_kernel_forward
from mlops_tpu.ops.lane_softmax import LANES, VMEM_LIMIT_BYTES, joint_softmax

STEP_ROWS = 512  # queries a step of a full layer, a lane tile of heads: 512 rows
WINDOW_BLOCK = 128  # queries a step under a window: a tile of keys is a few blocks
MAX_KEYS = 16384  # a key/value tile's keys and values are held whole: 4 MB each
# a step's scores are its rows x the visit's keys, float32, in VMEM: 8 MB
MAX_STEP_SCORES = STEP_ROWS * 4096
# The body is straight-line code, a region a place: 0.0049 bundles for each
# score of each place (Mosaic's final bundles, compiled for a described
# v5e). Measured on a v5e (PERF.md section 6, PR 38): a kernel of 55 k
# bundles or less (11.2 M scores over its places) runs a place at 1.4 cycles
# a bundle; one of 68 k or more pays 0.4 us A STEP for each 1,000 bundles of
# the WHOLE kernel, whichever place the step runs (the 40 us a step of PR
# 33: four and eight heads stacked, 100 k and 200 k bundles)
MAX_CODE_SCORES = 11_000_000


def _blocks_back(window: int) -> int:
    """Blocks before a query block's own that its window reaches."""
    return -(-(window - 1) // WINDOW_BLOCK)


def _tiling(
    seq: int, heads: int, kv_heads: int, width: int, window: int | None
) -> tuple[int | None, int, int] | None:
    """The kernel's tiling rule, from shapes alone: (the window, ``None``
    where it is no shorter than the history: no window; queries a step;
    lane tiles of query heads a step), or ``None`` where no tiling is.

    A head is ONE lane tile, or half of one with an even number of
    key/value heads (a PAIR of them is then the tile): queries, keys,
    values and outputs are column blocks of the projections as written.
    The history is whole query blocks and fits VMEM as one tile's keys and
    values. A step takes as many of a group's lane tiles of query heads,
    stacked on the query axis, as keep its visit's float32 scores in VMEM
    (`MAX_STEP_SCORES`) and the kernel's code, a region for each place of a
    step, resident (`MAX_CODE_SCORES`): all of them under a short window,
    one against a long history."""
    if width not in (LANES, LANES // 2) or heads % kv_heads or kv_heads * width % LANES:
        return None
    fold = LANES // width  # heads a lane tile
    if window is None or window >= seq:
        window, block = None, STEP_ROWS // fold
        places = seq // block  # each one block of keys wider than the last
        visit, keys = seq, block * places * (places + 1) // 2
    else:
        block = WINDOW_BLOCK
        back, blocks = _blocks_back(window), seq // block
        places = min(back, blocks)  # before the band's one place
        visit = min((back + 1) * block, seq)
        keys = block * places * (places + 1) // 2 + (visit if back < blocks else 0)
    if seq % block or seq > MAX_KEYS:
        return None
    tiles = heads // kv_heads
    for share in range(tiles, 0, -1):
        rows = share * fold * block
        if (
            tiles % share == 0
            and rows * visit <= MAX_STEP_SCORES
            and rows * keys <= MAX_CODE_SCORES
        ):
            return window, block, share
    return None


def wants_gqa_kernel(
    seq: int, heads: int, kv_heads: int, width: int, window: int | None = None
) -> bool:
    """Whether `_tiling` has a tiling for the shape; every other shape
    takes the XLA form."""
    return _tiling(seq, heads, kv_heads, width, window) is not None


def _gqa_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, block, share, fold, ratio, window):
    """One (history, tile of key/value heads, lane tiles of queries, query
    block) step. ``q_ref`` holds the block's queries, ``share`` lane tiles
    side by side ``[block, share * 128]``; ``k_ref``/``v_ref`` the key/value
    tile at every position. The step's query heads go on the query axis of
    the one key tile (a block of rows each), then ONE visit of a static
    width chosen by the step's place, in one softmax. A full layer: every
    key before the block's own unmasked, and the own under the causal mask.
    Under a window: one tile of keys from the first block the window
    reaches to the own, under the band's mask. Keys after the block are
    never read.

    ``fold`` narrow heads share a lane tile (2 at a width of 64; ``ratio``
    query heads a key/value head). The key/value tile is then a PAIR of
    heads, and a query head goes against it 128 lanes wide: in the half its
    key/value head has there, beside zeros, so the product over 128 lanes
    is the head's own (finite keys taken for granted: a zero times the
    neighbour's infinity is not one); its values come out in the same half.
    The visit's softmax is `ops/lane_softmax.py joint_softmax`."""
    blocks = k_ref.shape[1] // block
    qi = pl.program_id(2) % blocks
    heads = share * fold  # query heads a step
    first = pl.program_id(2) // blocks * heads  # the step's first of the key/value tile's

    def moved(x, h, home=False):
        """Narrow head ``h`` of the step, float32 ``[block, 128]``: from the
        half it has in its lane tile to its key/value head's half (or back
        ``home``), zeros in the neighbour's half."""
        mine = (first + h) // ratio  # 0 or 1
        x = jnp.where(mine == h % 2, x, pltpu.roll(x, LANES // 2, 1))
        half = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // (LANES // 2)
        return jnp.where(half == (h % 2 if home else mine), x, 0.0)

    def queries(h):
        tile = q_ref[0, :, h // fold * LANES : (h // fold + 1) * LANES]
        return tile if fold == 1 else moved(tile.astype(jnp.float32), h).astype(tile.dtype)

    q = jnp.concatenate([queries(h) for h in range(heads)], axis=0)

    def scores(start, size):
        return jax.lax.dot_general(
            q, k_ref[0, pl.ds(start, size), :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    def seen(s, ahead):
        """``s`` ``[heads * block, n]`` under the mask: its first key lies
        ``ahead`` positions before the block's first query."""
        gap = (  # query's position minus key's, one head's rows
            jax.lax.broadcasted_iota(jnp.int32, (block, s.shape[1]), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (block, s.shape[1]), 1)
            + ahead
        )
        visible = gap >= 0
        if window is not None:
            visible &= gap < window
        by_head = s.reshape(heads, block, s.shape[1])
        return jnp.where(visible[None], by_head, NEG_INF).reshape(s.shape)

    def visit(start, before):
        """The ``before`` blocks from key ``start`` on, then the own."""
        ahead = before * block
        if window is not None:
            parts = [(start, seen(scores(start, ahead + block), ahead))]
        else:
            parts = [(ahead, seen(scores(ahead, block), 0))]
            if before:
                parts.insert(0, (0, scores(0, ahead)))
        out = joint_softmax(parts, lambda at, size: v_ref[0, pl.ds(at, size), :])
        for c in range(share):
            if fold == 1:
                tile = out[c * block : (c + 1) * block]
            else:
                tile = sum(
                    moved(out[h * block : (h + 1) * block], h, home=True)
                    for h in range(c * fold, (c + 1) * fold)
                )
            o_ref[0, :, c * LANES : (c + 1) * LANES] = tile.astype(o_ref.dtype)

    # one branch a place, each of static widths: a flat chain of `pl.when`s
    # (`ops/mla.py`). Under a window every place from `back` on is the same
    # visit but for where it starts.
    back = None if window is None else _blocks_back(window)
    for before in range(blocks if back is None else min(back, blocks)):

        @pl.when(qi == before)
        def _place(before=before):
            visit(0, before)

    if back is not None and back < blocks:

        @pl.when(qi >= back)
        def _band():
            visit(pl.multiple_of((qi - back) * block, block), back)


def gqa_attend_blockwise(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: float,
    window: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """`causal_attend` over every position (``q`` ``[B, S, H, E]``, ``k`` and
    ``v`` ``[B, S, G, E]`` -> ``[B, S, H, E]``) as one Pallas kernel, for
    shapes `wants_gqa_kernel` admits. Compiled by Mosaic
    (``interpret=False``): it lowers for a TPU and raises anywhere else;
    ``interpret=True`` is for CPU tests, which pass it themselves. The
    operands are read as the projections wrote them, ``[B, S, heads * E]``
    (the reshapes here move nothing), a key/value tile's once a group."""
    b, seq, heads, width = q.shape
    groups = k.shape[2]
    tiling = _tiling(seq, heads, groups, width, window)
    if tiling is None:
        raise ValueError(
            f"no tiling for {heads} heads over {groups} of {width}, {seq} positions, "
            f"window {window}"
        )
    window, block, share = tiling
    fold = LANES // width  # heads a lane tile
    tiles = heads // groups  # lane tiles of queries a tile of key/value heads
    steps, blocks = tiles // share, seq // block
    q_block = pl.BlockSpec(
        (1, block, share * LANES), lambda bi, g, j: (bi, j % blocks, g * steps + j // blocks)
    )
    # the group's keys and values: the same blocks for every step of the
    # group, so fetched once a group
    kv_block = pl.BlockSpec((1, seq, LANES), lambda bi, g, j: (bi, 0, g))
    out = pl.pallas_call(
        functools.partial(
            _gqa_kernel, scale=scale, block=block, share=share, fold=fold, ratio=tiles,
            window=window,
        ),
        grid=(b, groups // fold, steps * blocks),
        in_specs=[q_block, kv_block, kv_block],
        out_specs=q_block,
        out_shape=jax.ShapeDtypeStruct((b, seq, heads * width), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="gqa_attend_fwd",
    )(
        q.reshape(b, seq, heads * width),
        k.reshape(b, seq, groups * width),
        v.reshape(b, seq, groups * width),
    )
    return out.reshape(b, seq, heads, width)


_gqa_attend = tpu_kernel_forward(
    lambda q, k, v, scale, window, query_block: gqa_attend_blockwise(q, k, v, scale, window),
    causal_attend,
    static_argnames=("scale", "window", "query_block"),
)


def gqa_attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: float,
    read: np.ndarray | None = None,
    query_block: int = QUERY_BLOCK,
    window: int | None = None,
) -> jnp.ndarray:
    """`causal_attend`, argument for argument and answer for answer. Where
    `wants_gqa_kernel` admits the shape and the computation is lowered for
    a TPU (`kernel_gate`), the forward is the blockwise kernel and the
    backward the XLA form's; everywhere else, and with ``read``, the XLA
    form is both, steered by ``query_block`` as before."""
    seq, heads, width = q.shape[1:]
    if read is not None or not wants_gqa_kernel(seq, heads, k.shape[2], width, window):
        return causal_attend(
            q, k, v, scale, read=read, query_block=query_block, window=window
        )
    return _gqa_attend(q, k, v, scale=scale, window=window, query_block=query_block)
