"""The joint softmax of one visit of a blockwise attention kernel, written
once for the three that make one (`ops/eva_attention.py _eva_kernel`,
`ops/mla.py _mla_kernel`, `ops/gqa_attention.py _gqa_kernel`).

A VISIT is what a grid step does in one go: a block of query rows against
one or more key sets side by side (say the blocks before the step's own,
unmasked, and its own under the causal mask), all in ONE softmax. The
kernel forms and masks the scores, float32 ``[rows, n * 128]`` a set; the
visit takes their maximum, the exponentials, the normaliser and one
float32 ``p @ values`` a set, all in VMEM.

Cross-lane reductions are what this shape of kernel pays for: a running
maximum and sum reduced at every 512-key tile were three fifths of the
first version of `eva_attend_fwd`'s time (PERF.md section 6). So
the maximum takes ONE cross-lane reduction a visit, after an elementwise
maximum over the visit's lane tiles (`over_lane_tiles`), and the
normaliser is kept as 128 partial sums a row (lane ``c`` sums the keys
``c mod 128``), reduced across lanes once, where the kernel divides.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

LANES = 128
VMEM_LIMIT_BYTES = 96 * 2**20  # of a v5e's 128 MiB; the default scoped limit is 16

# (first key of the set, its float32 scores ``[rows, n * 128]``)
Parts = Sequence[tuple[int, jax.Array]]
# (first key, keys) -> the set's values ``[keys, width]``
Values = Callable[[int, int], jax.Array]


def over_lane_tiles(x: jax.Array, op: Callable) -> jax.Array:
    """``[rows, n * 128] -> [rows, 128]``: ``op`` over the lane tiles,
    elementwise (no cross-lane reduction)."""
    out = x[:, :LANES]
    for c in range(LANES, x.shape[1], LANES):
        out = op(out, x[:, c : c + LANES])
    return out


def _weigh(parts: Parts, values: Values, carried: Callable[[], jax.Array] | None):
    """(the carried maximum or ``None``, the maximum, a set's lane partial
    sums and float32 ``p @ values`` each). The weights are rounded to the
    values' dtype once, as the product's operand; a set's values are read
    after its weights are made."""
    tile_max = functools.reduce(
        jnp.maximum, (over_lane_tiles(s, jnp.maximum) for _, s in parts)
    )
    top = jnp.max(tile_max, axis=-1, keepdims=True)
    before = None
    if carried is not None:
        before = carried()
        top = jnp.maximum(before, top)
    sums, mixed = [], []
    for start, s in parts:
        p = jnp.exp(s - top)
        sums.append(over_lane_tiles(p, jnp.add))
        v = values(start, s.shape[1])
        mixed.append(
            jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
    return before, top, sums, mixed


def joint_softmax(parts: Parts, values: Values) -> jax.Array:
    """The visit's answer, float32 ``[rows, width]``: the key sets'
    ``parts`` in one softmax over ``values(first key, keys)``, divided by
    the lane sum of the normaliser's partials (a kernel that makes one
    visit a step: `_mla_kernel`, `_gqa_kernel`)."""
    _, _, sums, mixed = _weigh(parts, values, None)
    total = jnp.sum(functools.reduce(jnp.add, sums), axis=-1, keepdims=True)
    return functools.reduce(jnp.add, mixed) / total


def joint_softmax_state(
    parts: Parts, values: Values, carried: Callable[[], jax.Array] | None = None
) -> tuple[jax.Array | None, jax.Array, jax.Array, jax.Array]:
    """The visit's softmax state, not yet divided, for a kernel that makes
    several visits a step and merges them (`_eva_kernel`): (the maximum
    ``carried`` read, or ``None``; the row maximum ``[rows, 1]`` the
    exponentials are taken from; the normaliser's 128 lane partial sums
    ``[rows, 128]``; the float32 weighted values ``[rows, width]``).
    ``carried`` reads the maximum of the visits before, ``[rows, 1]``: the
    visit's maximum is then no less than it, and the caller rescales what
    it holds by ``exp(carried - maximum)``."""
    before, top, sums, mixed = _weigh(parts, values, carried)
    return before, top, functools.reduce(jnp.add, sums), functools.reduce(jnp.add, mixed)
