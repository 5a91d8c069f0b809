"""The routed experts' grouped products of one segment as Pallas kernels
(Mosaic): what `ops/moe_dispatch.py grouped_swiglu` (scope ``experts``) and
`grouped_relu2` (scope ``relu2_experts``) call where the computation is
lowered for a TPU and `wants_grouped_kernel` admits the segment. The XLA
forms, `jax.lax.ragged_dot` calls, stay in `ops/moe_dispatch.py` and are
the definition.

A segment's rows are sorted by expert; ``sizes`` ``[held]`` says how many
each held expert got, and may sum to LESS than the rows (a segment past
the last held assignment is clipped): the rows past the sum belong to no
expert and are left as they are found, undefined, as a grouped product
leaves them.

Two forms of expert, each two kernels over one walk:

- the SwiGLU (`grouped_swiglu_kernels`): ``experts_gate_up_fwd`` makes
  ``silu(x @ gate[e]) * (x @ up[e])`` in ONE call. A grid step owns one
  tile of rows of one expert, reads it once, makes both products over the
  whole contraction with float32 accumulation, and writes the SwiGLU
  (float32 arithmetic, then the one cast to the products' dtype): the two
  float32 ``[rows, f]`` the XLA form writes and reads again never reach
  HBM;
- the ReLU² of Nemotron-H (`grouped_relu2_kernels`), two matrices and no
  gate: ``experts_up_fwd`` makes ``relu(x @ up[e])^2`` the same way, its
  epilogue the square of the ReLU;
- then ``experts_down_fwd``: ``x @ down[e]``, float32 out, the same walk
  without an epilogue.

**The walk** (after `jax.experimental.pallas.ops.tpu.megablox`): the
(expert, row tile) pairs that hold a row, expert by expert, are worked out
from ``sizes`` by XLA and handed to the kernel by scalar prefetch. A tile
that two experts share is visited once for each, one after the other, and
a visit writes its own expert's rows alone. The grid is ``(column tiles,
visits)``, the visits innermost: an expert's block of weights keeps its
index over the expert's consecutive row tiles and is fetched ONCE an
expert and column tile however many row tiles the expert straddles (what
a memory-bound segment of a few rows an expert needs), and the rows are
read once a column tile. The grid's length is static, ``rows // tile +
held - 1`` visits, the most a segment can need; the steps past the last
real visit repeat its block indices (nothing is fetched or written back)
and skip their body.

**Tiles from the rows an expert gets** (`grouped_tiles`, from shapes
alone): the contraction is always whole (no partial sums between steps);
see the function for the row and column tiles and why.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# of a v5e's 128 MiB (the default scoped limit is 16); not the attention kernels' 96
# (`ops/lane_softmax.py`): sized for this module's blocks, BLOCKS_BUDGET_BYTES
VMEM_LIMIT_BYTES = 100 * 2**20
BLOCKS_BUDGET_BYTES = 64 * 2**20  # a step's blocks, each held twice by the pipeline


class Tiles(NamedTuple):
    rows: int  # rows of one expert a step
    gate_up: int  # columns of the first kernel's weights a step (`gate` and `up`, or `up`)
    down: int  # columns of `down` a step


def _widest(width: int, fits) -> int:
    """The largest number of whole 128-lane tiles that divides ``width``
    and that ``fits``; 0 if none does."""
    lanes = width // 128
    for parts in range(1, lanes + 1):
        if lanes % parts == 0 and fits(width // parts):
            return width // parts
    return 0


def grouped_tiles(
    rows: int, held: int, d: int, f: int, itemsize: int = 2, gated: bool = True
) -> Tiles | None:
    """The kernels' tiles for a segment of ``rows`` rows over ``held``
    experts of ``[d, f]`` (and ``[f, d]``), or None where they have none:
    widths that are not whole 128-lane tiles, rows that are not whole row
    tiles, or a contraction too long for VMEM. ``gated``: the SwiGLU's two
    weights (gate, up) a step of the first kernel; else the ReLU²'s one
    (up), whose ``f`` may also be no whole number of lane tiles (Nemotron-H's
    1,856 is 14.5): the first kernel's columns and the second's contraction
    are then the whole width, one block as wide as the array.

    Rows a step, from ``rows // held``, what an even router gives an
    expert: 256 from 1,024 rows an expert on (compute-bound: an expert's
    weights are read for many rows; each expert costs one more visit than
    its rows fill, a sixth of its work at 1,536 rows, and a tile of 512
    doubles that for a product that runs no faster), else 128 (a few rows
    an expert, memory-bound: a tile no longer than a group, so that a
    visit computes few rows that are not its expert's). PERF.md section 6,
    PR 34, has the times by tile. Columns a step: as many as leave the
    step's blocks (rows in, the weights, rows out), held twice each by the
    pipeline, within `BLOCKS_BUDGET_BYTES`; the fewer column tiles, the
    fewer times the rows are read."""
    whole_lanes = f % 128 == 0
    if min(rows, held, d, f) <= 0 or d % 128 or f % 8 or (gated and not whole_lanes):
        return None
    tile = 256 if rows // held >= 1024 else 128
    if rows % tile:
        return None
    weights = 2 if gated else 1

    def gate_up_fits(columns):  # x and the weights in; the activation out
        step = tile * d * itemsize + weights * d * columns * itemsize + tile * columns * itemsize
        return 2 * step <= BLOCKS_BUDGET_BYTES

    def down_fits(columns):  # x, down in; float32 out
        step = tile * f * itemsize + f * columns * itemsize + tile * columns * 4
        return 2 * step <= BLOCKS_BUDGET_BYTES

    if whole_lanes:
        gate_up = _widest(f, gate_up_fits)
    else:
        gate_up = f if gate_up_fits(f) else 0
    down = _widest(d, down_fits)
    if not gate_up or not down:
        return None
    return Tiles(tile, gate_up, down)


def wants_grouped_kernel(
    rows: int, held: int, d: int, f: int, itemsize: int = 2, gated: bool = True
) -> bool:
    """The kernels' rule, from shapes alone (`grouped_tiles` has the
    reasons). Every tiny configuration of the tests, whose widths are no
    lane tiles, takes the XLA form."""
    return grouped_tiles(rows, held, d, f, itemsize, gated) is not None


class Walk(NamedTuple):
    offsets: jnp.ndarray  # int32 [held + 1]: the row each expert's run starts at
    expert: jnp.ndarray  # int32 [visits]: the expert of each grid step
    tile: jnp.ndarray  # int32 [visits]: its row tile
    live: jnp.ndarray  # int32 [1]: the real visits; the steps past them are skipped


def plan_walk(sizes: jnp.ndarray, rows: int, tile: int) -> Walk:
    """The (expert, row tile) pairs that hold a row, expert by expert. An
    empty expert has no visit; an expert whose run starts inside a tile
    visits that tile after the expert before it did."""
    held = sizes.shape[0]
    visits = rows // tile + held - 1
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile
    tiles = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 0)
    upto = jnp.cumsum(tiles)
    live = upto[-1]
    # the steps past the last real visit repeat it: no block index moves
    step = jnp.minimum(jnp.arange(visits, dtype=jnp.int32), jnp.maximum(live - 1, 0))
    # the first expert whose visits end past the step (a count: no search loop)
    expert = jnp.minimum((upto[None, :] <= step[:, None]).sum(axis=1), held - 1)
    tile_of = first[expert] + step - (upto[expert] - tiles[expert])
    return Walk(
        jnp.concatenate([jnp.zeros(1, jnp.int32), ends]),
        expert,
        jnp.clip(tile_of, 0, rows // tile - 1),
        live.reshape(1),
    )


def _swiglu(gated, lifted):
    return jax.nn.silu(gated) * lifted


def relu2(lifted):
    """Nemotron-H's ``relu2``: the square of the ReLU."""
    return jnp.square(jax.nn.relu(lifted))


def _visit_kernel(offsets_ref, expert_ref, tile_ref, live_ref, x_ref, *refs, epilogue):
    """One visit: the tile's rows against the expert's block of each
    weight, whole contraction, float32; ``epilogue`` of the products goes
    to the rows of the tile that are the expert's, and the others keep
    what the visit before wrote (or what was there)."""
    *weight_refs, o_ref = refs
    step = pl.program_id(1)  # read here: interpret mode knows no grid inside a branch

    @pl.when(step < live_ref[0])
    def _visit():
        x = x_ref[...]
        values = epilogue(
            *(jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32) for w_ref in weight_refs)
        )
        rows = o_ref.shape[0]
        first = tile_ref[step] * rows
        start, end = offsets_ref[expert_ref[step]], offsets_ref[expert_ref[step] + 1]
        whole = (start <= first) & (end >= first + rows)

        @pl.when(whole)
        def _every_row():
            o_ref[...] = values.astype(o_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _its_own_rows():
            row = first + jax.lax.broadcasted_iota(jnp.int32, values.shape, 0)
            mine = (row >= start) & (row < end)
            kept = o_ref[...].astype(jnp.float32)
            o_ref[...] = jnp.where(mine, values, kept).astype(o_ref.dtype)


def _grouped_call(name, epilogue, walk, x, weights, tile, columns, out_dtype, interpret):
    """``epilogue(x @ w[e] for w in weights)`` ``[rows, width]``, each row
    by its own expert, as one kernel over ``walk``."""
    rows, inner = x.shape
    width = weights[0].shape[-1]
    rows_in = pl.BlockSpec((tile, inner), lambda n, s, offsets, expert, at, live: (at[s], 0))
    an_experts = pl.BlockSpec(
        (None, inner, columns), lambda n, s, offsets, expert, at, live: (expert[s], 0, n)
    )
    return pl.pallas_call(
        functools.partial(_visit_kernel, epilogue=epilogue),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(width // columns, walk.expert.shape[0]),
            in_specs=[rows_in] + [an_experts] * len(weights),
            out_specs=pl.BlockSpec(
                (tile, columns), lambda n, s, offsets, expert, at, live: (at[s], n)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, width), out_dtype),
        compiler_params=pltpu.CompilerParams(
            # a tile two experts share is revisited: the visits run in order
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(*walk, x, *weights)


def grouped_swiglu_kernels(
    x: jnp.ndarray,
    gate: jnp.ndarray,
    up: jnp.ndarray,
    down: jnp.ndarray,
    sizes: jnp.ndarray,
    tiles: Tiles | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """A segment's three grouped products as two kernels, for shapes
    `wants_grouped_kernel` admits. Compiled by Mosaic
    (``interpret=False``): it lowers for a TPU and raises anywhere else;
    ``interpret=True`` and other ``tiles`` than the rule's are for CPU
    tests, which pass them themselves."""
    rows, d = x.shape
    held, _, f = gate.shape
    tiles = tiles or grouped_tiles(rows, held, d, f, x.dtype.itemsize)
    if tiles is None:
        raise ValueError(f"no tiling for {rows} rows over {held} experts of {d} x {f}")
    walk = plan_walk(sizes, rows, tiles.rows)
    hidden = _grouped_call(
        "experts_gate_up_fwd", _swiglu, walk, x, (gate, up),
        tiles.rows, tiles.gate_up, x.dtype, interpret,
    )
    return _grouped_call(
        "experts_down_fwd", lambda product: product, walk, hidden, (down,),
        tiles.rows, tiles.down, jnp.float32, interpret,
    )


def grouped_relu2_kernels(
    x: jnp.ndarray,
    up: jnp.ndarray,
    down: jnp.ndarray,
    sizes: jnp.ndarray,
    tiles: Tiles | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """A segment's two grouped products of the ReLU² experts, ``down(relu(x
    up)^2)``, as two kernels, for shapes `wants_grouped_kernel` admits with
    ``gated=False``. Compiled by Mosaic (``interpret=False``); ``interpret``
    and other ``tiles`` are for CPU tests."""
    rows, d = x.shape
    held, _, f = up.shape
    tiles = tiles or grouped_tiles(rows, held, d, f, x.dtype.itemsize, gated=False)
    if tiles is None:
        raise ValueError(f"no tiling for {rows} rows over {held} ReLU² experts of {d} x {f}")
    walk = plan_walk(sizes, rows, tiles.rows)
    hidden = _grouped_call(
        "experts_up_fwd", relu2, walk, x, (up,), tiles.rows, tiles.gate_up, x.dtype, interpret,
    )
    return _grouped_call(
        "experts_down_fwd", lambda product: product, walk, hidden, (down,),
        tiles.rows, tiles.down, jnp.float32, interpret,
    )
