"""A sparse expert layer's routing and dropless dispatch, for a chip that
holds SOME of the experts (expert parallelism's share of a layer).

The layer is told ``(first_expert, experts_held)``. It routes every token
over ALL the experts, normalises a token's weights over everything the
token chose, held here or not, and computes the part of the result that
its own experts give, for exactly the tokens routed to them. What the
absent experts would have added is left out; nothing stands in for the
other chips or for their exchange.

- ``route``: DeepSeek-V3's router (``scoring_func`` sigmoid, ``topk_method``
  noaux_tc, one group): ``s = sigmoid(h W_g)`` in float32; the ``k``
  experts chosen are ``top_k(s + b)``, ``b`` being the selection bias (it
  chooses, it never weighs); ``w_i = scaling * s_i / (sum over the chosen
  of s + eps)``, ``eps`` the family's own (DeepSeek-V3's 1e-20 for
  `models/kimi_k2.py`, 1e-6 for `models/lfm2_moe.py`).
- ``plan``: the (token, slot) assignments that fell on held experts,
  sorted by expert (a stable sort: tokens ascend within an expert), and
  how many each held expert got. No capacity is set and no token is
  dropped.
- ``grouped_swiglu``: ``sum_i w_i * down_i(silu(gate_i(h)) * up_i(h))``
  over the held experts as grouped (ragged) matrix products over exactly
  the assignments that fell here; ``grouped_relu2`` the same walk for
  Nemotron-H's experts, ``sum_i w_i * down_i(relu(up_i(h))^2)``: two
  matrices and no gate. The sorted assignments are walked in
  SEGMENTS of a fixed number of rows (shapes stay static): a segment
  gathers its tokens' rows, runs the three products with its own slice of
  the group sizes (`segment_products`), and its weighted rows are combined
  into the result; a segment past the last held assignment is skipped
  (`lax.cond`), so the work follows the load. The worst case (every token
  choosing only held experts) is ``min(k, held) * tokens`` rows and is
  walked in full: routing skew costs time, never an answer.

Which form of a segment's products runs where:

- `ops/expert_products.py`, two Pallas kernels (Mosaic): where the
  computation is lowered for a TPU (`kernel_gate.tpu_kernel_or`) and
  `wants_grouped_kernel` admits the segment (widths of whole 128-lane
  tiles, or for the ReLU² an expert width taken whole; rows of whole row
  tiles; every cell's published shapes are such). ``experts_gate_up_fwd``
  makes ``gate`` and ``up`` in one pass over the rows with the SwiGLU in
  its epilogue (the two float32 ``[rows, f]`` never reach HBM),
  ``experts_up_fwd`` ``up`` under the ReLU², ``experts_down_fwd`` the last
  product; the tiles follow the rows an expert gets (`grouped_tiles`).
- ``segment_products_xla`` (three `jax.lax.ragged_dot` calls and the
  activation between them) and ``segment_relu2_xla`` (two), the
  definition: every other platform, every other shape (each tiny
  configuration of the tests), and the backward everywhere
  (`kernel_gate.tpu_kernel_forward`: the kernels are the forward, the XLA
  form is recomputed and differentiated).

Which form of the combine runs where:

- one segment holds every held assignment (``segments == 1``, a fact of
  the static shapes: every expert held, or a share of half the experts or
  more): every token GATHERS its own experts' rows and sums them. The
  sorted order is a permutation of the assignment ids, so its inverse
  (`landed`, one more sort of ``T * k`` integers a layer) says where each
  (token, slot) landed; ``result[t] = sum over s of where(held[t, s],
  w[t, s] * out[pos[t, s]], 0)``, the weights and the mask inside the
  reduction, slots outermost so that the gathered ``[k, T, d]`` is
  ``[k * T, d]`` viewed. No scatter: on a v5e a gather of rows runs at
  half the memory's rate and a scatter-add of as many at an eighth of it
  (`lfm2-8b-a1b.bulk-hist`: 49,152 rows of 2,048 float32 a layer and run,
  5.1 ms with its ``where`` against 2.5 and the sort's 0.05).
- several segments under the `fori_loop` (a small share: `kimi-k2-5l`'s 24
  of 384 experts, eight segments of which one is usually live): a segment
  SCATTER-ADDS its weighted rows at their tokens. A token-side gather
  would read ``T * k`` rows a live segment to use a sixteenth of them
  (6,144 tokens, top-8, rows of 7,168 float32: 8.6 ms against the
  scatter's 6.8 alone, and 3% of the cell's rows a second).

The scopes ``router``, ``moe_dispatch`` (sort, gather), ``experts`` (the
SwiGLU's grouped products: the kernels' calls carry it; the compiler's own
``ragged-dot`` call carries none), ``relu2_experts`` (the ReLU²'s, the
same way) and ``moe_combine`` are what a device trace carries
(`benchmark/layer_metrics/`).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from mlops_tpu.ops.expert_products import (
    grouped_relu2_kernels,
    grouped_swiglu_kernels,
    relu2,
    wants_grouped_kernel,
)
from mlops_tpu.ops.kernel_gate import tpu_kernel_forward


class Routing(NamedTuple):
    experts: jnp.ndarray  # int32 [T, k]: the experts each token chose
    weights: jnp.ndarray  # float32 [T, k]: normalised over all k, scaled
    scores: jnp.ndarray  # float32 [T, E]: sigmoid scores, before the bias


class Plan(NamedTuple):
    order: jnp.ndarray  # int32 [T * k]: assignment ids (token * k + slot),
    # those on held experts first, by expert; the rest behind them
    offsets: jnp.ndarray  # int32 [held + 1]: where each held expert's run starts
    counts: jnp.ndarray  # int32 [held]: assignments each held expert got


@jax.named_scope("router")
def route(
    h: jnp.ndarray,
    gate: jnp.ndarray,
    bias: jnp.ndarray,
    top_k: int,
    scaling: float,
    eps: float,
) -> Routing:
    """``h`` ``[T, d]``, ``gate`` ``[d, E]``, ``bias`` ``[E]`` -> the
    choices and their weights, all in float32."""
    scores = jax.nn.sigmoid(
        jnp.dot(
            h.astype(jnp.float32),
            gate.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
    )
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = scaling * chosen / (chosen.sum(axis=-1, keepdims=True) + eps)
    return Routing(experts.astype(jnp.int32), weights, scores)


@jax.named_scope("moe_dispatch")
def plan(experts: jnp.ndarray, first_expert: int, experts_held: int) -> Plan:
    local = experts.reshape(-1) - first_expert
    held = (local >= 0) & (local < experts_held)
    key = jnp.where(held, local, experts_held)  # the absent ones sort last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    offsets = jnp.searchsorted(
        key[order], jnp.arange(experts_held + 1, dtype=key.dtype), side="left"
    ).astype(jnp.int32)
    return Plan(order, offsets, offsets[1:] - offsets[:-1])


def segment_rows(tokens: int, top_k: int, num_experts: int, experts_held: int) -> int:
    """Rows a segment holds: twice what an even router sends the held
    experts (one segment then serves nearly every call), in whole 128-row
    tiles, and no more than the worst case."""
    worst = min(top_k, experts_held) * tokens
    even = -(-tokens * top_k * experts_held // num_experts)
    return min(worst, -(-2 * even // 128) * 128)


def segment_products_xla(
    taken: jnp.ndarray,
    gate: jnp.ndarray,
    up: jnp.ndarray,
    down: jnp.ndarray,
    sizes: jnp.ndarray,
) -> jnp.ndarray:
    """A segment's three grouped products in plain XLA, the definition:
    ``taken`` ``[rows, d]`` sorted by expert, ``sizes`` ``[held]`` rows an
    expert -> float32 ``[rows, d]``; the rows past the sizes' sum are
    undefined."""
    dtype = taken.dtype
    gated = jax.lax.ragged_dot(
        taken, gate.astype(dtype), sizes, preferred_element_type=jnp.float32
    )
    lifted = jax.lax.ragged_dot(
        taken, up.astype(dtype), sizes, preferred_element_type=jnp.float32
    )
    return jax.lax.ragged_dot(
        (jax.nn.silu(gated) * lifted).astype(dtype),
        down.astype(dtype),
        sizes,
        preferred_element_type=jnp.float32,
    )


_segment_products = tpu_kernel_forward(grouped_swiglu_kernels, segment_products_xla)


def segment_products(
    taken: jnp.ndarray,
    gate: jnp.ndarray,
    up: jnp.ndarray,
    down: jnp.ndarray,
    sizes: jnp.ndarray,
) -> jnp.ndarray:
    """`segment_products_xla`'s answer. Where `wants_grouped_kernel`
    admits the shape and the computation is lowered for a TPU
    (`kernel_gate`), the forward is `ops/expert_products.py`'s two kernels
    and the backward the XLA form's; everywhere else the XLA form is
    both."""
    rows, d = taken.shape
    held, _, f = gate.shape
    dtype = taken.dtype
    if not wants_grouped_kernel(rows, held, d, f, dtype.itemsize):
        return segment_products_xla(taken, gate, up, down, sizes)
    return _segment_products(taken, gate.astype(dtype), up.astype(dtype), down.astype(dtype), sizes)


def segment_relu2_xla(
    taken: jnp.ndarray, up: jnp.ndarray, down: jnp.ndarray, sizes: jnp.ndarray
) -> jnp.ndarray:
    """A segment's two grouped products of the ReLU² experts in plain XLA,
    the definition: ``down(relu(taken up)^2)``, the square in float32 and
    rounded once; ``taken`` ``[rows, d]`` sorted by expert -> float32
    ``[rows, d]``, the rows past the sizes' sum undefined."""
    dtype = taken.dtype
    lifted = jax.lax.ragged_dot(
        taken, up.astype(dtype), sizes, preferred_element_type=jnp.float32
    )
    return jax.lax.ragged_dot(
        relu2(lifted).astype(dtype), down.astype(dtype), sizes,
        preferred_element_type=jnp.float32,
    )


_segment_relu2 = tpu_kernel_forward(grouped_relu2_kernels, segment_relu2_xla)


def segment_relu2(
    taken: jnp.ndarray, up: jnp.ndarray, down: jnp.ndarray, sizes: jnp.ndarray
) -> jnp.ndarray:
    """`segment_relu2_xla`'s answer: on a TPU, where `wants_grouped_kernel`
    admits the shape (``gated=False``), the two kernels forward and the XLA
    form backward; everywhere else the XLA form."""
    rows, d = taken.shape
    held, _, f = up.shape
    dtype = taken.dtype
    if not wants_grouped_kernel(rows, held, d, f, dtype.itemsize, gated=False):
        return segment_relu2_xla(taken, up, down, sizes)
    return _segment_relu2(taken, up.astype(dtype), down.astype(dtype), sizes)


def landed(order: jnp.ndarray) -> jnp.ndarray:
    """`Plan.order`'s inverse, int32 ``[T * k]``: the position each assignment
    id took in the sorted order (``order[landed(order)]`` counts up from 0).
    A held assignment's is under ``offsets[-1]``."""
    return jnp.argsort(order).astype(jnp.int32)


def grouped_swiglu(
    h: jnp.ndarray,
    routing: Routing,
    planned: Plan,
    gate: jnp.ndarray,
    up: jnp.ndarray,
    down: jnp.ndarray,
    rows: int,
) -> jnp.ndarray:
    """``h`` ``[T, d]`` in the products' dtype; ``gate``, ``up`` ``[held, d,
    f]``, ``down`` ``[held, f, d]`` -> float32 ``[T, d]``: the held experts'
    weighted part of every token's result (zero rows for tokens that chose
    none of them)."""
    return grouped_experts(
        h, routing, planned, rows, gate.shape[0],
        lambda taken, sizes: segment_products(taken, gate, up, down, sizes), "experts",
    )


def grouped_relu2(
    h: jnp.ndarray,
    routing: Routing,
    planned: Plan,
    up: jnp.ndarray,
    down: jnp.ndarray,
    rows: int,
) -> jnp.ndarray:
    """`grouped_swiglu` for the ReLU² experts: ``up`` ``[held, d, f]``,
    ``down`` ``[held, f, d]``, the products under ``relu2_experts``."""
    return grouped_experts(
        h, routing, planned, rows, up.shape[0],
        lambda taken, sizes: segment_relu2(taken, up, down, sizes), "relu2_experts",
    )


def grouped_experts(
    h: jnp.ndarray,
    routing: Routing,
    planned: Plan,
    rows: int,
    held: int,
    products,
    scope: str,
) -> jnp.ndarray:
    """The walk both expert forms share: ``products(taken, sizes)`` is a
    segment's grouped products (float32 ``[rows, d]``), run under
    ``scope``; ``held`` experts."""
    tokens, top_k = routing.experts.shape
    total = planned.offsets[-1]
    flat_weights = routing.weights.reshape(-1)
    segments = -(-min(top_k, held) * tokens // rows)

    def segment(start):
        """The segment from ``start`` on: which of its rows hold an
        assignment, the rows' assignment ids and tokens, and the products'
        float32 ``[rows, d]``. Rows past the last held assignment belong to
        no group: a grouped product leaves them undefined."""
        with jax.named_scope("moe_dispatch"):
            at = start + jnp.arange(rows, dtype=jnp.int32)
            live = at < total
            assignment = planned.order[jnp.minimum(at, tokens * top_k - 1)]
            token = assignment // top_k
            taken = jnp.take(h, token, axis=0)
            edges = jnp.clip(planned.offsets, start, start + rows)
            sizes = edges[1:] - edges[:-1]
        with jax.named_scope(scope):
            return live, assignment, token, products(taken, sizes)

    def gathered():
        *_, out = segment(0)
        with jax.named_scope("moe_dispatch"):
            # slots outermost: ``[k, T, d]`` is ``[k * T, d]`` viewed
            pos = landed(planned.order).reshape(tokens, top_k).T
        with jax.named_scope("moe_combine"):
            picked = out.at[jnp.minimum(pos, rows - 1)].get(mode="promise_in_bounds")
            weighted = routing.weights.T[:, :, None] * picked
            # a ``where`` and no product with 0: an absent assignment's
            # clamped position reads another's row or an undefined one
            return jnp.where((pos < total)[:, :, None], weighted, 0.0).sum(axis=0)

    def scattered(index, result):
        start = index * rows

        def run(result):
            live, assignment, token, out = segment(start)
            with jax.named_scope("moe_combine"):
                # the undefined rows are zeroed
                weighted = jnp.where(
                    live[:, None], out * flat_weights[assignment][:, None], 0.0
                )
                return result.at[token].add(weighted)

        return jax.lax.cond(start < total, run, lambda result: result, result)

    nothing = jnp.zeros(h.shape, jnp.float32)
    if segments == 1:
        return jax.lax.cond(0 < total, gathered, lambda: nothing)
    return jax.lax.fori_loop(0, segments, scattered, nothing)
