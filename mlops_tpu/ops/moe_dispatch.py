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
  over the held experts as grouped (ragged) matrix products,
  `jax.lax.ragged_dot`, over exactly the assignments that fell here. The
  sorted assignments are walked in SEGMENTS of a fixed number of rows
  (shapes stay static): a segment gathers its tokens' rows, runs the three
  products with its own slice of the group sizes, and adds its weighted
  rows into the result; a segment past the last held assignment is
  skipped (`lax.cond`), so the work follows the load. The worst case
  (every token choosing only held experts) is ``min(k, held) * tokens``
  rows and is walked in full: routing skew costs time, never an answer.

The scopes ``router``, ``moe_dispatch`` (sort, gather), ``experts`` (the
three grouped products) and ``moe_combine`` are what a device trace
carries (`benchmark/layer_metrics/`).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


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
    tokens, top_k = routing.experts.shape
    total = planned.offsets[-1]
    flat_weights = routing.weights.reshape(-1)
    dtype = h.dtype
    segments = -(-min(top_k, gate.shape[0]) * tokens // rows)

    def segment(index, result):
        start = index * rows

        def run(result):
            with jax.named_scope("moe_dispatch"):
                at = start + jnp.arange(rows, dtype=jnp.int32)
                live = at < total
                assignment = planned.order[jnp.minimum(at, tokens * top_k - 1)]
                token = assignment // top_k
                taken = jnp.take(h, token, axis=0)
                edges = jnp.clip(planned.offsets, start, start + rows)
                sizes = edges[1:] - edges[:-1]
            with jax.named_scope("experts"):
                gated = jax.lax.ragged_dot(
                    taken, gate.astype(dtype), sizes, preferred_element_type=jnp.float32
                )
                lifted = jax.lax.ragged_dot(
                    taken, up.astype(dtype), sizes, preferred_element_type=jnp.float32
                )
                out = jax.lax.ragged_dot(
                    (jax.nn.silu(gated) * lifted).astype(dtype),
                    down.astype(dtype),
                    sizes,
                    preferred_element_type=jnp.float32,
                )
            with jax.named_scope("moe_combine"):
                # rows past the last held assignment belong to no group: a
                # grouped product leaves them undefined, so they are zeroed
                weighted = jnp.where(
                    live[:, None], out * flat_weights[assignment][:, None], 0.0
                )
                return result.at[token].add(weighted)

        return jax.lax.cond(start < total, run, lambda result: result, result)

    result = jnp.zeros(h.shape, jnp.float32)
    if segments == 1:
        return segment(0, result)
    return jax.lax.fori_loop(0, segments, segment, result)
