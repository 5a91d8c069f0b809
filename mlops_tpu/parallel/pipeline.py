"""Pipeline parallelism: GPipe-style microbatch streaming over a mesh axis.

Completes the framework's parallelism set (DP `bulk`/`steps`, TP
`sharding`, SP `ring_attention`, EP `models/moe.py` — the reference has
none of these, SURVEY.md SS2.7). Stage-stacked layer weights ``[S, ...]``
shard their leading axis over a 'stage' mesh axis so each device holds
one stage; microbatches stream through the ring: at every tick each
device applies its stage to the activation it received, hands the result
to the next stage with a single-hop ``ppermute`` (ICI-neighbor traffic
only), and stage ``S-1`` banks finished microbatches. ``M`` microbatches
drain in ``M + S - 1`` ticks — the classic GPipe bubble of
``(S-1)/(M+S-1)`` idle fraction, amortized by raising ``M``.

The tick loop is a ``lax.scan`` with static length, so the whole
pipeline is reverse-mode differentiable (``ppermute`` transposes to the
inverse permutation) and usable for training, not just inference.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from mlops_tpu.parallel.compat import pcast_varying, shard_map


def pipeline_stage_shard(
    stage_weights: Any,
    x: jnp.ndarray,
    *,
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    axis_name: str,
    axis_size: int,
    extra_varying: tuple[str, ...] = (),
) -> jnp.ndarray:
    """Per-device body, to be called INSIDE shard_map.

    Args:
      stage_weights: local stage slice — leading axis length 1 (this
        device's stage), e.g. ``[1, D, D]`` kernels.
      x: the full microbatch stack ``[M, B, D]`` (replicated; only stage 0
        reads it).
      stage_fn: ``(weights_for_one_stage, activation [B, D]) -> [B, D]``.
      axis_name: the 'stage' mesh axis.
      axis_size: number of stages S (static).

    Returns the completed ``[M, B, D]`` outputs (identical on every device
    after the closing psum).
    """
    s = jax.lax.axis_index(axis_name)
    num_micro = x.shape[0]
    local = jax.tree_util.tree_map(lambda w: w[0], stage_weights)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    varying_axes = (axis_name, *extra_varying)

    def tick(carry, t):
        recv, out = carry
        # Stage 0 ingests microbatch t; later stages consume what the
        # previous stage handed them last tick.
        ingest = jax.lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, num_micro - 1), 0, keepdims=False
        )
        h = jnp.where(s == 0, ingest, recv)
        y = stage_fn(local, h)
        # Stage S-1 banks microbatch m = t - (S-1) once it exists.
        m = t - (axis_size - 1)
        banked = jax.lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(m, 0, num_micro - 1), 0
        )
        is_last = s == axis_size - 1
        valid = jnp.logical_and(is_last, jnp.logical_and(m >= 0, m < num_micro))
        out = jnp.where(valid, banked, out)
        # One-hop hand-off to the next stage (ICI-neighbor ppermute).
        recv = jax.lax.ppermute(y, axis_name, perm)
        return (recv, out), None

    # The carry varies per device from the first tick (each stage computes
    # its own activations), so the zero initials must be typed as varying
    # over the stage axis — and over the batch axis too when the
    # microbatches arrive DP-sharded (extra_varying) — for shard_map's
    # scan typing.
    recv0 = pcast_varying(jnp.zeros(x.shape[1:], x.dtype), varying_axes)
    # zeros_like(x) already inherits x's varying axes (the batch axis when
    # DP-sharded), so out0 only needs the stage axis added.
    out0 = pcast_varying(jnp.zeros_like(x), (axis_name,))
    (recv, out), _ = jax.lax.scan(
        tick, (recv0, out0), jnp.arange(num_micro + axis_size - 1)
    )
    # Only stage S-1 holds the results; psum broadcasts them to the ring
    # (every other contribution is zero).
    return jax.lax.psum(out, axis_name)


def make_pipeline(
    mesh: Mesh,
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    axis_name: str = "stage",
    batch_axis: str | None = None,
) -> Callable[[Any, jnp.ndarray], jnp.ndarray]:
    """Build ``run(stage_weights, x) -> y`` executing ``stage_fn`` as an
    S-deep pipeline over ``mesh[axis_name]``.

    ``stage_weights`` is any pytree whose leaves carry a leading stage
    axis of size S (sharded across devices); ``x`` is ``[M, B, D]``
    microbatches. ``batch_axis`` composes DP x PP: the microbatch B dim
    shards over that mesh axis and the stage ring runs independently per
    batch shard (all communication stays on the 'stage' axis).
    Equivalent to folding ``stage_fn`` sequentially over the stage axis —
    validated exactly in ``tests/test_pipeline_parallel.py``.
    """
    axis_size = mesh.shape[axis_name]
    body = partial(
        pipeline_stage_shard,
        stage_fn=stage_fn,
        axis_name=axis_name,
        axis_size=axis_size,
        extra_varying=(batch_axis,) if batch_axis else (),
    )
    x_spec = P(None, batch_axis) if batch_axis else P()
    # Not compile-cached: this is a GPipe TRAINING-layout building block
    # (one compile per training run, amortized over thousands of steps),
    # not a per-process serving entry point; the cached train entries are
    # train-step-dense and train-step-tp (compilecache/registry.py).
    return jax.jit(  # tpulint: disable=TPU203
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis_name), x_spec),
            out_specs=x_spec,
        )
    )
