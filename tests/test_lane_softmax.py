"""`ops/lane_softmax.py`, the joint softmax of one visit of the attention
kernels, on plain arrays: key sets side by side in one softmax are the
softmax over their concatenation, and two visits merged through the
carried maximum are one visit over both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlops_tpu.ops.lane_softmax import (
    LANES,
    joint_softmax,
    joint_softmax_state,
    over_lane_tiles,
)


def _visit(widths, seed=0, rows=8, width=16):
    rng = np.random.default_rng(seed)
    keys = sum(widths)
    scores = jnp.asarray(rng.normal(scale=4.0, size=(rows, keys)), jnp.float32)
    values = jnp.asarray(rng.normal(size=(keys, width)), jnp.float32)
    starts = np.cumsum([0, *widths[:-1]])
    parts = [(int(a), scores[:, a : a + w]) for a, w in zip(starts, widths)]
    return parts, scores, values


def _reference(scores, values):
    return jax.nn.softmax(scores, axis=-1) @ values


def test_over_lane_tiles_folds_the_lane_tiles_elementwise():
    x = jnp.arange(4 * 3 * LANES, dtype=jnp.float32).reshape(4, 3 * LANES)
    np.testing.assert_array_equal(
        over_lane_tiles(x, jnp.add), x[:, :LANES] + x[:, LANES : 2 * LANES] + x[:, 2 * LANES :]
    )
    np.testing.assert_array_equal(over_lane_tiles(x, jnp.maximum), x[:, 2 * LANES :])


@pytest.mark.parametrize("widths", [(LANES,), (2 * LANES, LANES), (LANES, LANES, 3 * LANES)])
def test_key_sets_side_by_side_are_one_softmax(widths):
    parts, scores, values = _visit(widths)
    read = lambda at, size: values[at : at + size]
    np.testing.assert_allclose(
        joint_softmax(parts, read), _reference(scores, values), rtol=1e-5, atol=1e-6
    )
    carried, top, lane_sums, mixed = joint_softmax_state(parts, read)
    assert carried is None and lane_sums.shape == (8, LANES)
    np.testing.assert_allclose(top[:, 0], scores.max(axis=-1), rtol=0)
    np.testing.assert_allclose(
        mixed / lane_sums.sum(axis=-1, keepdims=True),
        _reference(scores, values),
        rtol=1e-5,
        atol=1e-6,
    )


def test_two_visits_merged_through_the_carried_maximum_are_one():
    """As `_eva_kernel` merges its local keys and its summaries."""
    (first, second), scores, values = _visit((2 * LANES, LANES), seed=3)
    read = lambda at, size: values[at : at + size]
    _, top, lanes, mixed = joint_softmax_state([first], read)
    carried, top_two, lanes_two, mixed_two = joint_softmax_state(
        [second], read, carried=lambda: top
    )
    np.testing.assert_array_equal(carried, top)
    alpha = jnp.exp(carried - top_two)
    lanes = lanes * alpha + lanes_two
    mixed = mixed * alpha + mixed_two
    np.testing.assert_allclose(
        mixed / lanes.sum(axis=-1, keepdims=True), _reference(scores, values), rtol=1e-5, atol=1e-6
    )
