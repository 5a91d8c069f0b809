"""The routed experts' grouped products as Pallas kernels (ISSUE 34,
`ops/expert_products.py`) against the XLA form they stand in for
(`ops/moe_dispatch.py segment_products_xla`), in interpret mode on the CPU:
the walk over (expert, row tile) pairs, the rows a visit may write, which
shapes take the kernels, and the backward. The chip's compiler is asked in
`tests/test_tpu_compile.py`; times are the chip's alone (PERF.md)."""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlops_tpu.ops import moe_dispatch
from mlops_tpu.ops.expert_products import (
    Tiles,
    grouped_swiglu_kernels,
    grouped_tiles,
    plan_walk,
    wants_grouped_kernel,
)

CONFIGS = Path(__file__).resolve().parents[1] / "benchmark/configs"


def operands(rows, d, f, sizes, dtype, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape) / math.sqrt(shape[-2]), dtype)  # noqa: E731
    held = len(sizes)
    return (
        jnp.asarray(rng.normal(size=(rows, d)), dtype),
        draw(held, d, f), draw(held, d, f), draw(held, f, d),
        jnp.asarray(sizes, jnp.int32),
    )


@pytest.mark.parametrize("dtype,atol", [("float32", 2e-5), ("bfloat16", 2.0**-6)])
@pytest.mark.parametrize(
    "rows,d,f,sizes,tiles",
    [
        (512, 128, 256, [128, 128, 128, 128], None),
        (512, 128, 256, [200, 0, 184, 128], None),  # an EMPTY expert
        (640, 128, 256, [40, 330, 90, 180], None),  # one inside a tile, one that spans three
        (512, 128, 256, [37, 100, 290, 85], Tiles(128, 128, 128)),  # every edge off a tile's; two column tiles
        (512, 128, 256, [100, 0, 0, 61], None),  # a clipped segment: 161 rows of 512 are anyone's
        (512, 128, 256, [0, 0, 0, 5], None),
        (256, 128, 1792, [1, 2, 3, 200], None),  # f of 14 lane tiles
        (2048, 128, 256, [700, 1348], None),  # 1,024 rows an expert: the long row tile
        (2048, 128, 256, [515, 1021], Tiles(512, 128, 128)),  # a longer tile than the rule's
    ],
    ids=["even", "empty-expert", "small-and-spanning", "off-every-edge", "clipped", "nearly-empty",
         "f-1792", "long-tile", "long-tile-clipped"],
)
def test_the_kernels_match_the_xla_form(rows, d, f, sizes, tiles, dtype, atol):
    """Every row that belongs to an expert, to the order of a sum in
    float32 and to a rounding of the hidden activation in bfloat16; the
    rows past the sizes' sum are undefined in both forms and not compared."""
    args = operands(rows, d, f, sizes, jnp.dtype(dtype), seed=len(sizes) + rows)
    with jax.default_matmul_precision("highest"):
        expected = moe_dispatch.segment_products_xla(*args)
        out = grouped_swiglu_kernels(*args, tiles=tiles, interpret=True)
    assert out.shape == expected.shape == (rows, d) and out.dtype == expected.dtype == jnp.float32
    live = sum(sizes)
    assert float(jnp.abs(expected[:live]).max()) > 0.1
    np.testing.assert_allclose(np.asarray(out[:live]), np.asarray(expected[:live]), atol=atol)


def test_the_walk_visits_each_experts_tiles_once_in_order():
    """Sizes 37, 0, 290, 85 over tiles of 128: expert 0 tile 0; expert 2
    tiles 0-2 (rows 37..326); expert 3 tiles 2-3 (rows 327..411); six real
    visits of the 4 + 4 - 1 steps, and the dead steps repeat the last."""
    walk = plan_walk(jnp.asarray([37, 0, 290, 85], jnp.int32), 512, 128)
    assert walk.offsets.tolist() == [0, 37, 37, 327, 412]
    assert walk.live.tolist() == [6]
    assert walk.expert.tolist() == [0, 2, 2, 2, 3, 3, 3]
    assert walk.tile.tolist() == [0, 0, 1, 2, 2, 3, 3]
    none = plan_walk(jnp.zeros(4, jnp.int32), 512, 128)  # nothing to visit: every step is skipped
    assert none.live.tolist() == [0] and max(none.tile.tolist()) < 4


def test_which_shapes_take_the_grouped_kernels():
    """Both cells' published shapes do (the segment's rows are what
    `segment_rows` gives a chunk run), with tiles from the rows an expert
    gets; widths that are no lane tiles and rows that are no row tiles do
    not, and take the XLA form with no `custom_vjp` in the trace."""
    lfm2 = json.loads((CONFIGS / "lfm2-8b-a1b.json").read_text())
    mc, tokens = lfm2["model_config"], 4 * 3072
    rows = moe_dispatch.segment_rows(tokens, mc["experts_per_token"], mc["num_experts"], mc["num_experts"])
    shape = (rows, mc["num_experts"], mc["token_dim"], mc["moe_ffn_dim"])
    assert shape == (49152, 32, 2048, 1792) and wants_grouped_kernel(*shape)
    assert grouped_tiles(*shape) == Tiles(256, 1792, 2048)
    kimi = json.loads((CONFIGS / "kimi-k2-5l.json").read_text())
    mc, tokens = kimi["model_config"], 2 * 3072
    rows = moe_dispatch.segment_rows(tokens, mc["experts_per_token"], mc["num_experts"], mc["experts_held"])
    shape = (rows, mc["experts_held"], mc["token_dim"], mc["moe_ffn_dim"])
    assert shape == (6144, 24, 7168, 2048) and wants_grouped_kernel(*shape)
    assert grouped_tiles(*shape) == Tiles(128, 1024, 7168)
    assert not wants_grouped_kernel(600, 4, 32, 24)  # the tiny configurations of the tests
    assert not wants_grouped_kernel(512, 4, 128, 96)  # f is no whole lane tile
    assert not wants_grouped_kernel(600, 4, 128, 256)  # rows are no whole row tiles
    assert not wants_grouped_kernel(512, 4, 2**20, 128)  # a contraction past VMEM
    refused = operands(512, 128, 96, [128] * 4, jnp.float32)
    with pytest.raises(ValueError, match="no tiling"):
        grouped_swiglu_kernels(*refused, interpret=True)
    traced = str(jax.make_jaxpr(moe_dispatch.segment_products)(*refused))
    assert "custom_vjp" not in traced and "pallas_call" not in traced
    admitted = operands(512, 128, 256, [128] * 4, jnp.float32)
    assert "custom_vjp" in str(jax.make_jaxpr(moe_dispatch.segment_products)(*admitted))


def test_the_backward_of_the_kernels_shape_is_the_xla_forms():
    """At a shape the kernels take, `segment_products` is a `custom_vjp`
    whose backward differentiates the XLA form: the same gradients as
    autodiff of the XLA form itself (on the CPU the forward is that form
    too), and none for the sizes."""
    *arrays, sizes = operands(512, 128, 256, [100, 0, 300, 112], jnp.float32, seed=3)
    weights = jnp.asarray(np.random.default_rng(4).normal(size=(512, 128)), jnp.float32)

    def loss(products):
        return lambda *xs: (products(*xs, sizes) * weights).sum()

    got = jax.grad(loss(moe_dispatch.segment_products), argnums=(0, 1, 2, 3))(*arrays)
    expected = jax.grad(loss(moe_dispatch.segment_products_xla), argnums=(0, 1, 2, 3))(*arrays)
    for g, e in zip(got, expected):
        assert np.abs(np.asarray(e)).max() > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-6, atol=1e-7)
