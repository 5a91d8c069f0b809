"""`ops/gqa_attention.py`: the blockwise kernel (interpret mode, passed
here and nowhere else) against `ops/causal_attention.py causal_attend`,
the gradient through the ``custom_vjp``, and which shapes take which
form. Compiling the kernel for the chip is `tests/test_tpu_compile.py`'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlops_tpu.ops.causal_attention import causal_attend
from mlops_tpu.ops.gqa_attention import (
    MAX_KEYS,
    STEP_ROWS,
    WINDOW_BLOCK,
    gqa_attend,
    gqa_attend_blockwise,
    wants_gqa_kernel,
)


def _operands(seq, heads, kv_heads, width, seed=0, batch=1):
    rng = np.random.default_rng(seed)
    shapes = ((batch, seq, heads, width),) + ((batch, seq, kv_heads, width),) * 2
    return tuple(jnp.asarray(rng.normal(size=s), jnp.float32) for s in shapes)


# a full layer's block is `STEP_ROWS` rows of a lane tile of heads (512
# queries at a width of 128, 256 at 64), a window layer's `WINDOW_BLOCK`; these
# short histories' steps stack several lane tiles of a group's heads
@pytest.mark.parametrize("ratio", [1, 4, 8])
@pytest.mark.parametrize("width", [128, 64])
@pytest.mark.parametrize(
    "blocks,window",
    [
        (1, None),  # a history of one block: the own block alone, under the causal mask
        (3, None),  # of several: every key before the own block, unmasked
        (3, WINDOW_BLOCK),  # the window is the block: the block before it and the own
        (3, WINDOW_BLOCK // 2),  # half a block: the band's mask cuts the own block too
        (4, 2 * WINDOW_BLOCK),  # two blocks: three in a tile, the first place a tile of one
    ],
)
def test_kernel_answers_as_causal_attend(ratio, width, blocks, window):
    block = WINDOW_BLOCK if window else STEP_ROWS * width // 128
    kv_heads = 2  # a width of 64 takes key/value heads in pairs
    q, k, v = _operands(blocks * block, ratio * kv_heads, kv_heads, width, seed=ratio)
    expected = causal_attend(q, k, v, width**-0.5, window=window)
    out = gqa_attend_blockwise(q, k, v, width**-0.5, window=window, interpret=True)
    assert out.shape == expected.shape and out.dtype == expected.dtype
    np.testing.assert_allclose(out, expected, atol=2e-6, rtol=1e-5)


def test_kernel_rounds_the_weights_once_as_causal_attend_does():
    """bfloat16 operands: the products run on them and the weights are
    rounded to bfloat16 once; the answers agree to a bfloat16 step."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _operands(512, 8, 2, 128, batch=2))
    for window in (None, 128):
        expected = causal_attend(q, k, v, 128**-0.5, window=window)
        out = gqa_attend_blockwise(q, k, v, 128**-0.5, window=window, interpret=True)
        assert out.dtype == jnp.bfloat16
        gap = jnp.abs(out.astype(jnp.float32) - expected.astype(jnp.float32))
        assert float(gap.max()) <= 2**-6 and float(gap.mean()) < 1e-4


@pytest.mark.parametrize("window", [None, 128])
def test_gradients_are_the_xla_forms(window):
    """`gqa_attend` at a shape the kernel takes is a ``custom_vjp``: its
    backward differentiates `causal_attend`, recomputed."""
    q, k, v = _operands(512, 4, 2, 128)
    assert wants_gqa_kernel(512, 4, 2, 128, window)
    weight = jnp.asarray(np.random.default_rng(1).normal(size=q.shape), jnp.float32)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v, 0.1, window=window) * weight)

    assert "custom_vjp" in str(jax.make_jaxpr(loss(gqa_attend))(q, k, v))
    got = jax.grad(loss(gqa_attend), argnums=(0, 1, 2))(q, k, v)
    expected = jax.grad(loss(causal_attend), argnums=(0, 1, 2))(q, k, v)
    for g, e in zip(got, expected):
        np.testing.assert_allclose(g, e, atol=1e-6, rtol=1e-6)


def _traced(*operands, **kwargs) -> str:
    return str(jax.make_jaxpr(lambda q, k, v: gqa_attend(q, k, v, 0.1, **kwargs))(*operands))


def test_gqa_attend_on_this_platform_is_causal_attend_bit_for_bit():
    """Not lowered for a TPU, the admitted shape runs the XLA form, steered
    by ``query_block`` as before; the kernel is the other branch."""
    q, k, v = _operands(512, 4, 2, 128)
    assert "pallas_call" in _traced(q, k, v) and "pallas_call" in _traced(q, k, v, window=128)
    for window, query_block in ((None, 128), (128, 512)):
        out = gqa_attend(q, k, v, 0.1, window=window, query_block=query_block)
        expected = causal_attend(q, k, v, 0.1, window=window, query_block=query_block)
        assert jnp.array_equal(out, expected)


def test_read_takes_the_xla_form():
    read = np.array([47, 511])
    q, k, v = _operands(512, 4, 2, 128)
    for window in (None, 128):
        text = _traced(q[:, read], k, v, read=read, window=window)
        assert "pallas_call" not in text and "custom_vjp" not in text
        out = gqa_attend(q[:, read], k, v, 0.1, read=read, window=window)
        assert jnp.array_equal(
            out, causal_attend(q[:, read], k, v, 0.1, read=read, window=window)
        )


@pytest.mark.parametrize(
    "seq,heads,kv_heads,width,window,taken",
    [
        (3072, 64, 8, 128, None, True),  # k-exaone-236b-a23b's full layers
        (3072, 64, 8, 128, 128, True),  # and its window layers
        (3072, 32, 8, 64, None, True),  # lfm2-8b-a1b: a pair of key/value heads a tile
        (512, 4, 4, 128, None, True),  # one block, as many key/value heads as query heads
        (4096, 64, 8, 128, None, True),  # the most float32 scores a step may hold in VMEM
        (MAX_KEYS, 64, 8, 128, 128, True),  # a window's visit does not grow with the history
        (3072 - 48, 64, 8, 128, None, False),  # a ragged history: no whole blocks
        (3072 - 48, 64, 8, 128, 128, False),
        (3072 + 512, 64, 8, 128, None, True),
        (3072 + 256, 64, 8, 128, None, False),  # no whole blocks of 512
        (3072 + 256, 32, 8, 64, None, False),  # heads of 64: blocks of 256, twice the places,
        (4096, 32, 8, 64, None, False),  # and past 3,072 keys more code than stays resident
        (384, 8, 2, 128, 384, False),  # a window no shorter than the history is none:
        (384, 8, 2, 128, 4096, False),  # the full form's blocks of 512 do not divide 384
        (512, 8, 2, 128, 4096, True),
        (3072, 64, 8, 32, None, False),  # a narrow head: four a lane tile
        (3072, 24, 8, 96, None, False),
        (3072, 32, 8, 256, None, False),  # a head of two lane tiles
        (3072, 12, 3, 64, None, False),  # no pairs of key/value heads
        (3072, 12, 3, 128, None, True),
        (3072, 12, 8, 128, None, False),  # query heads that no group divides
        (8192, 64, 8, 128, None, False),  # one visit's float32 scores: past VMEM
        (8192, 64, 8, 128, 4481, True),  # 36 places of a window's tile: the most code
        (8192, 64, 8, 128, 4609, False),  # 37
        (2 * MAX_KEYS, 64, 8, 128, 128, False),  # a tile's keys and values: past VMEM
    ],
)
def test_which_shapes_take_the_kernel(seq, heads, kv_heads, width, window, taken):
    assert wants_gqa_kernel(seq, heads, kv_heads, width, window) is taken
    if not taken and heads % kv_heads == 0:
        operands = [
            jax.ShapeDtypeStruct((1, seq, h, width), jnp.bfloat16)
            for h in (heads, kv_heads, kv_heads)
        ]
        assert "pallas_call" not in _traced(*operands, window=window)
        with pytest.raises(ValueError, match="no tiling"):
            jax.eval_shape(
                lambda *xs: gqa_attend_blockwise(*xs, 0.1, window=window), *operands
            )
