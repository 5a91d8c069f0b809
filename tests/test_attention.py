"""Flash-attention kernel numerics vs the dense XLA reference.

These tests choose interpret mode themselves (``interpret=True``), so
they exercise the exact kernel code paths (tiling, online softmax,
padding mask) without a TPU. The compiled kernels are asked of the TPU
compiler in tests/test_tpu_compile.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlops_tpu.ops import attention
from mlops_tpu.ops.attention import attend, reference_attention

flash_attention = functools.partial(attention.flash_attention, interpret=True)


def _qkv(b, s, h, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize(
    "b,s,h,d",
    [
        (2, 128, 4, 32),  # exact block multiple
        (1, 200, 2, 16),  # ragged: seq padded inside the kernel
        (2, 24, 2, 8),  # FT-Transformer shape, below one block
    ],
)
def test_flash_matches_reference(b, s, h, d):
    q, k, v = _qkv(b, s, h, d)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_bf16_close_to_f32_reference():
    q, k, v = _qkv(2, 128, 4, 32, dtype=jnp.bfloat16, seed=1)
    out = flash_attention(q, k, v)
    ref = reference_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2
    )


def test_flash_gradients_match_reference():
    q, k, v = _qkv(1, 96, 2, 16, seed=2)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, block_q=32, block_k=32).sum()

    def loss_ref(q, k, v):
        return reference_attention(q, k, v).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=2e-5)


def test_flash_gradients_match_reference_ragged_and_cross():
    """The Pallas backward under padding: a seq that is NOT a block
    multiple (mask path in all three kernels) and distinct q/kv lengths
    (cross-attention) must still match dense gradients."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 45, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 70, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 70, 2, 16)).astype(np.float32))

    def loss_flash(q, k, v):
        # A non-uniform cotangent (sum of squares) exercises delta != 1.
        return (flash_attention(q, k, v, block_q=32, block_k=32) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=1e-4
        )


def test_flash_backward_is_pallas_not_dense_remat():
    """The VJP must lower to Pallas kernels: the backward
    jaxpr carries the dq and dkv pallas_calls and — unlike the round-4
    dense-remat VJP — no [S, S] softmax materialization."""
    q, k, v = _qkv(1, 64, 2, 16, seed=8)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda q: flash_attention(q, k, v, block_q=32, block_k=32).sum())
    )(q)
    text = str(jaxpr)
    # forward + dq + dkv kernels
    assert text.count("pallas_call") >= 3, text.count("pallas_call")
    assert "softmax" not in text


def test_flash_bf16_gradients_finite_and_close():
    q, k, v = _qkv(2, 128, 4, 32, dtype=jnp.bfloat16, seed=9)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(
        lambda q, k, v: reference_attention(q, k, v).sum(), argnums=(0, 1, 2)
    )(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    for a, b in zip(g, gr):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), atol=6e-2
        )


def test_flash_under_jit_and_vmap():
    q, k, v = _qkv(2, 64, 2, 16, seed=3)
    jitted = jax.jit(lambda q, k, v: flash_attention(q, k, v, block_q=32, block_k=32))
    np.testing.assert_allclose(
        np.asarray(jitted(q, k, v)),
        np.asarray(reference_attention(q, k, v)),
        atol=2e-5,
    )


def test_attend_auto_dispatch_is_decided_at_lowering():
    """The None-dispatch carries BOTH paths to lowering
    (`ops/kernel_gate.py`): lowered for the CPU it is XLA dense — no
    Mosaic call, and a result — while the traced program still holds the
    kernel for a TPU lowering. Nothing interprets by itself:
    ``use_flash=True`` off-TPU is the compiled kernel, which the CPU
    refuses."""
    q, k, v = _qkv(1, 256, 2, 16, seed=10)
    auto = jax.jit(lambda q: attend(q, k, v))
    assert "pallas_call" in str(jax.make_jaxpr(auto)(q))
    assert "tpu_custom_call" not in auto.lower(q).as_text()
    np.testing.assert_allclose(
        np.asarray(auto(q)), np.asarray(reference_attention(q, k, v)), atol=2e-5
    )
    with pytest.raises(Exception, match="(?i)interpret|cpu|platform"):
        jax.jit(lambda q: attend(q, k, v, use_flash=True))(q)


def test_attend_dispatch():
    # Short sequence routes to the dense path; long sequences lowered for
    # the CPU are dense too, and the kernel itself is pinned against dense
    # in interpret mode.
    q, k, v = _qkv(1, 24, 2, 8, seed=4)
    assert "pallas_call" not in str(jax.make_jaxpr(lambda q: attend(q, k, v))(q))
    np.testing.assert_allclose(
        np.asarray(attend(q, k, v)),
        np.asarray(reference_attention(q, k, v)),
        atol=2e-5,
    )
    q, k, v = _qkv(1, 160, 2, 8, seed=5)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v)),
        np.asarray(reference_attention(q, k, v)),
        atol=2e-5,
    )
