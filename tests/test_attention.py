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


# --------------------------------------------------------------------------
# The module's one dense path (`dense_attention` over the fused projection's
# [B, S, 3*H*D] layout) against plain float32 softmax attention written out
# here, and the module's contract with what surrounds it.
# --------------------------------------------------------------------------

from flax import linen as nn  # noqa: E402

from mlops_tpu.models import layers  # noqa: E402
from mlops_tpu.models.layers import MultiHeadSelfAttention  # noqa: E402
from mlops_tpu.ops.attention import dense_attention  # noqa: E402

_B, _S, _H, _D = 3, 10, 4, 8
_DIM = _H * _D


def _plain_heads(q, k, v, mask=None):
    """Softmax attention by its formula: [B,S,H,D] float32 in and out."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if mask is not None:
        s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    s = s - s.max(axis=-1, keepdims=True)
    p = jnp.exp(s) / jnp.exp(s).sum(axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _plain_module(params, x, mask=None):
    """The whole module by its formula, from the parameter tree's shapes."""
    qkv = jnp.einsum("bsd,dchf->cbshf", x, params["qkv"]["kernel"])
    q, k, v = qkv + params["qkv"]["bias"][:, None, None]
    o = _plain_heads(q, k, v, mask)
    return (
        jnp.einsum("bshf,hfe->bse", o, params["out"]["kernel"])
        + params["out"]["bias"]
    )


class _ParentModule(nn.Module):
    """The module as it stood before PR 26 (two `nn.DenseGeneral`s around
    [B,S,H,D] einsums): what wrote every saved bundle's parameter tree."""

    heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        n, s, dim = x.shape
        d = dim // self.heads
        qkv = nn.DenseGeneral((3, self.heads, d), dtype=self.dtype, name="qkv")(
            x.reshape(n * s, dim)
        ).reshape(n, s, 3, self.heads, d)
        out = reference_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
        return nn.DenseGeneral(
            dim, axis=(-2, -1), dtype=self.dtype, name="out"
        )(out.reshape(n * s, self.heads, d)).reshape(n, s, dim)


def _fused(q, k, v):
    """[B,S,H,D] x 3 -> the fused projection's [B, S, 3*H*D]."""
    b, s = q.shape[:2]
    return jnp.concatenate([t.reshape(b, s, -1) for t in (q, k, v)], axis=-1)


def _inputs(seed=11):
    x = jax.random.normal(jax.random.PRNGKey(seed), (_B, _S, _DIM), jnp.float32)
    mask = jnp.arange(_S)[None, :] < jnp.array([_S, 7, 1])[:, None]
    return x, mask


def _module(**kw):
    module = MultiHeadSelfAttention(heads=_H, dtype=jnp.float32, **kw)
    x, _ = _inputs()
    params = module.init(jax.random.PRNGKey(0), x)["params"]
    # biases start at zero: give them values, or a misplaced one passes
    bias = jax.random.normal(jax.random.PRNGKey(5), (3, _H, _D))
    params["qkv"]["bias"] = bias
    params["out"]["bias"] = jnp.linspace(-1.0, 1.0, _DIM)
    return module, params


def _case_forward_plain():
    q, k, v = _qkv(_B, _S, _H, _D, seed=12)
    out = dense_attention(_fused(q, k, v), _H)
    assert out.shape == (_B, _S, _DIM)
    np.testing.assert_allclose(
        np.asarray(out.reshape(_B, _S, _H, _D)),
        np.asarray(_plain_heads(q, k, v)),
        atol=2e-6,
    )


def _case_forward_masked():
    q, k, v = _qkv(_B, _S, _H, _D, seed=13)
    _, mask = _inputs()
    out = dense_attention(_fused(q, k, v), _H, mask=mask)
    np.testing.assert_allclose(
        np.asarray(out.reshape(_B, _S, _H, _D)),
        np.asarray(_plain_heads(q, k, v, mask)),
        atol=2e-6,
    )
    # a padded key moves nothing: its values may be anything
    v2 = jnp.where(mask[:, :, None, None], v, 1e3)
    np.testing.assert_allclose(
        np.asarray(dense_attention(_fused(q, k, v2), _H, mask=mask)),
        np.asarray(out),
        atol=2e-6,
    )


def _case_forward_bf16():
    """At the compute dtype: bf16 operands into both products, f32 softmax."""
    q, k, v = _qkv(_B, _S, _H, _D, dtype=jnp.bfloat16, seed=14)
    out = dense_attention(_fused(q, k, v), _H)
    assert out.dtype == jnp.bfloat16
    want = _plain_heads(*(t.astype(jnp.float32) for t in (q, k, v)))
    np.testing.assert_allclose(
        np.asarray(out, np.float32).reshape(_B, _S, _H, _D),
        np.asarray(want),
        atol=3e-2,
    )


def _case_grads_qkv():
    q, k, v = _qkv(_B, _S, _H, _D, seed=15)
    _, mask = _inputs()
    weight = jax.random.normal(jax.random.PRNGKey(16), (_B, _S, _H, _D))

    def got(q, k, v):
        out = dense_attention(_fused(q, k, v), _H, mask=mask)
        return (out.reshape(_B, _S, _H, _D) * weight).sum()

    def want(q, k, v):
        return (_plain_heads(q, k, v, mask) * weight).sum()

    for a, b in zip(
        jax.grad(got, argnums=(0, 1, 2))(q, k, v),
        jax.grad(want, argnums=(0, 1, 2))(q, k, v),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _case_grads_kernels():
    module, params = _module()
    x, mask = _inputs()

    def got(p):
        return (module.apply({"params": p}, x, mask=mask) ** 2).sum()

    def want(p):
        return (_plain_module(p, x, mask) ** 2).sum()

    g, w = jax.grad(got)(params), jax.grad(want)(params)
    for name in ("qkv", "out"):
        for leaf in ("kernel", "bias"):
            np.testing.assert_allclose(
                np.asarray(g[name][leaf]),
                np.asarray(w[name][leaf]),
                atol=2e-4,
                rtol=1e-4,
            )


def _case_module_forward():
    module, params = _module()
    x, mask = _inputs()
    for m in (None, mask):
        np.testing.assert_allclose(
            np.asarray(module.apply({"params": params}, x, mask=m)),
            np.asarray(_plain_module(params, x, m)),
            atol=1e-5,
        )


def _case_weight_dropout():
    """Training with weight dropout is the same dense path: rate 0 of it
    is the plain output, a real rate drops whole probabilities (so rows no
    longer sum to one) and needs the 'dropout' stream."""
    module, params = _module(dropout=0.5)
    x, _ = _inputs()
    plain = _plain_module(params, x)
    kept = module.apply({"params": params}, x, deterministic=True)
    np.testing.assert_allclose(np.asarray(kept), np.asarray(plain), atol=1e-5)
    rngs = {"dropout": jax.random.PRNGKey(3)}
    a = module.apply({"params": params}, x, deterministic=False, rngs=rngs)
    b = module.apply({"params": params}, x, deterministic=False, rngs=rngs)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.abs(np.asarray(a) - np.asarray(plain)).max() > 1e-2
    # every head draws its own mask: with value rows of ones, out = p.sum()
    ones = jnp.ones((1, _S, _H, _D))
    sums = dense_attention(
        _fused(jnp.zeros_like(ones), jnp.zeros_like(ones), ones),
        _H,
        dropout_rate=0.5,
        dropout_rng=jax.random.PRNGKey(3),
    ).reshape(_S, _H, _D)[:, :, 0]
    assert len(np.unique(np.asarray(sums).round(4), axis=1)[0]) > 1
    # and what is kept is scaled by 1 / (1 - rate): the mean is kept
    assert abs(float(sums.mean()) - 1.0) < 0.2


def _case_attend_fn_boundary():
    """The injected ring is handed, and hands back, [B,S,H,D]."""
    seen = []

    def ring(q, k, v):
        seen.append((q.shape, k.shape, v.shape))
        return reference_attention(q, k, v)

    module, params = _module(attend_fn=ring)
    x, _ = _inputs()
    seen.clear()  # `init` ran it once
    out = module.apply({"params": params}, x)
    assert seen == [((_B, _S, _H, _D),) * 3]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_plain_module(params, x)), atol=1e-5
    )


def _case_flash_boundary():
    """A sequence of FLASH_MIN_SEQ and longer goes to the dispatcher with
    [B,S,H,D] arguments; a shorter one, a mask or weight dropout do not."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        _flash_boundary(monkeypatch)


def _flash_boundary(monkeypatch):
    seen = []

    def spy(q, k, v, use_flash=None):
        seen.append((q.shape, k.shape, v.shape, use_flash))
        return reference_attention(q, k, v)

    monkeypatch.setattr(layers, "attend", spy)
    module = MultiHeadSelfAttention(heads=2, dtype=jnp.float32)
    long = jnp.ones((1, attention.FLASH_MIN_SEQ, 16))
    params = module.init(jax.random.PRNGKey(0), long)
    seen.clear()  # `init` ran it once
    want = (1, attention.FLASH_MIN_SEQ, 2, 8)
    out = module.apply(params, long)
    assert seen == [(want, want, want, None)]
    assert out.shape == long.shape
    module.apply(params, long[:, :24])
    module.apply(params, long, mask=jnp.ones(long.shape[:2], bool))
    assert len(seen) == 1
    forced = MultiHeadSelfAttention(heads=2, dtype=jnp.float32, use_flash=True)
    forced.apply(params, long[:, :24])
    assert seen[1:] == [((1, 24, 2, 8),) * 3 + (True,)]


def _case_parameter_tree():
    """Names, shapes and dtypes, letter for letter: `benchmark/inputs.py`
    builds them, `benchmark/reference/bert.py` reads them, saved bundles
    hold them."""
    module = MultiHeadSelfAttention(heads=12)
    x = jnp.ones((2, 48, 768), jnp.bfloat16)
    tree = jax.tree_util.tree_map(
        lambda a: (a.shape, str(a.dtype)),
        jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x)),
    )
    assert tree == {
        "params": {
            "qkv": {
                "kernel": ((768, 3, 12, 64), "float32"),
                "bias": ((3, 12, 64), "float32"),
            },
            "out": {
                "kernel": ((12, 64, 768), "float32"),
                "bias": ((768,), "float32"),
            },
        }
    }


def _case_parent_tree():
    """A tree the parent's module initialised is the tree this module
    initialises, value for value, and gives the parent's output."""
    x, _ = _inputs()
    parent = _ParentModule(heads=_H)
    saved = parent.init(jax.random.PRNGKey(7), x)
    module = MultiHeadSelfAttention(heads=_H, dtype=jnp.float32)
    fresh = module.init(jax.random.PRNGKey(7), x)
    assert jax.tree_util.tree_structure(saved) == jax.tree_util.tree_structure(
        fresh
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(saved), jax.tree_util.tree_leaves(fresh)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    saved["params"]["qkv"]["bias"] = jax.random.normal(
        jax.random.PRNGKey(8), (3, _H, _D)
    )
    np.testing.assert_allclose(
        np.asarray(module.apply(saved, x)),
        np.asarray(parent.apply(saved, x)),
        atol=1e-5,
    )


@pytest.mark.parametrize(
    "case",
    [
        _case_forward_plain,
        _case_forward_masked,
        _case_forward_bf16,
        _case_grads_qkv,
        _case_grads_kernels,
        _case_module_forward,
        _case_weight_dropout,
        _case_attend_fn_boundary,
        _case_flash_boundary,
        _case_parameter_tree,
        _case_parent_tree,
    ],
    ids=lambda f: f.__name__.removeprefix("_case_"),
)
def test_dense_path_and_module_contract(case):
    case()
