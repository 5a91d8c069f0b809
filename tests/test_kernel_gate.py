"""`ops/kernel_gate.py tpu_kernel_forward`, the scaffold of every kernel
whose backward is its XLA form's, on a toy pair: a "kernel" that answers
differently from its XLA form (so the test sees which one ran) and an XLA
form with an integer operand."""

import jax
import jax.numpy as jnp
import numpy as np

from mlops_tpu.ops.kernel_gate import tpu_kernel_forward


def _pair():
    """(kernel, XLA form, how often each body was traced)."""
    traced = {"kernel": 0, "xla": 0}

    def kernel(x, n, scale):
        traced["kernel"] += 1
        return x * scale + 1.0

    def xla_form(x, n, scale):
        traced["xla"] += 1
        return jnp.sin(x) * scale * n.astype(x.dtype).sum()

    return kernel, xla_form, traced


def _operands():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)), jnp.float32)
    return x, jnp.arange(1, 4, dtype=jnp.int32)


def test_lowered_for_the_cpu_it_answers_as_the_xla_form():
    kernel, xla_form, _ = _pair()
    gated = tpu_kernel_forward(kernel, xla_form, static_argnames=("scale",))
    x, n = _operands()
    run = jax.jit(lambda x, n: gated(x, n, scale=0.5))
    assert "tpu_custom_call" not in run.lower(x, n).as_text()
    np.testing.assert_allclose(run(x, n), xla_form(x, n, 0.5), rtol=1e-6)


def test_its_gradient_is_the_xla_forms_and_an_integer_operand_gets_float0():
    kernel, xla_form, _ = _pair()
    gated = tpu_kernel_forward(kernel, xla_form, static_argnames=("scale",))
    x, n = _operands()
    g = jnp.ones_like(x)
    _, pull = jax.vjp(lambda x, n: gated(x, n, scale=0.5), x, n)
    _, pull_xla = jax.vjp(lambda x, n: xla_form(x, n, 0.5), x, n)
    dx, dn = pull(g)
    np.testing.assert_allclose(dx, pull_xla(g)[0], rtol=1e-6)
    np.testing.assert_allclose(dx, np.cos(x) * 0.5 * 6.0, rtol=1e-6)
    assert dn.dtype == jax.dtypes.float0 and dn.shape == n.shape
    grad = jax.grad(lambda x: gated(x, n, scale=0.5).sum())(x)
    np.testing.assert_allclose(grad, dx, rtol=1e-6)


def test_a_model_traces_the_kernel_once_for_all_its_layers():
    """Three layers' same-shape calls inside one program trace the kernel's
    Python body ONCE (a kernel's body is hundreds of operations of tracing:
    unjitted, eight layers traced it eight times and a process's set-up
    paid 18 s, PERF.md section 6). A new shape or a new static
    argument is a new trace."""
    kernel, xla_form, traced = _pair()
    gated = tpu_kernel_forward(kernel, xla_form, static_argnames=("scale",))
    x, n = _operands()

    def layers(x, n):
        for _ in range(3):
            x = gated(x, n, scale=0.5)
        return x

    jax.jit(layers)(x, n).block_until_ready()
    assert traced["kernel"] == 1, traced
    jax.jit(lambda x, n: gated(gated(x, n, scale=0.5), n, scale=0.25))(x, n)
    assert traced["kernel"] == 2, traced
    text = jax.jit(layers).lower(x, n).as_text()
    assert text.count("func.func private @_kernel_or_xla") == 1, text
