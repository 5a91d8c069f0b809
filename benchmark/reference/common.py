"""Shared arithmetic of the plain references: float32 ``jax.numpy`` with
every product at ``highest`` precision, and the 8-bit-float rounding that
serves as the control.

``precision``:

- ``"f32"``: the reference proper. On a TPU a float32 product runs in
  bfloat16 passes unless ``highest`` is asked for, so every ``einsum`` asks.
- ``"fp8"``: the control, put in the program's place to show that the
  comparison fails a lower precision than the configuration states
  (bfloat16 -> an 8-bit float). Both operands of every product are rounded
  to the 4 significant bits of e4m3; sums and everything else stay float32.
  (An int8 stand-in, 255 levels against each row's and each output
  channel's largest magnitude, was tried first: on ``bert-base`` it reads
  only 2.2 to 7.7 times what the bfloat16 program reads, seed by seed, too
  close to set a limit between; readings in PERF.md, PR 24.)

Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6  # flax.linen.LayerNorm's default, which the program uses


def round_e4m3(x):
    """Round to 4 significant bits (e4m3's mantissa) by plain arithmetic, so
    that it runs wherever float32 does; the exponent's range is not cut."""
    mantissa, exponent = jnp.frexp(x)  # mantissa in [0.5, 1)
    return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent)


def product(spec: str, a, b, precision: str):
    """``einsum`` of two operands at the stated precision."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "fp8":
        a, b = round_e4m3(a), round_e4m3(b)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, p):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_tanh(x):
    """The tanh form, which flax's ``nn.gelu`` defaults to."""
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def served_probability(logits, temperature):
    """Calibration as the bundle applies it: sigmoid(logit / T)."""
    return jax.nn.sigmoid(logits / temperature)


def in_blocks(fn, block_rows: int, cat, num):
    """Run ``fn(cat, num)`` over row blocks of one fixed size (the last one
    padded with its first row) so that one compiled program serves any
    number of rows and the activations fit beside what else is resident."""
    n = cat.shape[0]
    out = np.empty(n, np.float32)
    for start in range(0, n, block_rows):
        stop = min(start + block_rows, n)
        c, x = cat[start:stop], num[start:stop]
        if stop - start < block_rows:
            pad = block_rows - (stop - start)
            c = np.concatenate([c, np.repeat(c[:1], pad, axis=0)])
            x = np.concatenate([x, np.repeat(x[:1], pad, axis=0)])
        out[start:stop] = np.asarray(fn(c, x))[: stop - start]
    return out
