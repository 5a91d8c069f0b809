"""The double-gated short convolution of the LFM2 family (Liquid AI's
hybrid decoders; ``model_type: lfm2`` / ``lfm2_moe``), the token mixer of
three layers in four there: a causal DEPTHWISE convolution over positions,
a few taps wide, gated before and after.

With ``(B, C, u)`` the three parts of the input projection's output, each
``[.., S, d]``, and ``w`` the taps ``[L, d]`` (one filter a channel, no
bias):

    z[t] = sum_{j < L} w[j] * (B * u)[t - (L - 1) + j]
    y[t] = C[t] * z[t]

with zeros left of position 0: a sequence is one history, and the
convolution never reads across a history's start. It is causal (a
position reads itself and the ``L - 1`` before it), so rows padded behind
a short history change no answer.

The two gates and the taps are float32 multiply-adds on SHIFTED views of
the gated input, written so that XLA fuses them into one elementwise pass
from the projection's output to the output projection's operand: no
`lax.conv` (a depthwise convolution of width 3 would be lowered to one
anyway) and no float32 copy of ``[T, 3 d]`` in HBM. The projections are
the caller's (`models/lfm2_moe.py`, scopes ``conv_in`` and ``conv_out``).

Beside it `causal_conv`, the PLAIN causal depthwise convolution of the
Mamba family (`models/mamba2.py`, scope ``ssm_conv``): the same shifted
multiply-adds over one input with no gate, a bias a channel, and SiLU
after:

    y[t] = silu(bias + sum_{j < L} w[j] * x[t - (L - 1) + j])
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("short_conv")
def short_conv(bcu: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """``bcu`` ``[B, S, 3 d]`` (the input projection's output: the in-gate,
    the out-gate and the signal, in that order), ``taps`` ``[L, d]`` ->
    ``[B, S, d]`` in ``bcu``'s dtype; every product and sum in float32."""
    width, channels = taps.shape
    if bcu.shape[-1] != 3 * channels:
        raise ValueError(f"{bcu.shape[-1]} projected channels for taps of {channels}")
    seq = bcu.shape[1]
    in_gate, out_gate, signal = (
        part.astype(jnp.float32) for part in jnp.split(bcu, 3, axis=-1)
    )
    gated = jnp.pad(in_gate * signal, ((0, 0), (width - 1, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    mixed = sum(taps[j] * gated[:, j : j + seq] for j in range(width))
    return (out_gate * mixed).astype(bcu.dtype)


def causal_conv(x: jnp.ndarray, taps: jnp.ndarray, bias: jnp.ndarray) -> jnp.ndarray:
    """``x`` ``[B, S, d]``, ``taps`` ``[L, d]``, ``bias`` ``[d]`` -> float32
    ``[B, S, d]``: what follows (`ops/ssd.py`) rounds each part once, as
    its products' operand. Zeros left of position 0."""
    width, channels = taps.shape
    if x.shape[-1] != channels or bias.shape != (channels,):
        raise ValueError(f"{x.shape[-1]} channels, a bias of {bias.shape}, taps of {channels}")
    seq = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    mixed = sum(taps[j] * padded[:, j : j + seq] for j in range(width))
    return jax.nn.silu(mixed + bias.astype(jnp.float32))
